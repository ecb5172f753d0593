"""Memory-pressure governor: budgeted admission, an OOM recovery ladder,
and host-spilled hierarchies for graphs bigger than HBM.

The source paper's headline claim is bounded-memory scale (~300 GiB of
host RAM for 112B edges); ROADMAP item 4 maps that onto this repo via
the semi-external partitioning literature (arXiv 1404.4887): keep the
fine graph host-resident and stream work to the device.  Before this
module the system had the opposite failure mode — a ``DeviceOOM`` was
*classified* (resilience/errors.py) but only ever handled as a one-shot
site fallback, the whole multilevel hierarchy stayed device-resident for
the entire run, and the serving layer admitted requests with zero memory
model.  The governor turns the PR-7 observability (per-level
``buffer_bytes`` accounting, barrier memory watermarks,
``KAMINPAR_TPU_HBM_BYTES``) into a hard robustness contract:

    **a run either fits its declared memory budget or degrades through a
    deterministic ladder — it never dies with RESOURCE_EXHAUSTED.**

Three mechanisms, one module:

  * **budget + estimator** — :func:`estimate_run_bytes` is a calibrated
    per-phase peak-bytes model for a padded bucket ``(n_pad, m_pad,
    k_pad)`` (coefficients anchored to the coarsener's per-level
    ``buffer_bytes`` accounting and validated against measured
    watermarks in tests/test_memory.py).  It is enforced at two points:
    serving admission (structured ``insufficient-memory`` rejection,
    sized WITHOUT loading the graph) and :func:`preflight` in the
    shm/dist drivers before the device upload.
  * **OOM recovery ladder** — :func:`run_ladder` wraps the facade's core
    partition call.  On a classified ``DeviceOOM`` anywhere under
    ``compute_partition`` it unwinds cleanly (force-closes timer scopes
    opened by the failed attempt via the PR-5 ``Timer.unwind`` idiom,
    sheds the registered bounded caches with ``evict_to``, collects
    garbage) and retries at the next rung:

      ====  =========================================================
      rung  behavior
      ====  =========================================================
      0     normal run (power-of-two shape buckets, resident hierarchy)
      1     tight padding buckets (``caching.pad_policy_scope("tight")``)
      2     \\+ host-spilled hierarchy: coarse levels are dropped from
            device memory at the checkpoint barriers and re-uploaded on
            demand during uncoarsening (cut-identical by construction —
            deterministic pad buckets, same arrays)
      3     semi-external: the fine graph is coarsened HOST-side in
            node-range chunks (the ``io/compressed_binary`` /
            ``device_graph_from_compressed`` edge-block idiom) until the
            coarse graph fits the budget; only the coarse graph and the
            partition vector are ever device-resident
      4     host-only: recursive bisection on the host, no device at all
      ====  =========================================================

    Each engaged rung emits a ``degraded`` telemetry event carrying the
    rung id; the run report gains a ``memory_budget`` section (budget,
    estimate, watermark, rung, spill bytes/reloads).  Only when EVERY
    rung fails is the ``DeviceOOM`` re-raised with
    ``rungs_exhausted=True`` — the one crash-shaped verdict the serving
    per-class breaker may latch on.
  * **proactive pressure** — :func:`on_barrier` (called from the PR-5
    checkpoint barrier hook) compares the live-device-bytes watermark
    against the budget and triggers the rung-2 spill *before* an
    allocation fails, so the common case is graceful, not reactive.

Dormancy contract (pinned by tests/test_memory.py's jaxpr-equality
test): with no declared budget and no ``DeviceOOM`` in flight the
governor is two attribute reads per barrier and a try/except around the
core partition call — jaxprs and cuts are bitwise-identical to a
governor-free build.  ``KAMINPAR_TPU_MEM_GOVERNOR=0`` disables even the
ladder (raw allocator behavior, for debugging).
"""

from __future__ import annotations

import gc
import os
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import runstate
from .errors import DeviceOOM, classify

#: Kill switch: =0 disables the governor entirely (no ladder, no
#: pressure hook, no admission rule) — raw allocator behavior.
ENV_GOVERNOR = "KAMINPAR_TPU_MEM_GOVERNOR"
#: The declared device-memory budget in bytes (shared with the PR-7
#: observability override — declaring a ceiling now also enforces it).
ENV_BUDGET = "KAMINPAR_TPU_HBM_BYTES"
#: Test hook: force the ladder to START at rung N (0-4).
ENV_FORCE_RUNG = "KAMINPAR_TPU_MEM_RUNG"

#: The ladder's rungs, in engagement order.
RUNG_NORMAL = 0
RUNG_TIGHT_PADS = 1
RUNG_SPILL_HIERARCHY = 2
RUNG_SEMI_EXTERNAL = 3
RUNG_HOST_ONLY = 4

RUNG_NAMES = {
    RUNG_NORMAL: "normal",
    RUNG_TIGHT_PADS: "tight-pads",
    RUNG_SPILL_HIERARCHY: "spill-hierarchy",
    RUNG_SEMI_EXTERNAL: "semi-external",
    RUNG_HOST_ONLY: "host-only",
}

#: Fraction of the budget at which the barrier pressure hook starts
#: shedding caches and spilling hierarchy levels proactively.
PRESSURE_FRACTION = 0.9
#: The semi-external coarsening target: the coarse graph's (spilled-mode)
#: estimate must fit this fraction of the budget before the device
#: pipeline takes over.
STREAM_TARGET_FRACTION = 0.8

# ---------------------------------------------------------------------------
# the peak-bytes estimator
# ---------------------------------------------------------------------------
#
# Calibration (tests/test_memory.py::test_estimator_vs_watermark): the
# model must bound the measured live-device-bytes watermark from above
# (an under-estimate would admit a run the budget cannot hold) while
# staying within 2x of it on the bench shapes (a wild over-estimate
# would reject servable requests).  The resident term is anchored to the
# coarsener's per-level `buffer_bytes` accounting (row_ptr + src + dst +
# edge_w + node_w + cmap); the transient term covers the LP / contraction
# working arrays XLA keeps live between launches (labels, ratings,
# aggregation keys — all n_pad- or m_pad-shaped int32).

#: Resident hierarchy factor over the finest level's CSR.  Levels
#: shrink fast enough (forced-shrink retries, the limping-tail cutoff)
#: that the barrier-sampled watermark sits near ONE fine CSR; 1.5x
#: prices the hierarchy sum with the safety margin the never-under
#: contract needs (calibrated in tests/test_memory.py: the estimate
#: must stay within [1x, 2x] of the measured watermark).
HIERARCHY_FACTOR = 1.5
#: Rung-2 resident factor: the working level + the neighbor being
#: reloaded stay device-resident; the rest of the hierarchy is host.
SPILL_RESIDENT_FACTOR = 1.2
#: n_pad-shaped int32 working arrays live across launches (labels,
#: partition, active sets).
NODE_WORK_ARRAYS = 2
#: m_pad-shaped int32 working arrays held across launches (ratings /
#: aggregation outputs of the contraction).
EDGE_WORK_ARRAYS = 1
#: k_pad-shaped tables (block weights, caps, per-block gains), int64.
K_TABLE_ARRAYS = 8


def _weight_itemsize() -> int:
    try:
        from ..dtypes import WEIGHT_DTYPE

        return int(np.dtype(WEIGHT_DTYPE).itemsize)
    except Exception:
        return 4


def padded_bucket(n: int, m: int, k: int,
                  mode: str = "bucketed") -> Tuple[int, int, int]:
    """The executable-identity bucket ``(n_pad, m_pad, k_pad)`` the run
    would occupy under a pad policy — the unit the estimator prices."""
    from .. import caching

    with caching.pad_policy_scope(mode):
        try:
            from ..graphs.csr import shape_floors

            n_floor, m_floor = shape_floors()
        except Exception:
            n_floor, m_floor = 256, 256
        n_pad = caching.pad_size(int(n) + 1, n_floor)
        m_pad = caching.pad_size(max(int(m), 1), m_floor)
        k_pad = caching.pad_k(max(int(k), 1))
    return n_pad, m_pad, k_pad


def device_csr_bytes(n_pad: int, m_pad: int) -> int:
    """Bytes of one padded device CSR+COO level (the same arrays the
    coarsener's `buffer_bytes` level events count: row_ptr, src, dst,
    edge_w, node_w)."""
    w = _weight_itemsize()
    return 4 * (n_pad + 1) + n_pad * (4 + w) + m_pad * (8 + w)


def estimate_rung_bytes(rung: int, n: int, m: int, k: int) -> int:
    """Peak device bytes of a run at a given ladder rung.

    Rungs 0/1 price the fully resident hierarchy; rungs 2 AND 3 price
    the spilled hierarchy of the graph actually handed to the device —
    at rung 3 that is the coarse graph the host-side coarsening
    produced, and its preflight must price what is really uploaded;
    rung 4 is host-only.  Whether rung 3 can fit a FINE graph at all is
    a different question (the host coarsening shrinks until it fits) —
    :func:`rung_fits` answers that one."""
    if rung >= RUNG_HOST_ONLY:
        return 0
    mode = "bucketed" if rung == RUNG_NORMAL else "tight"
    n_pad, m_pad, k_pad = padded_bucket(n, m, k, mode)
    csr = device_csr_bytes(n_pad, m_pad)
    transient = (
        NODE_WORK_ARRAYS * n_pad * 4
        + EDGE_WORK_ARRAYS * m_pad * 4
        + K_TABLE_ARRAYS * k_pad * 8
    )
    if rung <= RUNG_TIGHT_PADS:
        resident = HIERARCHY_FACTOR * csr
    else:  # spilled hierarchy: working level + the neighbor reloading
        resident = SPILL_RESIDENT_FACTOR * csr
    return int(resident + transient)


def rung_fits(rung: int, n: int, m: int, k: int, budget: int) -> bool:
    """Whether a run over (n, m, k) can fit ``budget`` at a rung.  For
    rungs 0-2 that is the rung estimate itself; rung 3 fits whenever
    the SMALLEST possible device graph (the floor bucket) does — the
    host-side coarsening shrinks the graph until its device share fits;
    rung 4 (host-only) always fits."""
    if rung >= RUNG_HOST_ONLY:
        return True
    if rung == RUNG_SEMI_EXTERNAL:
        fn, fm, fk = padded_bucket(0, 0, k, "tight")
        floor = (
            SPILL_RESIDENT_FACTOR * device_csr_bytes(fn, fm)
            + NODE_WORK_ARRAYS * fn * 4 + EDGE_WORK_ARRAYS * fm * 4
            + K_TABLE_ARRAYS * fk * 8
        )
        return floor <= budget
    return estimate_rung_bytes(rung, n, m, k) <= budget


def estimate_run_bytes(n: int, m: int, k: int, ctx: Any = None) -> int:
    """The admission/report figure: estimated peak device bytes of a
    normal (rung-0) run for the padded bucket of ``(n, m, k)``.  ``ctx``
    is accepted for signature stability (the model currently depends on
    the partition target only through k)."""
    del ctx
    return estimate_rung_bytes(RUNG_NORMAL, n, m, k)


def estimate_stream_bytes(n: int, chunk_edges: int, k: int) -> int:
    """Peak device bytes of the OUT-OF-CORE stream phase
    (external/stream_coarsen.py): two in-flight padded edge-block chunk
    buffers (src_local + dst + weights — the double buffer the async
    dispatch queue holds) plus the fine-level O(n) vectors (labels,
    wanted, cluster weights, node weights, cluster map) and the k
    tables.  This is the figure the external driver shrinks its chunk
    target against, and the serving admission price of an
    external-scheme request — NOT a full-graph estimate, which is
    exactly what the scheme exists to avoid."""
    from .. import caching

    w = _weight_itemsize()
    e_pad = caching.pad_size(max(int(chunk_edges), 1), 4096)
    chunk = e_pad * (4 + 4 + w)
    vectors = int(n) * (4 + 4 + 4 + 2 * w)
    k_pad = caching.pad_k(max(int(k), 1))
    return int(2 * chunk + vectors + K_TABLE_ARRAYS * k_pad * 8)


def min_streamable_bytes(n: int, k: int) -> int:
    """The smallest budget the external scheme can stream a graph under
    (the floor chunk target) — the admission rule for `--scheme
    external` requests: below this not even the O(n) vectors + one
    floor chunk fit, so the request is structurally unserveable."""
    return estimate_stream_bytes(n, 1 << 15, k)


def min_serveable_bytes(n: int, m: int, k: int) -> int:
    """The smallest budget a request can be served DEVICE-RESIDENT under
    (the rung-2 spilled-hierarchy estimate) — the serving admission
    rule: below this, only the streamed/host rungs could run it, which a
    latency-bound service rejects instead (``insufficient-memory``);
    single-shot CLI runs still degrade through all rungs."""
    return estimate_rung_bytes(RUNG_SPILL_HIERARCHY, n, m, k)


# ---------------------------------------------------------------------------
# budget + per-run governor state
# ---------------------------------------------------------------------------


def governor_enabled() -> bool:
    """False only under the KAMINPAR_TPU_MEM_GOVERNOR=0 kill switch."""
    return os.environ.get(ENV_GOVERNOR, "") != "0"


def budget_bytes(ctx: Any = None) -> Optional[int]:
    """The DECLARED device-memory budget: ``ctx.resilience.memory_budget``
    first (the ``--memory-budget`` flag), else ``KAMINPAR_TPU_HBM_BYTES``.
    None when no budget was declared — the ladder still catches OOMs,
    but admission/preflight/pressure have nothing to enforce.  The
    backend's own ``bytes_limit`` is deliberately NOT used here: the
    contract is about a budget the operator declared, and the
    observability layer already reports headroom against the backend
    limit."""
    if ctx is not None:
        res = getattr(ctx, "resilience", None)
        if res is None:  # DistContext nests the shm tree
            res = getattr(getattr(ctx, "shm", None), "resilience", None)
        declared = float(getattr(res, "memory_budget", 0.0) or 0.0)
        if declared > 0:
            return int(declared)
    raw = os.environ.get(ENV_BUDGET, "")
    if raw:
        try:
            return int(float(raw))
        except ValueError:
            return None
    return None


def forced_rung() -> Optional[int]:
    """The KAMINPAR_TPU_MEM_RUNG test hook (None when unset)."""
    raw = os.environ.get(ENV_FORCE_RUNG, "")
    if not raw:
        return None
    try:
        return max(RUNG_NORMAL, min(RUNG_HOST_ONLY, int(raw)))
    except ValueError:
        return None


class GovernorState:
    """One run's memory-governor state (lives on the thread-local
    RunState, so serving requests can never observe each other's rung or
    spill accounting)."""

    __slots__ = (
        "budget", "rung", "initial_rung", "estimate", "bucket",
        "watermark", "pressure_events", "spills", "spill_bytes",
        "reloads", "reload_bytes", "shed_bytes", "exhausted",
        "engaged", "spiller", "graph_shape",
    )

    def __init__(self) -> None:
        self.budget: Optional[int] = None
        self.rung: int = RUNG_NORMAL
        self.initial_rung: int = RUNG_NORMAL
        self.estimate: Optional[int] = None
        self.bucket: str = ""
        self.watermark: int = 0
        self.pressure_events: int = 0
        self.spills: int = 0
        self.spill_bytes: int = 0
        self.reloads: int = 0
        self.reload_bytes: int = 0
        self.shed_bytes: int = 0
        self.exhausted: bool = False
        self.engaged: bool = False  # any rung > 0 or pressure action
        self.spiller: Optional[weakref.ref] = None
        self.graph_shape: Tuple[int, int, int] = (0, 0, 0)


def state() -> Optional[GovernorState]:
    """The calling thread's governor state, or None when no run armed
    one (nested runs, library use without the facade)."""
    return getattr(runstate.current(), "memory", None)


def _ensure_state() -> GovernorState:
    run = runstate.current()
    st = getattr(run, "memory", None)
    if st is None:
        st = GovernorState()
        run.memory = st
    return st


def begin_run(graph: Any, ctx: Any,
              price_shape: Optional[Tuple[int, int]] = None
              ) -> Optional[GovernorState]:
    """Arm the governor for one stream-owning run (facade entry): price
    the run, pick the starting rung (the forced test rung, else the
    lowest rung whose estimate fits the declared budget), and emit the
    `memory-budget` telemetry event when a budget is in force.  Returns
    None (and stays dormant) under the kill switch.

    ``price_shape=(n, m)`` overrides the PRICED shape: the dist driver
    passes its sharding plan's actual max padded shard (the budget is
    per-device and the node/edge arrays shard across the mesh — pricing
    the whole graph would refuse or over-rung a multi-chip run that
    fits after sharding, and pricing ``ceil/devices`` would undercount
    the heaviest rank of a skewed edge distribution)."""
    if not governor_enabled():
        run = runstate.current()
        run.memory = None
        return None
    st = GovernorState()
    runstate.current().memory = st
    st.budget = budget_bytes(ctx)
    if price_shape is not None:
        n, m = int(price_shape[0]), int(price_shape[1])
    else:
        n, m = int(graph.n), int(graph.m)
    k = int(getattr(ctx.partition, "k", 2) or 2)
    st.graph_shape = (n, m, k)
    st.bucket = "/".join(str(x) for x in padded_bucket(n, m, k))
    st.estimate = estimate_run_bytes(n, m, k)
    start = RUNG_NORMAL
    if st.budget:
        while (
            start < RUNG_HOST_ONLY
            and not rung_fits(start, n, m, k, st.budget)
        ):
            start += 1
    hook = forced_rung()
    if hook is not None:
        start = hook
    st.rung = st.initial_rung = start
    if start > RUNG_NORMAL:
        st.engaged = True
        _emit_rung_event(
            st, error="MemoryBudgetExceeded",
            detail=(
                f"rung-0 estimate {st.estimate} > budget {st.budget}"
                if hook is None else f"{ENV_FORCE_RUNG}={hook}"
            ),
            injected=hook is not None,
        )
    if st.budget or start:
        from .. import telemetry

        telemetry.event(
            "memory-budget",
            budget_bytes=st.budget,
            estimate_bytes=st.estimate,
            bucket=st.bucket,
            rung=st.rung,
        )
    return st


def register_spiller(coarsener: Any) -> None:
    """The active multilevel coarsener registers itself so the pressure
    hook can ask it to shed hierarchy levels (weakly referenced — the
    governor must never keep a dead hierarchy alive)."""
    st = state()
    if st is not None:
        st.spiller = weakref.ref(coarsener)


def note_spill(nbytes: int) -> None:
    st = state()
    if st is not None:
        st.spills += 1
        st.spill_bytes += int(nbytes)
        st.engaged = True


def note_reload(nbytes: int) -> None:
    st = state()
    if st is not None:
        st.reloads += 1
        st.reload_bytes += int(nbytes)


# ---------------------------------------------------------------------------
# cache shedding
# ---------------------------------------------------------------------------

#: Weakly-held BoundedCaches the governor may shed under pressure (the
#: serving result cache registers itself; future executable caches too).
_shed_targets: "weakref.WeakSet" = weakref.WeakSet()


def register_shed_target(cache: Any) -> None:
    """Register a BoundedCache-shaped object (``evict_to(target_bytes)``)
    for pressure shedding.  Weak: caches die with their owners."""
    _shed_targets.add(cache)


def shed_caches(target_bytes: int = 0) -> int:
    """Evict every registered cache down to ``target_bytes`` (pressure
    cause).  Returns the cache bytes freed."""
    freed = 0
    for cache in list(_shed_targets):
        try:
            freed += int(cache.evict_to(target_bytes, cause="pressure"))
        except Exception:
            continue
    st = state()
    if st is not None:
        st.shed_bytes += freed
    return freed


def _live_device_bytes() -> int:
    from ..utils import heap_profiler

    return int(heap_profiler.live_device_bytes())


def on_barrier(stage: str, live_bytes: Optional[int] = None) -> None:
    """The proactive-pressure hook, called from the PR-5 checkpoint
    barrier (host side, between launches).  Two attribute reads when the
    governor is dormant.  With a budget in force: track the watermark,
    and once live bytes cross PRESSURE_FRACTION of the budget shed the
    registered caches and spill cold hierarchy levels BEFORE the
    allocator fails.  ``live_bytes`` lets the barrier share the perf
    observatory's live-array sample instead of walking jax.live_arrays
    a second time in the same call."""
    st = state()
    if st is None:
        return
    if st.rung >= RUNG_SPILL_HIERARCHY:
        # rung-2+ runs keep the hierarchy host-spilled unconditionally
        self_spill = st.spiller() if st.spiller is not None else None
        if self_spill is not None:
            self_spill.spill_cold_levels()
    if not st.budget:
        return
    live = (
        int(live_bytes) if live_bytes is not None else _live_device_bytes()
    )
    if live > st.watermark:
        st.watermark = live
    if live <= PRESSURE_FRACTION * st.budget:
        return
    st.pressure_events += 1
    st.engaged = True
    freed = shed_caches(0)
    spilled = 0
    spiller = st.spiller() if st.spiller is not None else None
    if spiller is not None:
        spilled = spiller.spill_cold_levels()
    from .. import telemetry
    from ..utils.logger import log_warning

    telemetry.event(
        "memory-pressure",
        stage=stage,
        live_bytes=live,
        budget_bytes=st.budget,
        cache_bytes_freed=freed,
        spill_bytes=spilled,
    )
    log_warning(
        f"memory pressure at {stage}: live {live} > "
        f"{PRESSURE_FRACTION:.0%} of budget {st.budget} — shed {freed} "
        f"cache bytes, spilled {spilled} hierarchy bytes"
    )


def preflight(n: int, m: int, k: int, where: str = "") -> None:
    """The pre-upload budget check (shm/dist drivers, before the device
    upload): raises a ladder-retryable DeviceOOM when the CURRENT rung's
    estimate cannot fit the declared budget — the allocation is refused
    before a single byte lands on the device, and the facade's ladder
    moves to the next rung.  Dormant without a budget."""
    st = state()
    if st is None or not st.budget:
        return
    est = estimate_rung_bytes(st.rung, n, m, k)
    if est <= st.budget:
        return
    raise DeviceOOM(
        f"preflight{'@' + where if where else ''}: rung-{st.rung} "
        f"estimate {est} bytes exceeds the declared budget "
        f"{st.budget} bytes (n={n}, m={m}, k={k})",
        site="device-oom",
    )


# ---------------------------------------------------------------------------
# the recovery ladder
# ---------------------------------------------------------------------------


def _emit_rung_event(st: GovernorState, error: str, detail: str,
                     injected: bool = False,
                     triggering_rank: Optional[int] = None) -> None:
    from .. import telemetry
    from ..utils.logger import log_warning
    from .faults import SITES

    spec = SITES.get("device-oom")
    attrs = dict(
        site="device-oom",
        error=error,
        detail=detail[:300],
        fallback=spec.fallback if spec else "recovery ladder",
        attempts=st.rung,
        breaker_open=False,
        injected=injected,
        rung=st.rung,
        rung_name=RUNG_NAMES.get(st.rung, str(st.rung)),
    )
    if triggering_rank is not None:
        # agreed dist transitions name the rank whose proposal pulled
        # the fleet to this rung (shm transitions omit the key)
        attrs["triggering_rank"] = int(triggering_rank)
    telemetry.event("degraded", **attrs)
    log_warning(
        f"memory governor: {error} ({detail[:120]}); retrying at rung "
        f"{st.rung} ({RUNG_NAMES.get(st.rung)})"
        + (
            "" if triggering_rank is None
            else f" [agreed; triggered by rank {triggering_rank}]"
        )
    )


def _recover(st: GovernorState, depth: int, err: DeviceOOM) -> None:
    """Unwind one failed rung attempt: force-close the timer scopes it
    left open (Timer.unwind_to — the exception already closed scoped
    ones; this catches scopes opened by code that died between
    __enter__s), shed the bounded caches, and collect
    garbage so the dead attempt's device arrays are actually freed
    before the next rung allocates."""
    from ..utils import timer

    timer.GLOBAL_TIMER.unwind_to(depth)
    shed_caches(0)
    if st.rung >= RUNG_SPILL_HIERARCHY:
        # executables pin device memory too; at the aggressive rungs a
        # recompile is cheaper than another OOM
        try:
            import jax

            jax.clear_caches()
        except Exception:
            pass
    gc.collect()


def run_ladder(attempt: Callable[[], np.ndarray], graph: Any, ctx: Any,
               facade: Any) -> np.ndarray:
    """Run the core partition under the OOM recovery ladder.

    ``attempt`` is the normal device pipeline (rungs 0-2 re-run it under
    progressively more frugal policies); rungs 3-4 substitute the
    semi-external and host-only paths.  A non-OOM exception propagates
    unchanged on the first bounce — the ladder only ever absorbs
    allocator failure.  When every rung fails the final DeviceOOM is
    re-raised with ``rungs_exhausted=True`` (the serving breaker's one
    legitimate crash signal)."""
    if not governor_enabled():
        return attempt()
    from ..utils import timer

    st = state()
    start = st.rung if st is not None else RUNG_NORMAL
    rung = start
    while True:
        if st is not None:
            st.rung = rung
        depth = len(timer.GLOBAL_TIMER._stack)
        try:
            return _attempt_at_rung(rung, attempt, graph, ctx, facade)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as exc:
            err = classify(exc, site="device-oom")
            if not isinstance(err, DeviceOOM):
                raise
            if st is None:
                st = _ensure_state()
                st.rung = rung
            if rung >= RUNG_HOST_ONLY:
                st.exhausted = True
                err.rungs_exhausted = True
                from .. import telemetry
                from ..utils.logger import log_warning

                # stamp the audit trail NOW — the success-path annotate
                # in the facade is unreachable once this raise unwinds,
                # and `exhausted: true` is exactly the state a post-crash
                # (emergency/serving) report must be able to show
                telemetry.annotate(memory_budget=summary())
                log_warning(
                    "memory governor: recovery ladder EXHAUSTED "
                    f"(host-only rung failed: {err})"
                )
                raise err from exc
            rung += 1
            st.rung = rung
            st.engaged = True
            _recover(st, depth, err)
            _emit_rung_event(
                st, error=type(err).__name__, detail=str(err),
                injected=err.injected,
            )


def _attempt_at_rung(rung: int, attempt: Callable[[], np.ndarray],
                     graph: Any, ctx: Any, facade: Any) -> np.ndarray:
    from .. import caching

    if rung == RUNG_NORMAL:
        return attempt()
    if rung in (RUNG_TIGHT_PADS, RUNG_SPILL_HIERARCHY):
        # rung 2's spilling needs no wrapper here: on_barrier consults
        # the run's rung and spills unconditionally at rung >= 2
        with caching.pad_policy_scope("tight"):
            return attempt()
    if rung == RUNG_SEMI_EXTERNAL:
        with caching.pad_policy_scope("tight"):
            return _semi_external_rung(graph, ctx, facade)
    return host_only_partition(graph, ctx)


def _semi_external_rung(graph: Any, ctx: Any, facade: Any) -> np.ndarray:
    """Rung 3's primary is the DEVICE-STREAMED external subsystem
    (kaminpar_tpu/external/): LP rating + contraction over padded
    edge-block chunks with only the O(n) vectors device-resident — the
    ROADMAP item-4 path at device speed.  The host-only numpy LP loop
    (:func:`semi_external_partition`) is demoted to its FALLBACK: a
    non-OOM failure of the streamed subsystem (missing codec, a
    malformed source) degrades to it with a ``degraded`` event; a
    DeviceOOM propagates so the ladder moves on to host-only."""
    from ..external.driver import external_partition

    try:
        return external_partition(graph, ctx, facade)
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as exc:
        err = classify(exc, site="device-oom")
        if isinstance(err, DeviceOOM):
            raise  # the ladder's business: next rung is host-only
        from .. import telemetry
        from ..utils.logger import log_warning

        telemetry.event(
            "degraded",
            site="semi-external-stream",
            error=type(exc).__name__,
            detail=str(exc)[:300],
            fallback="host-chunked numpy LP (semi_external_partition)",
            attempts=1,
            breaker_open=False,
            injected=False,
        )
        log_warning(
            f"semi-external stream failed ({type(exc).__name__}: "
            f"{str(exc)[:120]}); falling back to the host-chunked LP path"
        )
        return semi_external_partition(graph, ctx, facade)


# ---------------------------------------------------------------------------
# the distributed (cross-rank agreed) ladder
# ---------------------------------------------------------------------------

#: The dist driver's rung order: semi-external is skipped (host-chunked
#: coarsening has no sharded-contraction analog — a dist run that
#: cannot even hold the spilled shard hierarchy goes straight to the
#: host-only path, which needs no device at all).
DIST_RUNG_ORDER = (
    RUNG_NORMAL, RUNG_TIGHT_PADS, RUNG_SPILL_HIERARCHY, RUNG_HOST_ONLY,
)


def _next_dist_rung(rung: int) -> int:
    for r in DIST_RUNG_ORDER:
        if r > rung:
            return r
    return RUNG_HOST_ONLY


def agree_rung(proposed: int) -> Tuple[int, int]:
    """The cross-rank rung agreement: allgather-max over the per-rank
    proposals (the ``deadline.agreed_stop`` idiom, shared through
    resilience/agreement.py) so a DeviceOOM on ANY rank unwinds every
    rank to the same rung instead of deadlocking the survivors inside
    ``shard_map`` collectives.  Returns ``(agreed, triggering_rank)`` —
    the rank whose proposal WAS the max; agreement failure (sick
    control link) degrades to the local proposal."""
    from .agreement import agree_max, rank

    try:
        return agree_max(int(proposed))
    except Exception:
        return int(proposed), rank()


def run_dist_ladder(attempt: Callable[[], np.ndarray], graph: Any,
                    ctx: Any, solver: Any) -> np.ndarray:
    """The dist facade's OOM recovery ladder (the :func:`run_ladder`
    twin with cross-rank agreed rung transitions).

    Rungs: 0 normal -> 1 tight pads -> 2 tight pads + host-spilled
    shard hierarchy (the dist driver registers itself as the spiller:
    per-level DistGraphs are dropped at the barriers and rebuilt
    deterministically on demand during uncoarsening — cut-identical by
    construction) -> 4 host-only recursive bisection.  On a classified
    DeviceOOM the failing rank PROPOSES the next rung and every rank
    adopts the allgather-max (:func:`agree_rung`); the ``degraded``
    event carries the triggering rank.  Rung exhaustion re-raises with
    ``rungs_exhausted=True``, exactly like the shm ladder.

    Multi-process caveat: the agreement gather is only symmetric when
    EVERY rank's attempt raised — which is how allocator failure
    surfaces under jax's distributed runtime (a collective whose peer
    died aborts on the survivors, so each process's attempt() raises
    and each enters this except path in the same ladder round).  A rank
    that fails WITHOUT surfacing fleet-wide is outside this protocol's
    reach; the divergence sentinel at the next barrier (agreed rung is
    one of its audited fields) is the backstop that converts that into
    a structured RankDivergence instead of a silent hang."""
    if not governor_enabled():
        return attempt()
    from ..utils import timer

    st = state()
    rung = st.rung if st is not None else RUNG_NORMAL
    if rung == RUNG_SEMI_EXTERNAL:
        # the forced-rung test hook (or a budget-driven start rung) may
        # name the shm-only rung: the dist order maps it to host-only
        rung = RUNG_HOST_ONLY
    while True:
        if st is not None:
            st.rung = rung
        depth = len(timer.GLOBAL_TIMER._stack)
        try:
            return _attempt_dist_at_rung(rung, attempt, graph, ctx, solver)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as exc:
            err = classify(exc, site="device-oom")
            if not isinstance(err, DeviceOOM):
                raise
            if st is None:
                st = _ensure_state()
                st.rung = rung
            if rung >= RUNG_HOST_ONLY:
                st.exhausted = True
                err.rungs_exhausted = True
                from .. import telemetry
                from ..utils.logger import log_warning

                telemetry.annotate(memory_budget=summary())
                log_warning(
                    "memory governor: dist recovery ladder EXHAUSTED "
                    f"(host-only rung failed: {err})"
                )
                raise err from exc
            proposed = _next_dist_rung(rung)
            agreed, trig = agree_rung(proposed)
            # never retry BELOW the local proposal (a lagging peer's
            # verdict must not re-run the rung that just OOMed here)
            rung = max(proposed, int(agreed))
            st.rung = rung
            st.engaged = True
            _recover(st, depth, err)
            _emit_rung_event(
                st, error=type(err).__name__, detail=str(err),
                injected=err.injected, triggering_rank=trig,
            )


def _attempt_dist_at_rung(rung: int, attempt: Callable[[], np.ndarray],
                          graph: Any, ctx: Any, solver: Any) -> np.ndarray:
    from .. import caching

    if rung == RUNG_NORMAL:
        return attempt()
    if rung in (RUNG_TIGHT_PADS, RUNG_SPILL_HIERARCHY):
        # rung 2's shard spilling needs no wrapper here: on_barrier
        # consults the run's rung and asks the registered spiller (the
        # dist driver) to drop cold per-level DistGraphs
        with caching.pad_policy_scope("tight"):
            return attempt()
    # host-only takes the SHM context tree (DistContext nests it;
    # ctx.partition already delegates there, but recursive bisection
    # also reads the shm initial-partitioning knobs)
    return host_only_partition(graph, getattr(ctx, "shm", ctx))


# ---------------------------------------------------------------------------
# rung 3: semi-external partitioning (host-chunked coarsening)
# ---------------------------------------------------------------------------


def _node_chunks(graph: Any, chunk_nodes: int):
    """Stream ``(v0, v1, deg, adj, ew)`` node-range blocks of a host or
    compressed graph — the same edge-block idiom as
    ``graphs.csr.device_graph_from_compressed`` and the chunk-streamed
    gate recompute: peak host memory is one block, never the flat edge
    list (for compressed inputs)."""
    n = int(graph.n)
    from ..graphs.compressed import CompressedHostGraph

    if isinstance(graph, CompressedHostGraph):
        for v0 in range(0, n, chunk_nodes):
            v1 = min(n, v0 + chunk_nodes)
            xr, adj, ew = graph.decode_range(v0, v1)
            deg = np.diff(np.asarray(xr, dtype=np.int64))
            yield v0, v1, deg, np.asarray(adj), (
                None if ew is None else np.asarray(ew)
            )
    else:
        xadj = np.asarray(graph.xadj, dtype=np.int64)
        ew_all = graph.edge_weights
        for v0 in range(0, n, chunk_nodes):
            v1 = min(n, v0 + chunk_nodes)
            lo, hi = int(xadj[v0]), int(xadj[v1])
            deg = np.diff(xadj[v0: v1 + 1])
            yield v0, v1, deg, np.asarray(graph.adjncy[lo:hi]), (
                None if ew_all is None else np.asarray(ew_all[lo:hi])
            )


# pure-host numpy kernel: the np.asarray casts view host-resident chunk
# arrays (the semi-external graph never touches the device), so calling
# this inside a timed span introduces no hidden device sync.
# tpulint: disable=R1
def _host_lp_cluster(graph: Any, max_cluster_weight: int,
                     num_iterations: int = 2,
                     chunk_nodes: int = 1 << 17) -> np.ndarray:
    """Chunked host label propagation: one pass over the edge blocks per
    iteration, exact per-chunk best-neighbor-label ratings (lexsort +
    reduceat — the numpy twin of the device segment aggregation), moves
    gated by the cluster weight cap.  Deterministic (no RNG): ties break
    toward the lower label via the stable sort.  Returns compacted
    cluster labels."""
    n = int(graph.n)
    node_w = np.asarray(graph.node_weight_array(), dtype=np.int64)
    labels = np.arange(n, dtype=np.int64)
    cl_w = node_w.copy()
    cap = int(max_cluster_weight)
    for _ in range(max(1, num_iterations)):
        moved = 0
        for v0, v1, deg, adj, ew in _node_chunks(graph, chunk_nodes):
            if len(adj) == 0:
                continue
            rows = np.repeat(np.arange(v0, v1, dtype=np.int64), deg)
            tl = labels[adj]
            w = (
                np.ones(len(adj), dtype=np.int64) if ew is None
                else np.asarray(ew, dtype=np.int64)
            )
            order = np.lexsort((tl, rows))
            r, t, w = rows[order], tl[order], w[order]
            new_grp = np.empty(len(r), dtype=bool)
            new_grp[0] = True
            new_grp[1:] = (r[1:] != r[:-1]) | (t[1:] != t[:-1])
            starts = np.flatnonzero(new_grp)
            rating = np.add.reduceat(w, starts)
            gr, gt = r[starts], t[starts]
            # per-row best rating (stable: ties pick the lower label)
            o2 = np.lexsort((gt, -rating, gr))
            gr2, gt2 = gr[o2], gt[o2]
            firsts = np.flatnonzero(
                np.r_[True, gr2[1:] != gr2[:-1]]
            )
            best_row, best_lab = gr2[firsts], gt2[firsts]
            cur = labels[best_row]
            nw = node_w[best_row]
            ok = (best_lab != cur) & (cl_w[best_lab] + nw <= cap)
            if not ok.any():
                continue
            rows_ok, labs_ok, nw_ok = best_row[ok], best_lab[ok], nw[ok]
            # exact cap enforcement (per-chunk prefix pass): order the
            # chunk's joins by (target label, node id) and accept per
            # target the maximal prefix whose CUMULATIVE weight fits the
            # remaining headroom.  Departures in the same pass free no
            # headroom (conservative), so the cap is never exceeded —
            # the vectorized apply used to overshoot it by up to a
            # chunk's worth of concurrent joins.
            order2 = np.lexsort((rows_ok, labs_ok))
            rows_ok, labs_ok = rows_ok[order2], labs_ok[order2]
            nw_ok = nw_ok[order2]
            grp = np.flatnonzero(np.r_[True, labs_ok[1:] != labs_ok[:-1]])
            cum = np.cumsum(nw_ok)
            base = np.repeat(
                cum[grp] - nw_ok[grp],
                np.diff(np.r_[grp, len(labs_ok)]),
            )
            accept = (cum - base) <= (cap - cl_w[labs_ok])
            if not accept.any():
                continue
            rows_ok, labs_ok = rows_ok[accept], labs_ok[accept]
            np.subtract.at(cl_w, labels[rows_ok], node_w[rows_ok])
            labels[rows_ok] = labs_ok
            np.add.at(cl_w, labs_ok, node_w[rows_ok])
            moved += int(len(rows_ok))
        if moved == 0:
            break
    _, compact = np.unique(labels, return_inverse=True)
    return compact.astype(np.int64)


# pure-host numpy kernel, same contract as _host_lp_cluster above.
# tpulint: disable=R1
def _host_contract(graph: Any, labels: np.ndarray,
                   chunk_nodes: int = 1 << 17):
    """Chunked host contraction: aggregate inter-cluster edges block by
    block (per-chunk dedup, periodic re-dedup of the accumulator so the
    host high-water stays ~O(coarse m + chunk)).  Returns the coarse
    HostGraph and the fine->coarse map."""
    from ..graphs.host import HostGraph

    c_n = int(labels.max()) + 1 if len(labels) else 0
    node_w = np.asarray(graph.node_weight_array(), dtype=np.int64)
    cw = np.zeros(c_n, dtype=np.int64)
    np.add.at(cw, labels, node_w)

    acc_key = np.empty(0, dtype=np.int64)
    acc_w = np.empty(0, dtype=np.int64)

    def dedup(keys, weights):
        uk, inv = np.unique(keys, return_inverse=True)
        uw = np.zeros(len(uk), dtype=np.int64)
        np.add.at(uw, inv, weights)
        return uk, uw

    for v0, v1, deg, adj, ew in _node_chunks(graph, chunk_nodes):
        if len(adj) == 0:
            continue
        rows = np.repeat(np.arange(v0, v1, dtype=np.int64), deg)
        cu, cv = labels[rows], labels[adj]
        keep = cu != cv
        key = cu[keep] * c_n + cv[keep]
        w = (
            np.ones(int(keep.sum()), dtype=np.int64) if ew is None
            else np.asarray(ew, dtype=np.int64)[keep]
        )
        k2, w2 = dedup(key, w)
        acc_key = np.concatenate([acc_key, k2])
        acc_w = np.concatenate([acc_w, w2])
        if len(acc_key) > 4 * max(len(k2), 1 << 20):
            acc_key, acc_w = dedup(acc_key, acc_w)
    acc_key, acc_w = dedup(acc_key, acc_w)
    cu = (acc_key // c_n).astype(np.int64)
    cv = (acc_key % c_n).astype(np.int32)
    xadj = np.zeros(c_n + 1, dtype=np.int64)
    np.add.at(xadj, cu + 1, 1)
    np.cumsum(xadj, out=xadj)
    coarse = HostGraph(
        xadj=xadj,
        adjncy=cv,
        node_weights=cw,
        edge_weights=acc_w,
    )
    return coarse, labels.astype(np.int32)


def semi_external_partition(graph: Any, ctx: Any, facade: Any) -> np.ndarray:
    """Rung 3: coarsen the fine graph HOST-side in node-range chunks
    until the coarse graph's spilled-mode estimate fits the budget, run
    the normal device pipeline on the coarse graph, and project the
    partition back through the host cmaps.  Only the coarse graph and
    the partition vector are ever device-resident; the fine graph stays
    in host RAM (compressed inputs are streamed block-wise and never
    decoded whole)."""
    from .. import telemetry
    from ..utils import timer
    from ..utils.logger import log_progress

    st = state()
    budget = st.budget if st is not None else None
    k = int(ctx.partition.k)
    target = (
        int(budget * STREAM_TARGET_FRACTION) if budget else None
    )
    cmaps: List[np.ndarray] = []
    current = graph
    cap = max(
        1,
        int(ctx.coarsening.max_cluster_weight(
            int(graph.n), int(ctx.partition.total_node_weight),
            ctx.partition,
        )),
    )
    with timer.scoped_timer("semi-external-coarsening"):
        for level in range(32):
            n, m = int(current.n), int(current.m)
            fits = (
                target is None
                or estimate_rung_bytes(RUNG_SPILL_HIERARCHY, n, m, k)
                <= target
            )
            if fits or n <= max(2 * ctx.coarsening.contraction_limit, 2):
                break
            labels = _host_lp_cluster(current, cap)
            c_n = int(labels.max()) + 1 if len(labels) else 0
            if c_n >= 0.95 * n:
                # clustering stalled: relax the cap (the forced-shrink
                # retry of the device coarsener) before giving up
                cap *= 2
                labels = _host_lp_cluster(current, cap)
                c_n = int(labels.max()) + 1 if len(labels) else 0
                if c_n >= 0.95 * n:
                    break
            current, cmap = _host_contract(current, labels)
            cmaps.append(cmap)
            log_progress(
                f"semi-external level {level}: n={current.n} "
                f"m={current.m} (host-resident)"
            )
    telemetry.event(
        "semi-external",
        levels=len(cmaps),
        coarse_n=int(current.n),
        coarse_m=int(current.m),
    )
    # `current` is the host-coarsened graph — or the original when
    # nothing could be coarsened away host-side; either way it goes to
    # the device pipeline (spill mode still active) and an OOM there
    # moves the ladder on to host-only
    part = facade._partition_core_resilient(current, ctx)
    part = np.asarray(part, dtype=np.int32)
    with timer.scoped_timer("semi-external-projection"):
        for cmap in reversed(cmaps):
            part = part[cmap]
    return part


# ---------------------------------------------------------------------------
# rung 4: host-only partitioning
# ---------------------------------------------------------------------------


def host_only_partition(graph: Any, ctx: Any) -> np.ndarray:
    """Rung 4: recursive bisection entirely on the host (the sequential
    pool bipartitioner) — no device arrays at all.  Quality is the
    initial-partitioning pool's, not the refined pipeline's; the output
    gate still validates and repairs balance downstream."""
    from .. import telemetry
    from ..graphs.compressed import CompressedHostGraph
    from ..partitioning.rb import recursive_bipartition
    from ..utils import rng as rng_mod
    from ..utils import timer

    hg = graph.decode() if isinstance(graph, CompressedHostGraph) else graph
    k = int(ctx.partition.k)
    telemetry.event("host-only-partition", n=int(hg.n), m=int(hg.m), k=k)
    with timer.scoped_timer("host-only-partitioning"):
        part = recursive_bipartition(
            hg, k, ctx, rng_mod.host_rng(ctx.seed ^ 0x40F7)
        )
    return np.asarray(part, dtype=np.int32)


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------


def summary() -> dict:
    """The run report's ``memory_budget`` section.  ``enabled`` is True
    when a budget was declared OR the ladder engaged (an OOM recovery
    with no declared budget is still worth auditing)."""
    st = state()
    if st is None:
        return {"enabled": False}
    d: Dict[str, Any] = {
        "enabled": bool(st.budget or st.engaged),
        "rung": int(st.rung),
        "rung_name": RUNG_NAMES.get(st.rung, str(st.rung)),
        "initial_rung": int(st.initial_rung),
        "exhausted": bool(st.exhausted),
        "spills": {
            "count": int(st.spills),
            "bytes": int(st.spill_bytes),
            "reloads": int(st.reloads),
            "reload_bytes": int(st.reload_bytes),
        },
        "pressure_events": int(st.pressure_events),
        "shed_cache_bytes": int(st.shed_bytes),
    }
    if st.budget is not None:
        d["budget_bytes"] = int(st.budget)
    if st.estimate is not None:
        d["estimate_bytes"] = int(st.estimate)
    if st.bucket:
        d["bucket"] = st.bucket
    if st.watermark:
        d["watermark_bytes"] = int(st.watermark)
    return d
