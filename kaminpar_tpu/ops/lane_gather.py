"""Static-index gather at streaming speed via Mosaic's lane-wise
``tpu.dynamic_gather``.

The pipeline's hot irregular op is ``table[idx]`` with ``idx`` an
edge-wide index array (``labels[dst]`` in LP rating, block lookups in
Jet).  XLA lowers that gather index-serially on TPU: ~12.5 ns per index,
0.1% of HBM peak (scripts/microbench_gather.py, docs/performance.md) —
the round-4 speed floor.

Mosaic (JAX >= 0.9) *does* lower one gather shape to hardware:
``jnp.take_along_axis(x, q, axis=0)`` on 2D operands of identical shape
becomes ``tpu.dynamic_gather``:

    out[s, l] = x[q[s, l], l]          # per-LANE gather across sublanes

Element (s, l) can only read column l.  A general gather therefore
needs indices routed to their *native lane* (``idx % 128``) first —
normally a per-call reshuffle as expensive as the gather itself.  Two
properties of this pipeline break the deadlock:

  1. The index arrays are STATIC per graph level (CSR topology does not
     change between LP/Jet rounds; only the table — labels, blocks —
     changes).  The routing can be planned ONCE per level and reused by
     every round.
  2. The consumers are ORDER-AGNOSTIC: the sort2 rating engine re-sorts
     (owner, label, weight) triples anyway and the dense engine
     segment-sums them, so gathered values never need to return to edge
     order.  Static co-arrays (src, edge_w) are routed once at plan
     build and ride along.

``build_gather_plan`` sorts the indices by (table chunk, lane) on
device, pads each lane's run to a common per-chunk height, and records
(a) ``q``: the in-chunk row each routed slot reads, (b) ``inv``: the
original position each routed slot serves (-1 for pad).  ``lane_gather``
then streams the table chunk-by-chunk through VMEM with a
scalar-prefetched chunk id per grid tile; per round it moves
8 B/element instead of paying the 12.5 ns/element XLA loop.

Reference anchor: the op this accelerates is the neighbor-label lookup
of the reference's LP loop (kaminpar-shm/label_propagation.h:1682) and
Jet's block lookups (kaminpar-shm/refinement/jet/jet_refiner.cc).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.math import ceil_div, round_up

L = 128  # TPU lane count — the native minor dimension of every table

# Rows per table chunk: 4096x128 int32 = 2 MiB.  With the (S, 128)
# q/out blocks double-buffered by the pallas pipeline this stays well
# inside the ~16 MiB VMEM budget.
DEFAULT_CHUNK_ROWS = 4096


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class GatherPlan:
    """Static routing plan for gathers from a fixed index array.

    Leaves (device arrays):
      q          i32[H, 128]   in-chunk source row per routed slot
      tile_chunk i32[H // S]   table chunk id per grid tile
      inv        i32[H * 128]  original index position per routed slot
                               (-1 for pad slots)
    Static:
      S       rows per table chunk (grid tile height)
      C       number of table chunks
      H       routed rows (multiple of S)
      m       original index count
      n_rows  table rows (table_len // 128)
    """

    q: jax.Array
    tile_chunk: jax.Array
    inv: jax.Array
    S: int
    C: int
    H: int
    m: int
    n_rows: int

    def tree_flatten(self):
        return (
            (self.q, self.tile_chunk, self.inv),
            (self.S, self.C, self.H, self.m, self.n_rows),
        )

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def num_slots(self) -> int:
        return self.H * L


@functools.partial(jax.jit, static_argnames=("sl",))
def _sort_by_key(idx, sl):
    """Sort positions by (chunk, lane) key; return key_s, pos_s, qloc_s."""
    m = idx.shape[0]
    lane = idx % L
    chunk = idx // (sl * L)
    qloc = (idx // L) % sl
    key = chunk * L + lane
    pos = jnp.arange(m, dtype=jnp.int32)
    return lax.sort((key, pos, qloc), num_keys=1)


@functools.partial(jax.jit, static_argnames=("H",))
def _scatter_plan(key_s, pos_s, qloc_s, chunk_start, region_off, H):
    """Place sorted entries at their padded routed slots."""
    m = key_s.shape[0]
    iota = jnp.arange(m, dtype=jnp.int32)
    prev = jnp.concatenate([jnp.array([-1], key_s.dtype), key_s[:-1]])
    grp_start = key_s != prev
    rank = iota - lax.cummax(jnp.where(grp_start, iota, 0))
    lane_s = key_s % L
    # expand the (C,) region offsets to the m sorted slots without an
    # m-wide gather: drop each chunk's offset at its first sorted
    # position (a C-element scatter; empty chunks share a position, so
    # .max keeps the largest = the live one) and forward-fill by cummax
    marks = (
        jnp.zeros(m, dtype=jnp.int32)
        .at[chunk_start]
        .max(region_off, mode="drop")
    )
    row = lax.cummax(marks) + rank
    slot = row * L + lane_s
    q = (
        jnp.zeros(H * L, dtype=jnp.int32)
        .at[slot]
        .set(qloc_s, mode="drop")
        .reshape(H, L)
    )
    inv = (
        jnp.full(H * L, -1, dtype=jnp.int32).at[slot].set(pos_s, mode="drop")
    )
    return q, inv


from ..resilience.errors import PlanBlowup


class PlanBlowupError(PlanBlowup, ValueError):
    """build_gather_plan aborted: the routed plan would exceed max_slots.

    Raised BEFORE the H*128-wide q/inv arrays are materialized, so a
    hub-skewed level can be rejected without first allocating the very
    blowup the cap exists to prevent.  Subclasses the structured
    resilience.PlanBlowup, so the `lane-gather` site's with_fallback
    wrapper classifies it and degrades to the XLA gather (ValueError is
    kept for backward compatibility with pre-resilience callers)."""

    def __init__(self, num_slots: int, max_slots: int) -> None:
        self.num_slots = num_slots
        self.max_slots = max_slots
        super().__init__(
            f"routed plan needs {num_slots} slots > cap {max_slots}"
        )


def build_gather_plan(
    idx,
    table_len: int,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    max_slots: Optional[int] = None,
) -> GatherPlan:
    """Plan lane-routed gathers from the static index array ``idx``.

    ``table_len`` must be a multiple of 128 (device arrays are padded
    to lane multiples already).  Values of ``idx`` must lie in
    [0, table_len).  Not jittable (the routed height depends on the
    lane-count histogram), but cheap: one m-wide sort, two m-wide
    scatters, and a 1 KiB histogram readback — amortized over every
    round at the level.

    With ``max_slots`` the plan aborts with PlanBlowupError as soon as
    the routed height is known (after the histogram, before any
    slot-wide array exists) when it would exceed the cap.
    """
    if table_len % L:
        raise ValueError(f"table_len {table_len} not a multiple of {L}")
    n_rows = table_len // L
    S = min(round_up(n_rows, 8), chunk_rows)
    C = ceil_div(n_rows, S)
    idx = jnp.asarray(idx, dtype=jnp.int32)
    m = idx.shape[0]
    if m:
        lo, hi = int(jnp.min(idx)), int(jnp.max(idx))
        if lo < 0 or hi >= table_len:
            raise ValueError(
                f"indices out of range [0, {table_len}): found "
                f"[{lo}, {hi}]"
            )
    key_s, pos_s, qloc_s = _sort_by_key(idx, S)

    # per-(chunk, lane) counts via boundary search on the sorted keys
    bounds = np.asarray(
        jnp.searchsorted(key_s, jnp.arange(C * L + 1, dtype=jnp.int32))
    )
    counts = (bounds[1:] - bounds[:-1]).reshape(C, L)
    # untouched chunks get NO region (no tile, no table-chunk stream)
    h_c = [
        0 if counts[c].max() == 0 else round_up(int(counts[c].max()), S)
        for c in range(C)
    ]
    if sum(h_c) == 0:
        h_c[0] = S  # degenerate m=0 plan: one all-pad tile
    # routed-row offsets <= H < 2^31 by construction  # tpulint: disable=R3
    region_off = np.concatenate([[0], np.cumsum(h_c)[:-1]]).astype(np.int32)
    chunk_start = bounds[: C * L : L].astype(np.int32)
    H = int(sum(h_c))
    if max_slots is not None and H * L > max_slots:
        raise PlanBlowupError(H * L, int(max_slots))

    q, inv = _scatter_plan(
        key_s,
        pos_s,
        qloc_s,
        jnp.asarray(chunk_start),
        jnp.asarray(region_off),
        H,
    )
    tiles: list[int] = []
    for c in range(C):
        tiles.extend([c] * (h_c[c] // S))  # empty chunks contribute none
    return GatherPlan(
        q=q,
        tile_chunk=jnp.asarray(tiles, dtype=jnp.int32),
        inv=inv,
        S=S,
        C=C,
        H=H,
        m=m,
        n_rows=n_rows,
    )


def route_codata(plan: GatherPlan, arr, fill):
    """Route a static edge-order co-array into the plan's slot order.

    Done once per level per array (an ordinary XLA gather); the result
    is reused by every round.  Pad slots get ``fill``.
    """
    arr = jnp.asarray(arr)
    safe = jnp.clip(plan.inv, 0, max(plan.m - 1, 0))
    return jnp.where(plan.inv >= 0, arr[safe], fill)


def _gather_kernel(tile_chunk_ref, table_ref, q_ref, out_ref):
    del tile_chunk_ref  # consumed by the index maps
    out_ref[...] = jnp.take_along_axis(table_ref[...], q_ref[...], axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lane_gather(table, plan: GatherPlan, interpret: bool = False):
    """Gather ``table[idx]`` in the plan's routed slot order.

    ``table`` is the flat i32[table_len] array (e.g. labels).  Returns
    i32[H * 128]; slot j serves original index position plan.inv[j]
    (-1 slots are pads).  Use ``route_codata`` at plan build to align
    per-edge companions.
    """
    S, C, H = plan.S, plan.C, plan.H
    tab = table.astype(jnp.int32)
    pad = C * S * L - tab.shape[0]
    if pad:
        tab = jnp.concatenate([tab, jnp.zeros(pad, jnp.int32)])
    tab3 = tab.reshape(C, S, L)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H // S,),
        in_specs=[
            pl.BlockSpec((None, S, L), lambda t, tc: (tc[t], 0, 0)),
            pl.BlockSpec((S, L), lambda t, tc: (t, 0)),
        ],
        out_specs=pl.BlockSpec((S, L), lambda t, tc: (t, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, L), jnp.int32),
        interpret=interpret,
    )(plan.tile_chunk, tab3, plan.q)
    return out.reshape(H * L)


# ---------------------------------------------------------------------------
# Per-level edge-plan pack + cache
# ---------------------------------------------------------------------------
#
# The hot consumers (LP rating, Jet conn build) gather labels at
# graph.dst with co-data (src, edge_w) riding along.  One plan per graph
# level serves every round of LP clustering, LP refinement, and Jet at
# that level; the deep-multilevel driver revisits the same DeviceGraph
# objects during uncoarsening, so plans are cached by the identity of
# the level's dst array.

# routed slots used by the current jit trace run in interpreter mode
# when this is set (CPU tests of the integration)
INTERPRET = False

# plan building pays one m-wide sort + two m-wide scatters; below this
# many edge slots the per-round XLA gather is cheap enough that the
# plan never pays for itself (matches ops/lp.DELTA_MIN_EDGE_SLOTS).
MIN_EDGE_SLOTS = 1 << 22

# Routed-slot blowup cap: per-chunk heights round each chunk's max
# per-lane count up to S, so one high in-degree hub (RMAT-typical)
# can inflate H*128 to a multiple of m — five i32 arrays of that width
# pin HBM per cached level and every rating sort then runs over the
# inflated slot count (ADVICE round 5 medium).  Plans wider than this
# multiple of the index count are discarded in favor of the XLA gather.
PLAN_MAX_SLOT_RATIO = 2.0


def slot_cap(m: int) -> Optional[int]:
    """The num_slots budget for an m-wide index array
    (PLAN_MAX_SLOT_RATIO * m); None = uncapped (tests lift the ratio
    to inf).  The single source of the cap for plan_within_cap and
    edge_plans' build_gather_plan(max_slots=...) abort."""
    import math

    ratio = PLAN_MAX_SLOT_RATIO * max(int(m), 1)
    return None if math.isinf(ratio) or math.isnan(ratio) else int(ratio)


def plan_within_cap(plan: GatherPlan, m: int) -> bool:
    """True when the routed plan's slot count is affordable for an
    m-wide index array (num_slots <= slot_cap(m))."""
    cap = slot_cap(m)
    return cap is None or plan.num_slots <= cap


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class EdgePlans:
    """Routed views of a level's static edge arrays."""

    plan: GatherPlan
    owner_key: jax.Array  # i32[H*128] src per routed slot (pad: n_pad-1)
    src_idx: jax.Array    # i32[H*128] src clipped for label lookups
    edge_w: jax.Array     # i32[H*128] edge weight per routed slot (pad: 0)

    def tree_flatten(self):
        return ((self.plan, self.owner_key, self.src_idx, self.edge_w), None)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        del aux
        return cls(*leaves)


# key -> (dst_array, EdgePlans).  The dst array itself is stored and
# identity-checked on every hit: holding the reference prevents Python
# id recycling from ever matching a DIFFERENT topology's array, and the
# `is` check makes the id-based key safe even across cache clears.
# Entries pin device memory (O(m) per level), so the cache is small and
# the partitioner clears it at every compute_partition entry.
_PLAN_CACHE: dict = {}
_PLAN_CACHE_MAX = 4


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()


def edge_plans(graph):
    """The routed edge views of a DeviceGraph level (cached), or None
    when the plan blew past PLAN_MAX_SLOT_RATIO and the level must use
    the XLA-gather fallback.  The verdict (and the pad-overhead ratio)
    is emitted as a `lane-gather-plan` telemetry event either way, so
    run reports show how much slot padding each routed level carries."""
    key = (id(graph.dst), graph.dst.shape[0], graph.n_pad)
    hit = _PLAN_CACHE.get(key)
    if hit is not None and hit[0] is graph.dst:
        return hit[1]
    m = int(graph.dst.shape[0])
    cap = slot_cap(m)
    from .. import telemetry
    from ..resilience import with_fallback

    def _build_pack():
        # the cap aborts inside the builder, BEFORE the H*128-wide
        # q/inv arrays exist — a hub-skewed level must not allocate
        # the very blowup it is being rejected for
        plan = build_gather_plan(graph.dst, graph.n_pad, max_slots=cap)
        telemetry.event(
            "lane-gather-plan",
            m=m,
            num_slots=plan.num_slots,
            pad_overhead=round(plan.num_slots / max(m, 1), 4),
            capped=False,
        )
        n_pad = graph.n_pad
        owner_key = route_codata(plan, graph.src, n_pad - 1)
        return EdgePlans(
            plan=plan,
            owner_key=owner_key,
            src_idx=jnp.clip(owner_key, 0, n_pad - 1),
            edge_w=route_codata(plan, graph.edge_w, 0),
        )

    def _xla_fallback(exc):
        num_slots = getattr(exc, "num_slots", None)
        pad_overhead = (
            round(num_slots / max(m, 1), 4) if num_slots is not None
            else None
        )
        telemetry.event(
            "lane-gather-plan",
            m=m,
            num_slots=num_slots,
            pad_overhead=pad_overhead,
            capped=True,
        )
        from ..utils.logger import log_progress

        detail = (
            f"num_slots={num_slots} > {PLAN_MAX_SLOT_RATIO}x m={m}, "
            f"pad overhead {pad_overhead}x"
            if num_slots is not None
            else f"{type(exc).__name__}" if exc is not None
            else "circuit breaker open"
        )
        log_progress(
            f"lane-gather: plan discarded ({detail}); falling back to "
            "the XLA gather"
        )
        return None

    pack = with_fallback(_build_pack, _xla_fallback, site="lane-gather")
    if len(_PLAN_CACHE) >= _PLAN_CACHE_MAX:
        _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
    _PLAN_CACHE[key] = (graph.dst, pack)
    return pack


def routed_block_ratings(plans: EdgePlans, labels, k: int, n_pad: int):
    """Dense (n_pad, k) rating table via the lane-routed block lookup —
    the routed twin of segments.dense_block_ratings (segment_sum is
    slot-order-agnostic; pad slots carry owner n_pad-1, weight 0)."""
    from .segments import ACC_DTYPE

    lab_c = jnp.clip(labels, 0, k - 1)
    nb_r = lane_gather(lab_c, plans.plan, interpret=INTERPRET)
    flat = plans.src_idx * k + jnp.clip(nb_r, 0, k - 1)
    return jax.ops.segment_sum(
        plans.edge_w.astype(ACC_DTYPE), flat, num_segments=n_pad * k
    ).reshape(n_pad, k)


# how much of a compiler refusal the probe status keeps
PROBE_ERROR_CHARS = 2000

# last probe / override decision, surfaced in run reports
# (telemetry.report `lane_gather` section) and by the probe event
_PROBE_STATUS: dict = {"mode": "not-probed"}


def probe_status() -> dict:
    """The current routing decision: probe verdict + timings when the
    support probe ran, or the env-override / not-probed state."""
    import os

    status = dict(_PROBE_STATUS)
    env = os.environ.get("KAMINPAR_TPU_LANE_GATHER", "")
    if env in ("0", "1"):
        status["env_override"] = env
        if env == "0":
            status["mode"] = "opt-out"
    return status


def maybe_edge_plans(graph):
    """EdgePlans for the level, or None when routing would not pay:
    backend without the Mosaic kernel, small levels, a plan over the
    PLAN_MAX_SLOT_RATIO blowup cap, or opted out via
    KAMINPAR_TPU_LANE_GATHER=0.  KAMINPAR_TPU_LANE_GATHER=1 force-enables
    routing past the size gate and the best-of-3 TIMING race — the
    symmetric override for noisy links where one slow probe round would
    otherwise disable routing for the whole process (ADVICE round 5 low
    #2).  The compile/correctness half of the probe still gates: forcing
    on a backend without the Mosaic kernel (a shell profile exported for
    TPU work, run on a CPU box) stays a no-op instead of a crash."""
    import os

    env = os.environ.get("KAMINPAR_TPU_LANE_GATHER", "")
    if env == "0":
        return None
    if env == "1":
        if _PROBE_STATUS.get("mode") != "forced-on":
            supported, status = _probe_support(skip_timing=True)
            status["mode"] = "forced-on"
            _PROBE_STATUS.clear()
            _PROBE_STATUS.update(status)
            from .. import telemetry
            from ..utils.logger import log_progress

            telemetry.event(
                "lane-gather-probe",
                verdict="forced-on",
                **{k: v for k, v in status.items() if k != "mode"},
            )
            log_progress(
                "lane-gather: force-enabled (KAMINPAR_TPU_LANE_GATHER=1)"
                + ("" if supported else
                   f" but unavailable: {status.get('reason')}")
            )
        return edge_plans(graph) if _PROBE_STATUS.get("supported") else None
    if graph.dst.shape[0] < MIN_EDGE_SLOTS:
        return None
    if not lane_gather_supported():
        return None
    return edge_plans(graph)


@functools.lru_cache(maxsize=1)
def lane_gather_supported() -> bool:
    """One-time probe: the backend must compile the dynamic_gather
    kernel, produce correct results on a multi-vreg (cross-sublane)
    table, AND actually beat the XLA gather at a representative shape —
    a lowering that emulates the gather slowly would silently regress
    every routed round otherwise.  The verdict (and both timings) is
    logged and recorded as a telemetry event: the probe is a single
    best-of-3 timing race cached for the process, and an operator must
    be able to see which way it went (ADVICE round 5 low #2)."""
    supported, status = _probe_support()
    _PROBE_STATUS.clear()
    _PROBE_STATUS.update(status)
    from .. import telemetry
    from ..utils.logger import log_progress

    telemetry.event(
        "lane-gather-probe",
        verdict="enabled" if supported else "disabled",
        **{k: v for k, v in status.items() if k != "mode"},
    )
    detail = ", ".join(
        f"{k}={v}" for k, v in status.items() if k not in ("mode",)
    )
    log_progress(
        f"lane-gather probe: {'enabled' if supported else 'disabled'}"
        + (f" ({detail})" if detail else "")
    )
    return supported


def _probe_support(skip_timing: bool = False):
    """Returns (supported, status dict with reason/timings).  With
    `skip_timing` (the =1 force-enable) only the platform and
    correctness halves gate — the timing race is not run."""
    try:
        from ..utils import platform as _platform

        platform = _platform.default_backend()
        if platform != "tpu":
            return False, {
                "mode": "probed",
                "supported": False,
                "reason": f"platform {platform} lacks the Mosaic kernel",
            }
        # correctness at a small cross-sublane shape
        n = 16 * L
        rng = np.random.RandomState(0)
        idx = rng.randint(0, n, 4096).astype(np.int32)
        table = rng.randint(0, 1 << 30, n).astype(np.int32)
        # probe plan: fixed 4096-index uniform shape, blowup impossible
        # tpulint: disable=R5
        plan = build_gather_plan(jnp.asarray(idx), n)
        got = np.asarray(lane_gather(jnp.asarray(table), plan))
        inv = np.asarray(plan.inv)
        ok = inv >= 0
        if not np.array_equal(got[ok], table[idx[inv[ok]]]):
            return False, {
                "mode": "probed",
                "supported": False,
                "reason": "dynamic_gather produced incorrect results",
            }
        if skip_timing:
            return True, {"mode": "probed", "supported": True}
        # speed: routed gather must beat the XLA gather at 4M indices
        # from a 2^19-entry table (a mid-size level's shape)
        import time

        m_probe, n_probe = 1 << 22, 1 << 19
        idx2 = jnp.asarray(
            np.random.RandomState(1).randint(0, n_probe, m_probe), jnp.int32
        )
        tab2 = jnp.asarray(
            np.random.RandomState(2).randint(0, 1 << 30, n_probe), jnp.int32
        )
        # probe plan: fixed uniform 4M-index shape, blowup impossible
        # tpulint: disable=R5
        plan2 = build_gather_plan(idx2, n_probe)
        # one-shot probe (lru_cached), the per-call retrace never repeats
        # tpulint: disable=R4
        xla = jax.jit(lambda t, i: t[i])

        def _time(fn, *args):
            out = fn(*args)
            int(jnp.sum(out[:1]))  # force completion (scalar readback)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                out = fn(*args)
                int(jnp.sum(out[:1]))
                best = min(best, time.perf_counter() - t0)
            return best

        t_routed = _time(lambda t: lane_gather(t, plan2), tab2)
        t_xla = _time(xla, tab2, idx2)
        status = {
            "mode": "probed",
            "supported": bool(t_routed < t_xla),
            "t_routed_s": round(t_routed, 6),
            "t_xla_s": round(t_xla, 6),
        }
        if not status["supported"]:
            status["reason"] = "routed gather lost the timing race"
        return status["supported"], status
    except Exception as e:  # pragma: no cover - backend specific
        # a Mosaic refusal is the finding the probe exists to surface:
        # keep the compiler's own message (its head names the op that
        # would not lower), not just the exception type
        return False, {
            "mode": "probed",
            "supported": False,
            "reason": f"probe raised {type(e).__name__}",
            "error": " ".join(str(e).split())[:PROBE_ERROR_CHARS],
        }
