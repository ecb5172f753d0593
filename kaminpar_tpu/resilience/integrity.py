"""Silent-data-corruption defense: invariant sentinels, exchange
digests, sampled re-execution audits, and the corruption-chaos helpers.

The resilience stack catches every *loud* failure — crashes, hangs,
OOM, preemption, rank divergence — but a flipped bit in a device
buffer, a truncated spill re-read, or a poisoned cache entry produces a
silently-worse (or invalid) result that sails through every verdict as
``served``.  This module is the quiet half of the failure model, four
legs:

  * **invariant sentinels** — cheap algebraic checks at the existing
    phase boundaries: node/edge-weight conservation across each
    contraction, cmap range/surjectivity, coarse-CSR symmetry,
    partition-vector range ``[0, k)``, and cut non-increase across an
    accepted refinement pass.  Each failure raises a structured
    :class:`~kaminpar_tpu.resilience.errors.IntegrityViolation`
    (invariant name + level + scope) that ``policy.with_fallback``
    NEVER absorbs, and that drives the bounded
    retry-from-last-good-barrier ladder (:func:`run_with_retry`:
    one re-execution from the last clean checkpoint barrier before
    giving up with verdict ``corrupt-result``);

  * **checksummed exchange** — content digests on every host-boundary
    handoff that previously trusted bytes: chunkstore spill files
    (external/chunkstore.py), supervised-worker npz replies
    (resilience/supervisor.py), and serving result-cache entries
    (serving/service.py).  A digest mismatch is a classified
    IntegrityViolation, not a crash, and each boundary has a local
    recovery (re-decode / fail the one request / forced miss + evict);

  * **sampled re-execution audits** — ``KAMINPAR_TPU_AUDIT_FRACTION``
    re-runs a deterministic sample of device reductions on the host
    twin and compares bitwise (integer arithmetic is exact on both
    sides), reported per scope as ``{audited, mismatched}``;

  * **corruption chaos** — :func:`chaos_flip_array` /
    :func:`chaos_flip_file` catch an injected fault at the
    ``bit-flip:*`` / ``spill-corrupt`` / ``cache-poison`` /
    ``worker-reply-corrupt`` sites and genuinely mutate bytes in
    flight, so the detectors above are exercised end-to-end.

Dormancy contract: every sentinel/digest runs host-side between
launches; the device-side checks are SEPARATE small jitted reductions
(the telemetry/quality.py precedent) — the LP / Jet / contraction
jaxprs are bitwise-identical with integrity on, off, or disabled.
``KAMINPAR_TPU_INTEGRITY=0`` is the kill switch (sentinels, digests,
and audits all dormant; chaos injection still mutates, which is how
the "undetected corruption is measurably wrong" half of the chaos
proof runs).
"""

from __future__ import annotations

import hashlib
import os
import time
from typing import Any, Callable, Dict, Optional, TypeVar

import numpy as np

from .errors import IntegrityViolation

ENV_INTEGRITY = "KAMINPAR_TPU_INTEGRITY"
ENV_AUDIT_FRACTION = "KAMINPAR_TPU_AUDIT_FRACTION"

#: Bounded retry ladder: how many re-executions from the last clean
#: barrier one run gets before the verdict is ``corrupt-result``.
MAX_RETRIES = 1

T = TypeVar("T")

# ---------------------------------------------------------------------------
# module state (host-side; reset() for test isolation)
# ---------------------------------------------------------------------------

_stats: Dict[str, Any] = {
    "checks": 0,
    "violations": [],  # [{invariant, level, scope, site, detail}]
    "retries": 0,
    "recovered": 0,
    "verdict": None,  # None | "recovered" | "corrupt-result"
    "wall_s": 0.0,
}
_digests: Dict[str, int] = {"computed": 0, "verified": 0, "mismatched": 0}
_audits: Dict[str, Dict[str, int]] = {}  # scope -> {audited, mismatched}
_audit_counts: Dict[str, int] = {}  # scope -> sampling call counter

# jitted sentinel reductions, cached per (key) — built lazily so this
# module imports without jax (supervisor-style host-side contract)
_jits: Dict[str, Any] = {}


def enabled() -> bool:
    """Sentinels/digests/audits run unless KAMINPAR_TPU_INTEGRITY=0."""
    return os.environ.get(ENV_INTEGRITY, "") != "0"


def audit_fraction() -> float:
    """The sampled re-execution audit fraction (0 = audits off)."""
    raw = os.environ.get(ENV_AUDIT_FRACTION, "").strip()
    if not raw:
        return 0.0
    try:
        val = float(raw)
    except ValueError:
        return 0.0
    return min(max(val, 0.0), 1.0)


def reset() -> None:
    """Clear counters, violations, audits (test isolation).  The jit
    cache survives — compiled sentinel reductions are state-free."""
    _stats.update(
        checks=0, violations=[], retries=0, recovered=0, verdict=None,
        wall_s=0.0,
    )
    _digests.update(computed=0, verified=0, mismatched=0)
    _audits.clear()
    _audit_counts.clear()


class _timed:
    """Accumulate sentinel wall time (the ``integrity_overhead_pct``
    numerator): every host-side check body runs under one of these."""

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        _stats["wall_s"] += time.perf_counter() - self._t0
        return False


# ---------------------------------------------------------------------------
# violations
# ---------------------------------------------------------------------------


def _record_violation(
    invariant: str, detail: str, *, level: Optional[int], scope: str,
    site: str,
) -> None:
    _stats["violations"].append({
        "invariant": invariant,
        "level": level,
        "scope": scope,
        "site": site,
        "detail": detail[:300],
    })
    from .. import telemetry
    from ..utils.logger import log_warning

    telemetry.event(
        "integrity", action="violation", invariant=invariant,
        level=level, scope=scope, site=site or None,
        detail=detail[:300],
    )
    log_warning(
        f"INTEGRITY violation [{invariant}"
        + (f"@level{level}" if level is not None else "")
        + f"] at {scope or '?'}: {detail[:160]}"
    )


def violation(
    invariant: str, detail: str, *, level: Optional[int] = None,
    scope: str = "", site: str = "",
) -> IntegrityViolation:
    """Record + build (the caller raises) a structured violation."""
    _record_violation(invariant, detail, level=level, scope=scope,
                      site=site)
    return IntegrityViolation(
        f"integrity violation [{invariant}] at {scope or '?'}: {detail}",
        invariant=invariant, level=level, scope_path=scope,
        site=site or None,
    )


def check(
    invariant: str, ok: bool, detail: str, *, level: Optional[int] = None,
    scope: str = "",
) -> None:
    """One sentinel predicate: counts, and raises on failure."""
    _stats["checks"] += 1
    if not ok:
        raise violation(invariant, detail, level=level, scope=scope)


# ---------------------------------------------------------------------------
# invariant sentinels (device reductions separate from the pipeline
# jaxprs — the quality-layer dormancy precedent)
# ---------------------------------------------------------------------------


def _contraction_jit():
    fn = _jits.get("contraction")
    if fn is None:
        import jax
        import jax.numpy as jnp

        from ..ops.segments import ACC_DTYPE

        @jax.jit
        def scalars(fine_graph, cmap, coarse_graph):
            # pad convention (graphs/csr.py): pad nodes/edges carry
            # weight 0, so unmasked weight sums are exact
            fine_nw = jnp.sum(fine_graph.node_w.astype(ACC_DTYPE))
            coarse_nw = jnp.sum(coarse_graph.node_w.astype(ACC_DTYPE))
            # every fine edge whose endpoints land in different clusters
            # contributes its weight to exactly one coarse (directed)
            # edge; contraction sums parallels and drops self-loops, so
            # the directed sums match exactly
            n_pad_c = coarse_graph.node_w.shape[0]
            cm = jnp.clip(cmap, 0, n_pad_c - 1)
            cross = jnp.sum(
                jnp.where(
                    cm[fine_graph.src] != cm[fine_graph.dst],
                    fine_graph.edge_w.astype(ACC_DTYPE),
                    0,
                )
            )
            coarse_ew = jnp.sum(coarse_graph.edge_w.astype(ACC_DTYPE))
            n_pad_f = cmap.shape[0]
            real_f = jnp.arange(n_pad_f) < fine_graph.n
            cmap_min = jnp.min(jnp.where(real_f, cmap, 0))
            cmap_max = jnp.max(jnp.where(real_f, cmap, 0))
            hit = jnp.zeros(n_pad_c, dtype=jnp.int32).at[cm].max(
                real_f.astype(jnp.int32), mode="drop"
            )
            real_c = jnp.arange(n_pad_c) < coarse_graph.n
            distinct = jnp.sum(jnp.where(real_c, hit, 0).astype(ACC_DTYPE))
            # CSR symmetry necessary conditions on the coarse graph:
            # equal directed weight both ways, zero self-loop weight
            w = coarse_graph.edge_w.astype(ACC_DTYPE)
            fwd = jnp.sum(
                jnp.where(coarse_graph.src < coarse_graph.dst, w, 0)
            )
            bwd = jnp.sum(
                jnp.where(coarse_graph.src > coarse_graph.dst, w, 0)
            )
            loops = jnp.sum(
                jnp.where(coarse_graph.src == coarse_graph.dst, w, 0)
            )
            return (fine_nw, coarse_nw, cross, coarse_ew, cmap_min,
                    cmap_max, distinct, fwd, bwd, loops)

        fn = _jits["contraction"] = scalars
    return fn


def check_contraction(
    fine_graph, cmap, coarse_graph, *, level: int, fine_n: int,
    coarse_n: int,
) -> None:
    """Contraction sentinels at the coarsening phase boundary.

    One separate jitted reduction returns ten scalars; every compare
    runs host-side.  Conservation is level-local (fine sum vs coarse
    sum of the SAME level) so preprocessing that legitimately drops
    weight before coarsening — isolated-node removal, subgraph
    extraction in deep partitioning — never trips the sentinel.
    No-op when integrity is disabled."""
    if not enabled():
        return
    vals = _contraction_jit()(fine_graph, cmap, coarse_graph)
    with _timed():
        (fine_nw, coarse_nw, cross, coarse_ew, cmap_min, cmap_max,
         distinct, fwd, bwd, loops) = (int(v) for v in vals)
        scope = f"coarsen:{level}"
        check(
            "node-weight-conservation",
            coarse_nw == fine_nw,
            f"coarse node-weight sum {coarse_nw} != fine {fine_nw}",
            level=level, scope=scope,
        )
        check(
            "edge-weight-conservation",
            cross == coarse_ew,
            f"fine cross-cluster edge weight {cross} != coarse edge "
            f"weight {coarse_ew}",
            level=level, scope=scope,
        )
        check(
            "cmap-range",
            0 <= cmap_min and cmap_max < coarse_n,
            f"cmap range [{cmap_min}, {cmap_max}] outside "
            f"[0, {coarse_n})",
            level=level, scope=scope,
        )
        check(
            "cmap-surjective",
            distinct == coarse_n,
            f"{distinct} distinct coarse ids hit, expected {coarse_n}",
            level=level, scope=scope,
        )
        check(
            "coarse-csr-symmetry",
            fwd == bwd and loops == 0,
            f"directed weight {fwd} vs {bwd}, self-loop weight {loops}",
            level=level, scope=scope,
        )
    # sampled re-execution audit: recompute the coarse node weights on
    # the host from the fine weights + projection map (np.bincount) and
    # compare the device scatter bitwise
    if should_audit("contraction-weights"):
        with _timed():
            nw = np.asarray(fine_graph.node_w)[:fine_n].astype(np.int64)
            cm = np.asarray(cmap)[:fine_n].astype(np.int64)
            host_bw = np.bincount(
                np.clip(cm, 0, max(coarse_n - 1, 0)), weights=nw,
                minlength=coarse_n,
            ).astype(np.int64)
            dev_bw = np.asarray(
                coarse_graph.node_w
            )[:coarse_n].astype(np.int64)
            record_audit(
                "contraction-weights",
                mismatched=not np.array_equal(host_bw, dev_bw),
                level=level,
            )


def _refine_jit(has_min: bool):
    key = f"refine:{has_min}"
    fn = _jits.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        from ..ops import metrics

        @jax.jit
        def scalars(graph, partition, max_bw, min_bw=None):
            cut = metrics.edge_cut(graph, partition)
            feas = metrics.is_feasible(graph, partition, max_bw, min_bw)
            real = jnp.arange(partition.shape[0]) < graph.n
            pmin = jnp.min(jnp.where(real, partition, 0))
            pmax = jnp.max(jnp.where(real, partition, 0))
            return cut, feas, pmin, pmax

        if has_min:
            fn = scalars
        else:
            fn = lambda g, p, mx: scalars(g, p, mx)  # noqa: E731
        _jits[key] = fn
    return fn


def refine_probe(graph, partition, max_block_weights, min_block_weights):
    """(cut, feasible, part_min, part_max) for the refinement sentinels
    — one separate jitted reduction, host ints out.  None when
    integrity is disabled."""
    if not enabled():
        return None
    from ..utils.timer import scoped_timer

    # a readback scope: the probe after a pass is the host's first read
    # since the refiners launched, so the wait for them lands here
    with scoped_timer("refine-probe", sync=True):
        if min_block_weights is None:
            vals = _refine_jit(False)(graph, partition, max_block_weights)
        else:
            vals = _refine_jit(True)(
                graph, partition, max_block_weights, min_block_weights
            )
        cut, feas, pmin, pmax = vals
        return int(cut), bool(feas), int(pmin), int(pmax)


def check_refinement(
    before, after, *, k: int, level: int,
) -> None:
    """Refinement sentinels across one accepted refine pass: partition
    range ``[0, k)`` and cut non-increase.  ``before``/``after`` are
    :func:`refine_probe` tuples (None = disabled, no-op).

    Cut non-increase is guarded on feasibility BOTH sides: a balancer
    legitimately trades cut for balance on an infeasible input, so only
    a feasible->feasible pass that still raised the cut is corrupt."""
    if before is None or after is None:
        return
    with _timed():
        cut_b, feas_b, _, _ = before
        cut_a, feas_a, pmin, pmax = after
        scope = f"refine:{level}"
        check(
            "partition-range",
            0 <= pmin and pmax < k,
            f"partition range [{pmin}, {pmax}] outside [0, {k})",
            level=level, scope=scope,
        )
        check(
            "cut-non-increase",
            not (feas_b and feas_a and cut_a > cut_b),
            f"accepted refinement pass raised the cut {cut_b} -> {cut_a} "
            "on a feasible partition",
            level=level, scope=scope,
        )


def audit_refine_cut(graph, partition, device_cut: int, *,
                     level: int) -> None:
    """Sampled host-twin re-execution of one cut evaluation: recompute
    the edge cut from the host CSR with numpy and compare the device
    value bitwise (integer arithmetic, exact both ways)."""
    if not enabled() or not should_audit("refine-cut"):
        return
    with _timed():
        from ..graphs.csr import host_graph_from_device

        host = host_graph_from_device(graph)
        part = np.asarray(partition)[: host.n]
        xadj = np.asarray(host.xadj, dtype=np.int64)
        owner = np.repeat(
            np.arange(host.n, dtype=np.int64), np.diff(xadj)
        )
        crosses = part[owner] != part[np.asarray(host.adjncy)]
        ew = np.asarray(host.edge_weight_array(), dtype=np.int64)
        host_cut = int(ew[crosses].sum()) // 2
        record_audit(
            "refine-cut", mismatched=host_cut != int(device_cut),
            level=level,
            detail=f"host {host_cut} vs device {int(device_cut)}",
        )


# ---------------------------------------------------------------------------
# sampled audits
# ---------------------------------------------------------------------------


def should_audit(scope: str) -> bool:
    """Deterministic per-scope sampling at KAMINPAR_TPU_AUDIT_FRACTION:
    the draw is keyed by (seed, scope, call index), so reruns audit the
    same calls (the faults.py determinism contract)."""
    frac = audit_fraction()
    if frac <= 0.0 or not enabled():
        return False
    count = _audit_counts.get(scope, 0) + 1
    _audit_counts[scope] = count
    if frac >= 1.0:
        return True
    from ..utils import rng as rng_mod

    seed = rng_mod.get_seed()
    digest = hashlib.sha256(
        f"audit:{seed}:{scope}:{count}".encode()
    ).digest()
    return int.from_bytes(digest[:8], "big") / float(1 << 64) < frac


def record_audit(scope: str, *, mismatched: bool,
                 level: Optional[int] = None, detail: str = "") -> None:
    """Count one audited re-execution; a bitwise mismatch is a
    violation (raised) on top of the per-scope tally."""
    ent = _audits.setdefault(scope, {"audited": 0, "mismatched": 0})
    ent["audited"] += 1
    if mismatched:
        ent["mismatched"] += 1
        raise violation(
            f"audit:{scope}",
            detail or "host re-execution disagreed with the device "
                      "value bitwise",
            level=level, scope=f"audit:{scope}",
        )


# ---------------------------------------------------------------------------
# exchange digests
# ---------------------------------------------------------------------------


def content_digest(*arrays) -> str:
    """sha256 hex over the raw bytes of the given numpy arrays (shape
    and dtype folded in, so a reinterpretation cannot collide)."""
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}:{a.shape};".encode())
        h.update(a.tobytes())
    _digests["computed"] += 1
    return h.hexdigest()


def verify_digest(expected: str, *arrays, what: str = "",
                  site: str = "") -> None:
    """Recompute and compare a content digest; mismatch raises a
    classified IntegrityViolation (invariant ``exchange-digest``).
    A missing expected digest verifies vacuously (pre-upgrade data)."""
    if not expected or not enabled():
        return
    with _timed():
        actual = content_digest(*arrays)
        _digests["computed"] -= 1  # verification, not a new stamp
        _digests["verified"] += 1
        if actual != expected:
            _digests["mismatched"] += 1
            raise violation(
                "exchange-digest",
                f"{what or 'payload'}: digest {actual[:16]}... != "
                f"expected {expected[:16]}...",
                scope=what, site=site,
            )


def note_digest_mismatch(what: str, detail: str, *,
                         site: str = "") -> IntegrityViolation:
    """Record an externally detected digest mismatch (io/snapshot.py's
    SnapshotError path) as a classified violation; returns the exception
    for the caller to raise or recover from."""
    _digests["verified"] += 1
    _digests["mismatched"] += 1
    return violation("exchange-digest", f"{what}: {detail}",
                     scope=what, site=site)


# ---------------------------------------------------------------------------
# corruption chaos (faults.py sites; mutation is genuine)
# ---------------------------------------------------------------------------


def chaos_flip_array(site: str, arr: np.ndarray, *,
                     bit: int = 7) -> np.ndarray:
    """Injection hook for in-flight array corruption: when the fault
    plan fires at ``site``, return a copy with one bit of element 0
    flipped (a genuine mutation — the DETECTORS are what chaos tests);
    otherwise return ``arr`` unchanged.  Never raises."""
    from . import faults

    try:
        faults.maybe_inject(site)
    except IntegrityViolation:
        out = np.array(arr, copy=True)
        flat = out.reshape(-1)
        flat[0] = flat[0] ^ type(flat[0])(1 << bit)
        from .. import telemetry

        telemetry.event(
            "integrity", action="chaos-corrupt", site=site,
            kind="array", bit=bit,
        )
        return out
    return arr


def chaos_corrupt_contraction(coarse):
    """``bit-flip:contraction`` chaos: when the fault plan fires, flip
    one bit of the first coarse edge-weight slot (pull, flip,
    re-upload) — an accelerator-SDC stand-in.  The edge-weight
    conservation and CSR-symmetry sentinels are what detect it; with
    integrity disabled the wrong weight silently biases every deeper
    coarsening/refinement decision."""
    from . import faults

    try:
        faults.maybe_inject("bit-flip:contraction")
    except IntegrityViolation:
        import dataclasses

        import jax.numpy as jnp

        ew = np.array(np.asarray(coarse.graph.edge_w), copy=True)
        flat = ew.reshape(-1)
        flat[0] = flat[0] ^ flat.dtype.type(1 << 5)
        graph = dataclasses.replace(
            coarse.graph, edge_w=jnp.asarray(ew)
        )
        from .. import telemetry

        telemetry.event(
            "integrity", action="chaos-corrupt",
            site="bit-flip:contraction", kind="edge-weight", bit=5,
        )
        return dataclasses.replace(coarse, graph=graph)
    return coarse


def chaos_corrupt_partition(partition):
    """``bit-flip:partition`` chaos: when the fault plan fires, flip bit
    20 of the first partition label (pull, flip, re-upload).  Bit 20
    puts the label far outside any padded ``[0, k)`` bucket, so the
    partition-range sentinel fires at the refinement boundary — BEFORE
    the output gate's repair pass could quietly heal it."""
    from . import faults

    try:
        faults.maybe_inject("bit-flip:partition")
    except IntegrityViolation:
        import jax.numpy as jnp

        part = np.array(np.asarray(partition), copy=True)
        flat = part.reshape(-1)
        flat[0] = flat[0] ^ flat.dtype.type(1 << 20)
        from .. import telemetry

        telemetry.event(
            "integrity", action="chaos-corrupt",
            site="bit-flip:partition", kind="partition", bit=20,
        )
        return jnp.asarray(part)
    return partition


def chaos_flip_file(site: str, path: str) -> bool:
    """Injection hook for at-rest byte corruption: when the fault plan
    fires at ``site``, flip one bit of the middle byte of ``path`` in
    place.  Returns True when the file was mutated."""
    from . import faults

    try:
        faults.maybe_inject(site)
    except IntegrityViolation:
        try:
            size = os.path.getsize(path)
            if size <= 0:
                return False
            with open(path, "r+b") as f:
                f.seek(size // 2)
                b = f.read(1)
                f.seek(size // 2)
                f.write(bytes([b[0] ^ 0x40]))
            from .. import telemetry

            telemetry.event(
                "integrity", action="chaos-corrupt", site=site,
                kind="file", path=os.path.basename(path),
            )
            return True
        except OSError:
            return False
    return False


# ---------------------------------------------------------------------------
# the retry-from-last-good-barrier ladder
# ---------------------------------------------------------------------------


def run_with_retry(body: Callable[[], T], *, where: str = "") -> T:
    """Run the pipeline body under the bounded corruption-recovery
    ladder: on the first IntegrityViolation, reload the last clean
    checkpoint barrier (the sentinel fired BEFORE its level's barrier,
    so the newest manifest is by construction pre-corruption) and
    re-execute once; a second violation is the ``corrupt-result``
    verdict and propagates.  Fault counters are deliberately NOT reset,
    so a deterministic ``nth=K`` injection does not re-fire — the
    retried run is clean and (deterministic seeds) cut-identical to an
    uninjected one."""
    if not enabled():
        return body()
    last: Optional[IntegrityViolation] = None
    for attempt in range(MAX_RETRIES + 1):
        try:
            result = body()
        except IntegrityViolation as exc:
            last = exc
            if attempt >= MAX_RETRIES:
                break
            _stats["retries"] += 1
            resumed = _reload_last_barrier()
            try:
                from .. import telemetry

                telemetry.event(
                    "integrity", action="retry",
                    invariant=exc.invariant, level=exc.level,
                    scope=exc.scope_path, where=where or None,
                    resumed_from=resumed,
                )
            except Exception:
                pass
            try:
                from ..utils.logger import log_warning

                log_warning(
                    f"integrity: retrying from "
                    f"{resumed or 'scratch'} after violation "
                    f"[{exc.invariant}]"
                )
            except Exception:
                pass
            continue
        if attempt and last is not None:
            _stats["recovered"] += 1
            _stats["verdict"] = "recovered"
            try:
                from .. import telemetry

                telemetry.event(
                    "integrity", action="recovered",
                    invariant=last.invariant, level=last.level,
                    where=where or None,
                )
            except Exception:
                pass
        return result
    assert last is not None
    _stats["verdict"] = "corrupt-result"
    try:
        from .. import telemetry

        telemetry.event(
            "integrity", action="corrupt-result",
            invariant=last.invariant, level=last.level,
            where=where or None,
        )
    except Exception:
        pass
    raise last


def _reload_last_barrier() -> Optional[str]:
    """Re-arm the run's checkpoint resume state from the last persisted
    manifest (the last clean barrier).  Returns the stage id the retry
    will resume from, or None (no manager / no checkpoint: the retry
    re-executes from scratch, which IS the last clean barrier then)."""
    from . import runstate

    mgr = runstate.current().manager
    if mgr is None or not mgr.enabled or mgr.memory_only:
        return None
    try:
        state = mgr.load_resume_state()
    except Exception:
        return None
    if state is None:
        return None
    lvl = state.get("level")
    return (
        str(state.get("stage", ""))
        + ("" if lvl is None else f":{int(lvl)}")
    )


# ---------------------------------------------------------------------------
# report surface (schema v14 `integrity` section)
# ---------------------------------------------------------------------------


def summary() -> Dict[str, Any]:
    """The run report's ``integrity`` section.  The well-formed
    disabled default when the kill switch is set and nothing ran."""
    active = (
        enabled()
        or _stats["checks"] > 0
        or bool(_stats["violations"])
        or _digests["verified"] > 0
    )
    if not active:
        return {"enabled": False}
    clean = not _stats["violations"]
    return {
        "enabled": bool(enabled()),
        "checks": int(_stats["checks"]),
        "violations": [dict(v) for v in _stats["violations"]],
        "retries": int(_stats["retries"]),
        "recovered": int(_stats["recovered"]),
        "verdict": (
            _stats["verdict"] if _stats["verdict"] is not None
            else ("clean" if clean else "detected")
        ),
        "digests": dict(_digests),
        "audits": {k: dict(v) for k, v in sorted(_audits.items())},
        "audit_fraction": audit_fraction(),
        "wall_s": round(float(_stats["wall_s"]), 6),
    }


def overhead_pct(total_wall_s: float) -> float:
    """Sentinel wall time as a percentage of a run's total wall (the
    bench's always-present ``integrity_overhead_pct`` key)."""
    total = float(total_wall_s)
    if total <= 0:
        return 0.0
    return round(100.0 * float(_stats["wall_s"]) / total, 3)
