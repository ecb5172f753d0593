"""Per-run JSON report (`--report-json`).

One machine-readable artifact per partition call, the analog of the
reference's parseable RESULT + TIME output promoted to a single schema:
scope tree (from the hierarchical timer), result metrics, per-level
graph sizes (from the coarsener's telemetry events), the collective
traffic table (parallel/mesh comm accounting), statistics counters, and
an environment stamp.  `bench.py`
embeds the same dict into its BENCH line so ad-hoc runs and the perf
trajectory share one schema.

The schema is checked in at `run_report.schema.json` and enforced by
`scripts/check_report_schema.py` (invoked from a tier-1 test, so schema
drift is caught at commit time).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

from . import events as _events
from . import jsonable
from . import progress_series as _progress_series
from . import run_info as _run_info

SCHEMA_VERSION = 14
SCHEMA_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "run_report.schema.json"
)


def environment_stamp() -> dict:
    """Platform / device-count / version stamp for the report header."""
    from .. import __version__

    import platform as _platform

    env: Dict[str, Any] = {
        "version": __version__,
        "python": _platform.python_version(),
    }
    try:
        import jax

        from ..utils import platform

        env["jax_version"] = jax.__version__
        devices = platform.devices()
        env["platform"] = devices[0].platform
        env["device_count"] = len(devices)
        env["process_count"] = platform.process_count()
    except Exception:
        env.setdefault("jax_version", "unavailable")
        env.setdefault("platform", "unknown")
        env.setdefault("device_count", 0)
        env.setdefault("process_count", 1)
    return env


def _compile_section() -> dict:
    """Compile-cost aggregate (trace/lower/compile seconds per phase,
    cache hit/miss totals); empty-but-well-formed when the monitoring
    listeners never installed (telemetry enabled mid-run)."""
    try:
        from . import compile_account

        return compile_account.snapshot()
    except Exception:
        return {"caveat": "compile accounting unavailable",
                "totals": {}, "phases": {}}


def _perf_section(levels, perf_ranks=None) -> dict:
    """Schema v5 `perf` section: roofline rows, memory watermarks (with
    the per-level CSR buffer accounting folded in), pad-waste rows.
    Raises perf.UnknownDeviceError on a device without published peaks."""
    from . import perf

    section = perf.snapshot()
    mem = section.setdefault("memory", {})
    # per-level resident CSR/partition buffer bytes, from the
    # coarsener's level events (host-side metadata, never a device pull)
    mem["levels"] = [
        {k: lv[k] for k in ("level", "n", "m", "n_pad", "m_pad",
                            "buffer_bytes") if k in lv}
        for lv in levels
        if "buffer_bytes" in lv
    ]
    if perf_ranks:
        mem["ranks"] = perf_ranks
    return section


def _ledger_section() -> dict:
    """Schema v13 ``ledger`` section: per-scope launch counts joined
    with executable costs, the host<->device transfer ledger (per
    scope/kind, per phase, totals), and the donation audit
    (telemetry/ledger.py).  Well-formed disabled default when the
    ledger is unavailable."""
    try:
        from . import ledger

        return ledger.snapshot()
    except Exception:
        return {"enabled": False,
                "caveat": "execution ledger unavailable"}


def _integrity_section() -> dict:
    """Schema v14 ``integrity`` section: sentinel check/violation
    counts, the retry-from-barrier ladder outcome (verdict clean /
    detected / recovered / corrupt-result), exchange-digest tallies,
    and the sampled re-execution audits per scope
    (resilience/integrity.py).  Well-formed disabled default when the
    kill switch is set and nothing ran."""
    try:
        from ..resilience import integrity

        return integrity.summary()
    except Exception:
        return {"enabled": False}


def _quality_section(ranks=None) -> dict:
    """Schema v7 `quality` section: per-level cut-loss attribution
    (projected / refined / floor cuts, coarsening_locked vs
    refinement_left), coarsening-quality stats, and refinement-efficacy
    verdicts (telemetry/quality.py).  Well-formed disabled default when
    the observatory recorded nothing."""
    try:
        from . import quality

        section = quality.snapshot()
    except Exception:
        return {"enabled": False,
                "caveat": "quality observatory unavailable"}
    if ranks:
        section["ranks"] = ranks
    return section


def _supervision_section() -> dict:
    """Schema v10 `supervision` section from the module state (the
    serving layer overrides this with its pool-aware summary); the
    disabled default when nothing supervision-shaped ever armed."""
    try:
        from ..resilience import supervisor

        return supervisor.summary()
    except Exception:
        return {"enabled": False}


def _fault_section() -> dict:
    """The fault-plan echo (CLI satellite): plan, sites, injected log."""
    try:
        from ..resilience import faults

        return faults.plan_summary()
    except Exception:
        return {"plan": None, "sites": [], "injected": []}


def _scope_tree(node) -> dict:
    return {
        child.name: {
            "elapsed_s": round(child.elapsed, 6),
            "count": child.count,
            "children": _scope_tree(child),
        }
        for child in node.children.values()
    }


def build_run_report(extra_run: Optional[dict] = None) -> dict:
    """Assemble the report from the current telemetry/timer/stats state.

    Call after `compute_partition` returns (the facade annotates the run
    and result sections during the call); `extra_run` keys (e.g. CLI io /
    wall seconds) are merged into the `run` section."""
    from ..utils import statistics, timer

    info = _run_info()
    result = info.pop("result", {})
    # the output gate's verdict (resilience/gate.py); absent when the
    # gate was disabled or no partition ran in this stream
    gate_verdict = info.pop("output_gate", {"checked": False})
    # schema v3 resilience sections: the checkpoint manager's summary
    # (resilience/checkpoint.py) and the anytime/wind-down annotation
    # (resilience/deadline.py); well-formed defaults when the run used
    # neither
    ckpt_summary = info.pop("checkpoint", {"enabled": False})
    anytime = info.pop("anytime", {"anytime": False})
    # schema v4: the serving layer's per-request verdicts + admission
    # and cache statistics (serving/service.py); single-shot runs carry
    # the well-formed disabled default
    serving = info.pop("serving", {"enabled": False})
    # schema v5: the dist driver's per-rank memory rollup (collective,
    # gathered before the report) folds into the perf section below
    perf_ranks = info.pop("perf_ranks", None)
    # schema v6: the memory governor's audit trail (resilience/memory.py
    # — budget, estimate, ladder rung, spill/reload accounting); runs
    # with no declared budget and no OOM carry the disabled default
    memory_budget = info.pop("memory_budget", {"enabled": False})
    # schema v7: the dist driver's per-rank attribution rollup
    # (collective, gathered before the report) folds into the quality
    # section below
    quality_ranks = info.pop("quality_ranks", None)
    # schema v8: the dist resilience audit trail (divergence-sentinel
    # counters + per-rank dump, shard fingerprints, the agreed ladder
    # rung, what was resumed) — annotated by the dist driver; shm runs
    # carry the well-formed disabled default
    dist_resilience = info.pop("dist_resilience", {"enabled": False})
    # schema v9: the out-of-core streaming audit trail (external/driver
    # annotates it: chunk counts, decoded vs uploaded bytes, the
    # upload/compute overlap fraction, fine-level device residency);
    # in-core runs carry the well-formed disabled default
    external = info.pop("external", {"enabled": False})
    # schema v10: the supervision audit trail (resilience/supervisor.py
    # — worker lifecycle, hang events, heartbeat, watchdog).  The
    # serving layer annotates its pool-aware view; otherwise the module
    # state is read directly (a single-shot run with a heartbeat or an
    # armed watchdog still reports), and a run that configured nothing
    # carries the well-formed disabled default.
    supervision = info.pop("supervision", None)
    if supervision is None:
        supervision = _supervision_section()
    # schema v11: the dynamic-repartitioning audit trail (kaminpar_tpu/
    # dynamic/) — live sessions (deltas applied, in-place vs rebuild
    # counts, chain digest), the warm/cold/replica decision log with
    # drift scores and diff-gate verdicts, and the per-step cut
    # trajectory.  Annotated by the chain driver / serving layer; runs
    # with no sessions carry the well-formed disabled default.
    dynamic = info.pop("dynamic", {"enabled": False})
    run = dict(info)
    if extra_run:
        run.update({k: jsonable(v) for k, v in extra_run.items()})

    levels = [
        {"level": e.attrs.get("level"), **{
            k: e.attrs[k]
            for k in ("n", "m", "retries", "n_pad", "m_pad",
                      "buffer_bytes")
            if k in e.attrs
        }}
        for e in _events("coarsening-level")
    ]

    # per-level rating-engine choices (ops/rating.select_engine via the
    # coarsener's `rating-engine` events) + a per-engine level count —
    # the report-field twin of the telemetry event, so "which engine ran
    # where and why" is a read (bench_trend renders the counts column)
    rating_levels = [
        {k: e.attrs[k]
         for k in ("level", "engine", "reason", "avg_degree",
                   "degree_skew", "n", "m")
         if k in e.attrs}
        for e in _events("rating-engine")
    ]
    rating_counts: Dict[str, int] = {}
    for lv in rating_levels:
        eng = lv.get("engine")
        if eng:
            rating_counts[eng] = rating_counts.get(eng, 0) + 1
    rating_section = {"levels": rating_levels, "engines": rating_counts}

    try:
        from ..parallel import mesh

        phase_totals = mesh.comm_phase_totals()
        comm = {
            "caveat": mesh.COMM_CAVEAT,
            "records": mesh.comm_records(),
            # opened-vs-traced lets report consumers spot cache-hit
            # phases (opened but zero traced rows) explicitly
            "phase_opens": mesh.phase_opens(),
            # schema v12 (additive): the per-phase rollup + grand total
            # ROADMAP item 4 asks for — "comm bytes per phase" as a
            # read, next to the raw per-(phase, op, shape) records
            "phases": phase_totals,
            "bytes_total": sum(
                t["bytes_total"] for t in phase_totals.values()
            ),
        }
    except Exception:  # mesh pulls in jax; stay robust without a backend
        comm = {"caveat": "comm accounting unavailable", "records": []}

    # schema v12: per-request trace timelines (telemetry/tracing.py) —
    # the serving layer's end-to-end spans (admission wait -> resolve ->
    # compute -> gate, plus the supervised-worker boundary rows);
    # non-serving runs carry the well-formed empty default
    try:
        from . import tracing as _tracing

        tracing_section = _tracing.snapshot()
    except Exception:
        tracing_section = {"enabled": False, "traces": []}

    # distributed finalize: per-scope min/avg/max across processes (the
    # kaminpar-dist/timer.cc analog); on one process min == avg == max.
    # This is itself a host-side collective — the `collective`
    # degradation site covers it: a sick link degrades the report to
    # local-only timers instead of hanging or dying.  Runs BEFORE the
    # event lists below are snapshotted so its own `degraded` event (if
    # any) lands in this report.
    from ..resilience import CollectiveTimeout, with_fallback

    def _aggregate():
        try:
            return timer.aggregate_across_processes()
        except (TypeError, AttributeError, KeyError, IndexError,
                AssertionError, NameError):
            # programming-shaped errors are bugs, not degradations —
            # they must stay loud (docs/static_analysis.md hazard note)
            raise
        except Exception as e:
            # infra-shaped failures (backend/link/timeout) degrade
            raise CollectiveTimeout(
                f"timer aggregation failed: {type(e).__name__}: {e}"
            ) from e

    agg = with_fallback(
        _aggregate, lambda exc: None, site="collective",
        where="report-timers",
    )

    report: Dict[str, Any] = {
        "schema_version": SCHEMA_VERSION,
        "environment": environment_stamp(),
        "run": run,
        "result": result,
        "scope_tree": _scope_tree(timer.GLOBAL_TIMER.root),
        "levels": levels,
        # schema v6 (additive): per-level rating-engine choices — the
        # density-adaptive selection audit trail (ops/rating.py)
        "rating": rating_section,
        "comm": comm,
        "events": [e.to_dict() for e in _events()],
        "counters": statistics.as_dict() if statistics.enabled() else {},
        # resilience sections: the active fault plan (and every injected
        # fault), each degradation the policy wrapper recorded, and the
        # output gate's verdict — the run report is the audit trail of
        # what degraded and whether the postcondition still held
        "faults": _fault_section(),
        "degraded": [e.to_dict() for e in _events("degraded")],
        "output_gate": gate_verdict,
        # schema v2: per-iteration convergence series from the
        # instrumented device loops (telemetry/progress.py) and the
        # compile-cost split (telemetry/compile_account.py) — together
        # they answer "what did the algorithms do" and "was the slow
        # part compile or execute"
        "progress": [p.to_dict() for p in _progress_series()],
        "compile": _compile_section(),
        # schema v3: preemption-safety audit trail — what was
        # checkpointed (and whether durability degraded to memory-only)
        # and whether the run wound down early under a deadline/signal
        "checkpoint": ckpt_summary,
        "anytime": anytime,
        # schema v4: partitioning-as-a-service — every request's verdict
        # (served/anytime/degraded/rejected/failed), admission caps, and
        # the bounded result/executable cache hit rates
        "serving": serving,
        # schema v5: the performance observatory — per-scope roofline
        # rows (FLOPs/bytes vs measured wall vs device peak), barrier
        # memory watermarks + per-level buffer bytes, and pad-waste
        # attribution per (scope, bucket)
        "perf": _perf_section(levels, perf_ranks),
        # schema v6: the memory-pressure governor — declared budget vs
        # estimate vs watermark, the recovery-ladder rung the run ended
        # at, and spill/reload byte accounting (docs/robustness.md)
        "memory_budget": memory_budget,
        # schema v7: the quality observatory — per-level cut-loss
        # attribution (coarsening_locked vs refinement_left vs the
        # level-0 lower bound), coarsening-quality stats, and
        # refinement-efficacy verdicts (telemetry/quality.py)
        "quality": _quality_section(quality_ranks),
        # schema v8: the dist resilience audit trail — cross-rank
        # divergence-sentinel counters (+ the per-rank dump when one
        # fired), the input's shard-fingerprint vector, the agreed
        # memory-ladder rung, and the dist resume record
        # (resilience/agreement.py, docs/robustness.md)
        "dist_resilience": dist_resilience,
        # schema v9: the out-of-core streaming (external scheme)
        # section — per-level chunk/byte/overlap accounting, the
        # handoff point, and the fine level's device residency (0 for
        # any run that actually streamed)
        "external": external,
        # schema v10: the supervision audit trail — worker lifecycle
        # counters (spawned/recycled/killed/crashed), hang events with
        # the stuck stage/scope path, heartbeat file + touch count, and
        # watchdog arm/fire counts (resilience/supervisor.py,
        # docs/robustness.md "Supervision contract")
        "supervision": supervision,
        # schema v11: dynamic repartitioning — graph sessions (delta
        # chains, in-place vs rebuild bucket accounting, chain
        # digests), warm/cold/replica decisions with drift scores and
        # the PR-4 diff-gate verdict per step, and the cut trajectory
        # (kaminpar_tpu/dynamic/, docs/robustness.md "Dynamic
        # sessions")
        "dynamic": dynamic,
        # schema v12: per-request trace timelines — one row per span
        # (name, origin service/worker, start_ms, duration_ms, attrs),
        # per trace id; the report half of the fleet observatory
        # (docs/observability.md "Request tracing")
        "tracing": tracing_section,
        # schema v13: the execution ledger — per-scope launch counts
        # (the launch-honest half of the perf roofline), the
        # host<->device transfer ledger aggregated per scope/kind and
        # per phase, and the donation audit {requested, honored,
        # bytes_saved} per scope (telemetry/ledger.py,
        # docs/observability.md "Execution ledger")
        "ledger": _ledger_section(),
        # schema v14: the integrity audit — invariant-sentinel checks
        # and violations (named invariant + level + scope), the
        # retry-from-last-good-barrier outcome, exchange-digest
        # computed/verified/mismatched tallies, and the sampled
        # re-execution audits {audited, mismatched} per scope
        # (resilience/integrity.py, docs/robustness.md "Integrity
        # contract")
        "integrity": _integrity_section(),
    }
    if agg is not None:
        report["timers_aggregated"] = agg

    from ..utils import heap_profiler

    if heap_profiler.profiling_enabled():
        report["heap"] = heap_profiler.tree_dict()
    return report


def write_run_report(path: str, extra_run: Optional[dict] = None) -> dict:
    """Build the report, write it to `path`, and return it.

    Collective on multi-host runs: every process must call this (the
    aggregated-timer section allgathers), but only process 0 writes the
    file — concurrent writers on a shared filesystem would interleave.
    The written report is process 0's local view plus the cross-process
    min/avg/max timers."""
    from . import is_primary_process

    report = build_run_report(extra_run=extra_run)
    if is_primary_process():
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
    return report
