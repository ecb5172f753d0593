#!/usr/bin/env python
"""Round benchmark: end-to-end partition quality vs the reference binary.

Partitions the medium bench RMAT graph (n=2^16, m=600k — the BASELINE.md
workload class at a size whose full pipeline fits comfortably in a bench
run) into k=16 at eps=0.03 with the default preset, entirely through the
product path (KaMinPar facade -> device kernels -> host IP), best of two
seeds — the same methodology as the recorded reference number — and
compares the edge cut against the reference KaMinPar binary's cut on the
SAME graph (BASELINE_CPU.json medium_edge_cut, measured with the binary
built from /root/reference; see scripts/measure_cpu_baseline.py).

Prints ONE JSON line:
  {"metric": "edge_cut_rmat600k_k16", "value": <our cut>, "unit": "cut",
   "vs_baseline": <reference_cut / our_cut>}
vs_baseline > 1 means our cut BEATS the reference binary's (the
BASELINE.md north star asks for within 3%, i.e. >= 0.97).  An infeasible
partition reports vs_baseline = 0.

Larger-scale numbers (10M-edge graph: cut 0.47x reference; scale-22
k=64: cut 0.63x reference) are tracked in docs/performance.md.
"""

from __future__ import annotations

import json
import os

#: Quality-attribution keys the BENCH line ALWAYS carries (the same
#: never-vanish contract as the 10M block: null marks a run whose report
#: produced no attribution, ABSENCE is a coverage regression —
#: scripts/bench_trend.py gates presence from r06 on, and check_all.sh
#: asserts this contract without running the full bench).
QUALITY_KEYS = ("coarsening_locked_frac", "refinement_left_frac")

#: Out-of-core streaming keys (round 13, kaminpar_tpu/external/): the
#: wall of a forced-budget `--scheme external` run of the medium bench
#: graph and its upload/compute overlap fraction — same never-vanish
#: contract (null = the measurement failed or was skipped, ABSENCE =
#: silent coverage loss, gated by bench_trend from r06 on).
EXTERNAL_KEYS = ("external_seconds", "stream_overlap")

#: Supervised-serving key (round 14, resilience/supervisor.py): p95 of
#: a small `--serve-isolation process` batch — the latency cost of the
#: hang/crash-containment boundary (spawn amortized over the warm
#: worker).  Same never-vanish contract (null = inproc/skipped/failed,
#: ABSENCE = silent coverage loss, gated by bench_trend from r06 on).
SUPERVISED_KEYS = ("supervised_p95_ms",)

#: Serving-throughput keys (round 16, fleet observatory): sliding-window
#: requests/second and mean padded-executable occupancy of the SAME
#: supervised batch the p95 comes from — same never-vanish contract
#: (null = skipped/failed, ABSENCE = silent coverage loss, gated by
#: bench_trend from r06 on).
THROUGHPUT_KEYS = ("requests_per_second", "batch_occupancy")


def supervised_key(p95_ms=None) -> dict:
    """The BENCH line's supervised-serving key; always present, null
    when the supervised measurement was skipped or failed."""
    return {"supervised_p95_ms": p95_ms}


def throughput_keys(rps=None, occupancy=None) -> dict:
    """The BENCH line's serving-throughput keys; always present, null
    when the supervised measurement was skipped or failed."""
    return {"requests_per_second": rps, "batch_occupancy": occupancy}


#: Dynamic-repartitioning keys (round 15, kaminpar_tpu/dynamic/):
#: warm-vs-cold wall speedup and the max warm-vs-cold-twin cut drift
#: over a short delta chain on the medium bench graph.  Same
#: never-vanish contract (null = skipped/failed, ABSENCE = silent
#: coverage loss, gated by bench_trend from r06 on).
DYNAMIC_KEYS = ("dynamic_warm_speedup", "dynamic_cut_drift")


def dynamic_keys(speedup=None, drift=None) -> dict:
    """The BENCH line's dynamic-repartitioning keys; always present,
    null when the dynamic measurement was skipped or failed."""
    return {"dynamic_warm_speedup": speedup, "dynamic_cut_drift": drift}


def _measure_dynamic():
    """A 4-step ~1% churn delta chain on the medium bench graph: per
    step, a warm-started v-cycle repartition AND its cold twin from
    scratch.  Returns (warm_speedup, cut_drift): mean cold wall / mean
    warm wall, and the max fractional cut gap warm-vs-cold-twin —
    the dynamic acceptance pair (warm must be faster, and within the
    diff gate of the cold run it replaces)."""
    import time

    from kaminpar_tpu.dynamic import GraphSession, synth_chain
    from kaminpar_tpu.dynamic.repartition import repartition
    from kaminpar_tpu.graphs.factories import generate
    from kaminpar_tpu.kaminpar import KaMinPar, context_from_preset

    graph = generate(f"rmat;n={MED_N};m={MED_M};seed={MED_SEED}")
    batches = synth_chain(graph, steps=4, seed=41, edge_churn=0.01)
    ctx = context_from_preset("default")
    session = GraphSession("bench", graph, k=BENCH_K)
    solver = KaMinPar(ctx)
    solver.set_graph(session.graph)
    part = solver.compute_partition(k=BENCH_K, epsilon=BENCH_EPS, seed=1)
    m0 = solver.result_metrics(session.graph, part)
    session.commit_partition(part, int(m0["cut"]))

    warm_walls, cold_walls, drifts = [], [], []
    for i, batch in enumerate(batches):
        session.apply(batch)
        out = repartition(session, ctx, k=BENCH_K, epsilon=BENCH_EPS,
                          seed=1)
        warm_walls.append(
            out.warm_wall_s if out.warm_wall_s is not None
            else out.wall_s)
        # the cold twin: the per-step from-scratch run warm replaced
        cold_solver = KaMinPar(context_from_preset("default"))
        cold_solver.set_graph(session.graph)
        t0 = time.perf_counter()
        cold_part = cold_solver.compute_partition(
            k=BENCH_K, epsilon=BENCH_EPS, seed=1)
        cold_walls.append(time.perf_counter() - t0)
        cold_cut = int(cold_solver.result_metrics(
            session.graph, cold_part)["cut"])
        drifts.append(abs(out.cut - cold_cut) / max(cold_cut, 1))
    speedup = (sum(cold_walls) / len(cold_walls)) / max(
        sum(warm_walls) / len(warm_walls), 1e-9)
    return round(speedup, 2), round(max(drifts), 4)


def lint_keys(seconds=None) -> dict:
    """The BENCH line's static-analysis key (round 17, tpulint v2):
    wall seconds of a full-package `lint_paths` run with every rule
    (call graph + R9 schema pins included) — the analysis itself is a
    commit-gate stage, so its cost is a trend worth watching.  Always
    present, null when the lint run errored."""
    return {"tpulint_seconds": seconds}


def _measure_lint():
    """Wall seconds of one full-rule tpulint pass over the package."""
    import time

    from kaminpar_tpu.lint import LintConfig, lint_paths

    pkg = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "kaminpar_tpu")
    t0 = time.perf_counter()
    findings = lint_paths([pkg], LintConfig())
    seconds = time.perf_counter() - t0
    assert findings == [], (
        f"bench lint pass found {len(findings)} finding(s); the package "
        "must stay clean")
    return round(seconds, 2)


def quality_keys(report) -> dict:
    """The BENCH line's quality-attribution keys from an embedded run
    report (telemetry/quality.py totals); every key present, null when
    the report carries no attribution."""
    totals = ((report or {}).get("quality") or {}).get("totals") or {}
    return {key: totals.get(key) for key in QUALITY_KEYS}


#: Execution-ledger keys (round 19, telemetry/ledger.py): whether the
#: headline hbm_util came from launch-joined measured figures (every
#: launch ran a cost-captured executable — `honest`), the total launch
#: count, and the per-phase host<->device transfer bytes.  Same
#: never-vanish contract (null = the report carries no ledger, ABSENCE
#: = silent coverage loss, gated by bench_trend from r06 on).
LEDGER_KEYS = ("util_honest", "launches_total", "transfer_bytes_per_phase")


def ledger_keys(report) -> dict:
    """The BENCH line's execution-ledger keys from an embedded run
    report; every key present, null when the report has no ledger."""
    rep = report or {}
    perf_totals = (rep.get("perf") or {}).get("totals") or {}
    ledger = rep.get("ledger") or {}
    by_phase = (ledger.get("transfers") or {}).get("by_phase") or None
    return {
        "util_honest": perf_totals.get("util_honest"),
        "launches_total": perf_totals.get("launches"),
        "transfer_bytes_per_phase": by_phase,
    }


def external_keys(seconds=None, overlap=None) -> dict:
    """The BENCH line's out-of-core streaming keys; every key present,
    null when the external measurement was skipped or failed."""
    return {"external_seconds": seconds, "stream_overlap": overlap}


def _measure_external():
    """One `--scheme external` partition of the medium bench graph under
    a forced budget at 25% of its in-core estimate: (wall seconds,
    overlap fraction from the run's `external` report section).  The
    scale half of the north star gets a trend line next to the in-core
    kernels."""
    import time

    import numpy as np

    from kaminpar_tpu import telemetry
    from kaminpar_tpu.context import PartitioningMode
    from kaminpar_tpu.graphs.factories import generate
    from kaminpar_tpu.kaminpar import KaMinPar, context_from_preset
    from kaminpar_tpu.resilience.memory import estimate_run_bytes

    graph = generate(f"rmat;n={MED_N};m={MED_M};seed={MED_SEED}")
    ctx = context_from_preset("default")
    ctx.partitioning.mode = PartitioningMode.EXTERNAL
    ctx.resilience.memory_budget = float(
        int(estimate_run_bytes(graph.n, graph.m, BENCH_K) * 0.25)
    )
    solver = KaMinPar(ctx)
    solver.set_graph(graph)
    # the external section rides on the telemetry stream; this
    # measurement runs AFTER the main loop disabled telemetry, so it
    # must arm its own stream or overlap would be permanently null —
    # the r05 silent-coverage-loss class, just for the new keys
    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        t0 = time.perf_counter()
        part = solver.compute_partition(
            k=BENCH_K, epsilon=BENCH_EPS, seed=1
        )
        wall = time.perf_counter() - t0
        assert len(part) == graph.n and len(np.unique(part)) <= BENCH_K
        section = telemetry.run_info().get("external") or {}
        overlap = section.get("overlap_frac")
    finally:
        if not was_enabled:
            telemetry.disable()
        telemetry.reset()
    return round(wall, 2), overlap


MED_N = 1 << 16
MED_M = 600_000
MED_SEED = 3
BENCH_K = 16
BENCH_EPS = 0.03


def _init_platform() -> None:
    """Place the persistent compile cache and require a TPU: a bench
    line is a device measurement, so a machine without a chip is a
    failure, never a CPU number under a device metric's name."""
    from kaminpar_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench: no TPU (jax.devices()[0] is {dev.platform}:"
            f"{dev.device_kind}); the bench measures the chip and does "
            "not fall back to the CPU"
        )


def _measure_large_coarsening(
    reps: int = 2, budget_s: float = 0.0
) -> float | None:
    """LP+coarsening wall-clock on the LARGE (10M-edge) bench graph —
    the scale where the repo's CPU-vs-TPU comparison is meaningful (the
    medium graph is launch-floor-dominated; see docs/performance.md).
    Same graph and phase boundary as BASELINE_CPU.json's
    large10m_coarsening_s (scripts/measure_cpu_baseline.py --large).
    Returns seconds (best of `reps` runs — the first pays
    executable-cache loads even when compiled; the CPU denominator is
    likewise the binary's fastest run), or None on failure (the bench
    line then reports the large-graph keys as null).

    `budget_s` > 0 bounds the measurement wall (the CPU fallback): a
    run that blows the budget mid-hierarchy reports None — a null
    metric, never a silently-partial number."""
    import time

    import jax.numpy as jnp

    from kaminpar_tpu.graphs.csr import device_graph_from_host
    from kaminpar_tpu.graphs.factories import make_rmat
    from kaminpar_tpu.partitioning.coarsener import Coarsener
    from kaminpar_tpu.presets import create_context_by_preset_name

    host = make_rmat(1 << 20, 10_000_000, seed=7)
    ctx = create_context_by_preset_name("default")
    ctx.partition.setup(host, k=BENCH_K, epsilon=BENCH_EPS)
    ctx.seed = 1
    best = None
    for _ in range(max(reps, 1)):
        dgraph = device_graph_from_host(host)
        int(jnp.sum(dgraph.src[:1]))  # force the upload before timing
        coarsener = Coarsener(ctx, dgraph, host.n)
        threshold = max(2 * ctx.coarsening.contraction_limit, 2)
        t0 = time.perf_counter()
        while coarsener.current_n > threshold:
            if budget_s > 0 and time.perf_counter() - t0 > budget_s:
                import sys

                print(
                    f"bench: 10M coarsening blew its {budget_s:.0f}s "
                    "budget; reporting null",
                    file=sys.stderr,
                )
                return best
            if not coarsener.coarsen():
                break
        int(jnp.sum(coarsener.current.src[:1]))  # readback-synced stop
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def _measure_large_total(reps: int = 2, time_budget: float = 0.0):
    """Full end-to-end partition of the 10M-edge bench graph (default
    preset, warm cache): total wall + cut.  Catches SCALE regressions the
    medium line cannot (VERDICT r3 weak #4); compares against the
    reference binary's cut on the same graph
    (BASELINE_CPU.json large10m_edge_cut).

    `time_budget` > 0 arms the PR-5 anytime deadline so the CPU
    fallback stays wall-bounded: the run winds down at a pipeline
    barrier and still returns a gate-valid partition (cut/feasible stay
    honest numbers; the wall reads as the budget ceiling)."""
    import time

    import numpy as np

    from kaminpar_tpu.graphs.factories import make_rmat
    from kaminpar_tpu.graphs.host import host_partition_metrics
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.utils.logger import OutputLevel

    host = make_rmat(1 << 20, 10_000_000, seed=7)
    # best of `reps`: the first run pays per-process executable-cache
    # loads even when fully compiled (solo warm steady state is the
    # honest figure; the CPU denominator is likewise the binary's
    # fastest run)
    total = None
    part = None
    for _ in range(max(reps, 1)):
        p = KaMinPar("default")
        if time_budget > 0:
            p.ctx.resilience.time_budget = float(time_budget)
        p.set_output_level(OutputLevel.QUIET)
        t0 = time.perf_counter()
        part = p.set_graph(host).compute_partition(
            k=BENCH_K, epsilon=BENCH_EPS, seed=1
        )
        dt = time.perf_counter() - t0
        total = dt if total is None else min(total, dt)
    res = host_partition_metrics(host, part, BENCH_K)
    nw = host.node_weight_array()
    cap = (1 + BENCH_EPS) * np.ceil(nw.sum() / BENCH_K)
    feasible = bool(res["block_weights"].max() <= cap)
    return round(total, 1), int(res["cut"]), feasible


def _measure_utilization():
    """Achieved-bandwidth probes for the primitive ops the pipeline is
    built from (VERDICT r3: prove or break the 'structural floor' with
    utilization data).  Useful bytes / wall vs the running device's
    published HBM peak (telemetry/perf.DEVICE_PEAKS); the scalar gather
    lands around 0.1% — the per-index
    cost is XLA's lowering, not the memory system (full table:
    scripts/microbench_gather.py, docs/performance.md round-4 section)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kaminpar_tpu.telemetry.perf import device_peaks

    peak_gbps, _ = device_peaks(jax.devices()[0].device_kind)
    M, N = 1 << 24, 1 << 20
    rng = np.random.RandomState(0)
    dst = jnp.asarray(rng.randint(0, N, M).astype(np.int32))
    tab = jnp.asarray(rng.randint(0, 100, N).astype(np.int32))
    vals = jnp.asarray(rng.randint(0, 100, M).astype(np.int32))

    def probe(fn, useful_bytes, *args):
        f = jax.jit(fn)
        out = f(*args)
        int(jnp.sum(jax.tree_util.tree_leaves(out)[0].reshape(-1)[:1]))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = f(*args)
            int(jnp.sum(jax.tree_util.tree_leaves(out)[0].reshape(-1)[:1]))
            best = min(best, time.perf_counter() - t0)
        return round(100.0 * useful_bytes / best / 1e9 / peak_gbps, 3)

    out = {
        "util_gather_pct_hbm": probe(
            lambda t, d: t[d], M * 12, tab, dst
        ),
        "util_scatter_add_pct_hbm": probe(
            lambda v, d: jnp.zeros(N, jnp.int32).at[d].add(v),
            M * 12 + N * 8, vals, dst,
        ),
        "util_stream_cumsum_pct_hbm": probe(
            jnp.cumsum, M * 8, vals
        ),
    }
    return out


def _bench_line() -> dict:
    import numpy as np

    _init_platform()

    from kaminpar_tpu.graphs.factories import make_rmat
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.utils.logger import OutputLevel

    from kaminpar_tpu.graphs.host import host_partition_metrics

    host = make_rmat(MED_N, MED_M, seed=MED_SEED)
    nw = host.node_weight_array()
    cap = (1 + BENCH_EPS) * np.ceil(nw.sum() / BENCH_K)

    # best of two seeds — the same methodology the recorded reference
    # number uses (BASELINE_CPU.json medium_note: best of seeds 1-2);
    # a feasible candidate always beats an infeasible one
    import time

    from kaminpar_tpu.utils import timer

    # telemetry for the embedded run report: the BENCH line carries the
    # same schema as --report-json so the perf trajectory and ad-hoc
    # runs are directly comparable (telemetry/run_report.schema.json).
    # Spans must accrue DURING the run, so telemetry is on inside the
    # timed region; the facade's result-metrics pass that entails costs
    # ~14 ms on the medium graph (<1% of total_seconds — within seed
    # noise vs pre-telemetry BENCH lines).
    from kaminpar_tpu import telemetry

    telemetry.enable()

    # integrity-sentinel overhead accrues on the module's wall counter
    # (resilience/integrity.py): zero it here so the measured region is
    # exactly the timed seeds below, not any warmup run before them
    from kaminpar_tpu.resilience import integrity as integrity_mod

    integrity_mod.reset()

    best = None
    coarsening_times = []
    total_times = []
    lp_times = []
    contraction_times = []
    for seed in (1, 2):
        p = KaMinPar("default")
        p.set_output_level(OutputLevel.QUIET)
        t0 = time.perf_counter()
        cand = p.set_graph(host).compute_partition(
            k=BENCH_K, epsilon=BENCH_EPS, seed=seed
        )
        total_times.append(time.perf_counter() - t0)  # returns synced numpy
        # LP clustering + contraction wall-clock of this run, from the
        # hierarchical timer (compute_partition resets it; the coarsener
        # forces a scalar readback inside each lp scope, so attribution
        # is honest on the async remote backend).  The per-kernel split
        # (lp-clustering vs contraction) feeds the bench_trend kernel
        # columns — "which kernel regressed" is a read, not a dig.
        coarsening_times.append(
            timer.GLOBAL_TIMER.elapsed("partitioning", "coarsening")
        )
        lp_times.append(
            timer.GLOBAL_TIMER.elapsed(
                "partitioning", "coarsening", "lp-clustering"
            )
        )
        contraction_times.append(
            timer.GLOBAL_TIMER.elapsed(
                "partitioning", "coarsening", "contraction"
            )
        )
        cand_res = host_partition_metrics(host, cand, BENCH_K)
        cand_feasible = bool(cand_res["block_weights"].max() <= cap)
        # capture this run's report before the next compute resets the
        # telemetry stream; keep the one belonging to the best candidate
        try:
            from kaminpar_tpu.telemetry.report import build_run_report

            cand_report = build_run_report(extra_run={"bench_seed": seed})
        except Exception as e:  # never let telemetry break the line
            import sys

            print(f"bench: run-report build failed: {e}", file=sys.stderr)
            cand_report = None
        key = (not cand_feasible, cand_res["cut"])
        if best is None or key < best[0]:
            best = (key, cand_res, cand_feasible, cand_report)
    _, res, feasible, best_report = best
    telemetry.disable()
    cut = res["cut"]
    # times are min-over-seeds (steady state): the first seed's run may
    # include remote XLA compiles / cache loads, and the CPU denominator
    # is likewise the binary's fastest run
    coarsening_s = min(coarsening_times)
    total_s = min(total_times)
    # sentinel wall over BOTH timed seeds vs their total compute wall:
    # the < 3% dormancy budget as a measured figure, not a claim
    integrity_overhead = integrity_mod.overhead_pct(sum(total_times))

    vs = 0.0
    vs_cpu = None
    vs_cpu_10m = None
    coarsening_10m_s = None
    base = {}
    baseline_path = os.path.join(os.path.dirname(__file__), "BASELINE_CPU.json")
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            base = json.load(f)
        ref = base.get("medium_edge_cut")
        if feasible and ref:
            vs = ref / max(cut, 1)
        cpu_coarsening = base.get("medium_coarsening_s")
        if cpu_coarsening and coarsening_s > 0.01:
            # >1 means the TPU coarsening phase is FASTER than the
            # reference binary's (8-thread) coarsening on the same graph
            vs_cpu = round(cpu_coarsening / coarsening_s, 3)

    # large-graph speed ratio at >=10M edges — the scale that decides
    # the CPU-vs-TPU story.  The keys must never vanish from the
    # trajectory (BENCH_r05 dropped them silently).
    # KAMINPAR_TPU_BENCH_LARGE_BUDGET_S arms the PR-5 anytime deadline
    # around the end-to-end wall (default 0 = unbudgeted);
    # KAMINPAR_TPU_BENCH_SKIP_LARGE=1 skips for quick runs.
    total_10m = cut_10m = feasible_10m = None
    util = {}
    import jax as _jax

    platform = _jax.devices()[0].platform  # "tpu", by _init_platform
    if (
        base.get("large10m_coarsening_s")
        and os.environ.get("KAMINPAR_TPU_BENCH_SKIP_LARGE", "") != "1"
    ):
        reps = 2
        budget = float(
            os.environ.get("KAMINPAR_TPU_BENCH_LARGE_BUDGET_S", "") or 0.0
        )
        try:
            coarsening_10m_s = _measure_large_coarsening(
                reps=reps, budget_s=budget
            )
        except Exception as e:  # never let the large run break the line
            import sys

            print(f"bench: large-graph measurement failed: {e}",
                  file=sys.stderr)
        if coarsening_10m_s and coarsening_10m_s > 0.01:
            vs_cpu_10m = round(
                base["large10m_coarsening_s"] / coarsening_10m_s, 3
            )
        try:
            total_10m, cut_10m, feasible_10m = _measure_large_total(
                reps=reps, time_budget=budget
            )
        except Exception as e:
            import sys

            print(f"bench: 10M end-to-end failed: {e}", file=sys.stderr)
    if os.environ.get("KAMINPAR_TPU_BENCH_SKIP_LARGE", "") != "1":
        # the kernel-utilization probes are seconds of work on any
        # platform — they ride every run (platform stamps the context:
        # on the CPU fallback they are smoke signals, not measurements)
        try:
            util = _measure_utilization()
        except Exception as e:
            import sys

            print(f"bench: utilization probe failed: {e}", file=sys.stderr)

    line = {
        "metric": "edge_cut_rmat600k_k16",
        "value": cut,
        "unit": "cut",
        "vs_baseline": round(vs, 3),
        "lp_coarsening_seconds": round(coarsening_s, 2),
        "total_seconds": round(total_s, 2),
        # per-kernel split of the coarsening wall (min over seeds, same
        # steady-state rule as coarsening_s) — the bench_trend kernel
        # regression gate reads these
        "kernel_seconds": {
            "lp": round(min(lp_times), 2),
            "contraction": round(min(contraction_times), 2),
        },
        # the device every wall figure in this line was taken on
        # (_init_platform refuses anything but a TPU)
        "platform": platform,
    }
    if vs_cpu is not None:
        line["vs_cpu_coarsening"] = vs_cpu
    # the 10M block is ALWAYS present (BENCH_r05 dropped it silently;
    # bench_trend --check now fails a round that loses these keys) —
    # null means the measurement errored, not that it was skipped
    line["lp_coarsening_10m_seconds"] = (
        round(coarsening_10m_s, 2) if coarsening_10m_s is not None else None
    )
    line["vs_cpu_coarsening_10m"] = vs_cpu_10m
    line["total_10m_seconds"] = total_10m
    line["cut_10m"] = cut_10m
    line["feasible_10m"] = feasible_10m
    ref_10m = base.get("large10m_edge_cut_k16")
    line["vs_baseline_cut_10m"] = (
        round(ref_10m / max(cut_10m, 1), 3)
        if (ref_10m and cut_10m and feasible_10m) else None
    )
    line.update(util)
    # the probe keys share the 10M block's always-present contract
    # (bench_trend gates on ABSENCE; null marks a skipped/failed probe)
    for key in ("util_gather_pct_hbm", "util_scatter_add_pct_hbm",
                "util_stream_cumsum_pct_hbm"):
        line.setdefault(key, None)
    # quality-attribution headline (telemetry/quality.py): which share
    # of the per-level cut gap is locked by coarsening vs left by
    # refinement — ALWAYS present (null = no attribution recorded), so
    # the trajectory can never silently lose the quality signal
    line.update(quality_keys(best_report))
    # out-of-core streaming coverage (round 13): a forced-budget
    # external run of the medium graph — always-present keys (null =
    # skipped/failed), so the scale path can never silently drop out
    # of the trajectory like the r05 10M block did
    ext_seconds = ext_overlap = None
    if os.environ.get("KAMINPAR_TPU_BENCH_SKIP_LARGE", "") != "1":
        try:
            ext_seconds, ext_overlap = _measure_external()
        except Exception as e:
            import sys

            print(f"bench: external measurement failed: {e}",
                  file=sys.stderr)
    line.update(external_keys(ext_seconds, ext_overlap))
    # supervised-serving latency (round 14): the containment boundary's
    # p95 — always-present key (null = skipped/failed), same r05-class
    # presence contract as the 10M/external blocks
    # — and null it stays: one process for each chip.  This parent has
    # partitioned three graphs and holds the chip, so a spawned worker
    # (resilience/supervisor.py) would fail to get it, or come up on
    # the CPU and answer from there.  The leg needs a parent that never
    # touches JAX (ROADMAP C7).
    import sys

    print("bench: supervised measurement skipped: the parent holds the "
          "chip and a spawned worker cannot share it", file=sys.stderr)
    sup_p95 = sup_rps = sup_occ = None
    line.update(supervised_key(sup_p95))
    # serving-throughput coverage (round 16, fleet observatory): the
    # same batch's rps + mean executable occupancy — always-present
    # keys (null = skipped/failed), same r05-class presence contract
    line.update(throughput_keys(sup_rps, sup_occ))
    # dynamic-repartitioning coverage (round 15): warm-vs-cold speedup
    # and cut drift over a short delta chain — always-present keys
    # (null = skipped/failed), same r05-class presence contract
    dyn_speedup = dyn_drift = None
    if os.environ.get("KAMINPAR_TPU_BENCH_SKIP_LARGE", "") != "1":
        try:
            dyn_speedup, dyn_drift = _measure_dynamic()
        except Exception as e:
            import sys

            print(f"bench: dynamic measurement failed: {e}",
                  file=sys.stderr)
    line.update(dynamic_keys(dyn_speedup, dyn_drift))
    # static-analysis coverage (round 17, tpulint v2): the commit gate's
    # own wall — always-present key (null = errored), same r05-class
    # presence contract; also re-asserts the zero-finding state from
    # inside the bench
    lint_s = None
    try:
        lint_s = _measure_lint()
    except Exception as e:
        import sys

        print(f"bench: lint measurement failed: {e}", file=sys.stderr)
    line.update(lint_keys(lint_s))
    # launch-honest utilization + transfer-bytes coverage (round 19,
    # execution ledger): whether the perf headline is launch-joined
    # truth or a compile-time lower bound, plus where the host<->device
    # bytes went — always-present keys, same r05-class presence contract
    line.update(ledger_keys(best_report))
    # integrity-sentinel overhead (round 20, resilience/integrity.py):
    # host-side sentinel wall as a percentage of the measured partition
    # wall — ALWAYS present (0.0 when the kill switch disabled the
    # layer), same r05-class presence contract, advisory column in
    # bench_trend
    line["integrity_overhead_pct"] = integrity_overhead
    if best_report is not None:
        # rating-engine choices of the best run (ops/rating.py
        # selection, from the embedded report's `rating` section):
        # per-engine level counts, e.g. {"scatter": 3, "dense": 4}
        line["rating_engines"] = (
            best_report.get("rating", {}).get("engines", {})
        )
        # perf-observatory headline figures promoted next to cut/seconds
        # (the full per-scope breakdown rides in the embedded report's
        # `perf` section; scripts/bench_trend.py renders these columns)
        perf_totals = best_report.get("perf", {}).get("totals", {})
        for src, dst in (("hbm_util", "hbm_util"),
                         ("pad_waste", "pad_waste")):
            if perf_totals.get(src) is not None:
                line[dst] = perf_totals[src]
        # drop only OPTIONAL sections; everything the schema requires
        # (including events) stays, so the embedded report validates
        # against run_report.schema.json exactly like a --report-json file
        line["report"] = {
            k: v for k, v in best_report.items()
            if k not in ("timers_aggregated", "heap")
        }
    return line


#: stderr lines carrying any of these markers are machine noise, not
#: measurement output: the BENCH_r05 recorded tail was ~2 KB of ONE
#: XLA:CPU AOT loader machine-feature banner (cpu_aot_loader.cc
#: "Target machine feature ... not supported"), which drowned every
#: informative bench diagnostic out of the harness's tail window.
STDERR_NOISE_MARKERS = ("cpu_aot_loader.cc",)

#: Recorded-tail budget: after noise stripping, only the LAST lines up
#: to this many bytes are re-emitted (the harness tails stderr, so the
#: newest diagnostics are the ones that must survive).
STDERR_TAIL_CAP = 2048


def _filter_stderr_tail(raw: bytes) -> bytes:
    """Strip known-noise lines from captured bench stderr and keep the
    last genuinely informative lines within STDERR_TAIL_CAP bytes.

    Whole-line filtering only — any line without a noise marker passes
    through verbatim, so real warnings are never rewritten."""
    kept = [
        ln for ln in raw.decode("utf-8", "replace").splitlines()
        if ln.strip() and not any(m in ln for m in STDERR_NOISE_MARKERS)
    ]
    tail: list = []
    size = 0
    for ln in reversed(kept):
        size += len(ln) + 1
        if size > STDERR_TAIL_CAP and tail:
            break
        tail.append(ln)
    text = "\n".join(reversed(tail))
    return (text + "\n").encode("utf-8") if text else b""


def main() -> None:
    """Print the BENCH JSON line as the SOLE stdout line.

    Harness parsing used to depend on "the last stdout line survives XLA
    AOT loader warnings"; now every byte the measurement emits — python
    prints AND C-level noise (XLA loaders, absl banners) — is routed to
    stderr at the file-descriptor level, and only the final JSON line is
    written to the real stdout.  The stderr stream itself is captured
    and re-emitted through _filter_stderr_tail, so the harness's
    recorded tail carries the bench's own diagnostics instead of the
    ~2 KB cpu_aot_loader.cc machine-feature banner (the BENCH_r05 tail
    regression)."""
    import sys
    import tempfile

    sys.stdout.flush()
    sys.stderr.flush()
    real_stdout = os.dup(1)
    real_stderr = os.dup(2)
    cap = tempfile.TemporaryFile()
    os.dup2(cap.fileno(), 2)  # capture stderr for noise filtering
    os.dup2(2, 1)  # fd-level: C/C++ writes to fd 1 land on stderr too
    try:
        line = _bench_line()
    finally:
        sys.stdout.flush()
        sys.stderr.flush()
        os.dup2(real_stdout, 1)
        os.dup2(real_stderr, 2)
        os.close(real_stdout)
        os.close(real_stderr)
        try:
            cap.seek(0)
            filtered = _filter_stderr_tail(cap.read())
            if filtered:
                sys.stderr.buffer.write(filtered)
                sys.stderr.buffer.flush()
        except Exception:
            pass  # tail filtering must never eat the BENCH line
        finally:
            cap.close()
    print(json.dumps(line))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
