#!/usr/bin/env python3
"""Chip smoke: the deep-multilevel path, end to end, on one TPU.

The quickest proof that the system still starts on the chip.  One
process, no children, JAX imported once.  In order:

  1. print what JAX sees and exit non-zero unless it is a TPU — before
     any graph is built.  No flag or variable makes this pass on a CPU;
  2. the medium bench graph (rmat n=2^16 m=600k, k=16) through the CLI
     in-process with ``--report-json``: the cold run, XLA compilation
     included;
  3. the same graph through the facade: the warm run, every executable
     already compiled.  The two partitions must be bitwise equal;
  4. with >= 4 devices, the same graph through ``dKaMinPar`` on a
     four-device mesh.

Size.  The contract is a pass within 1200 s with nothing compiled
beforehand, and on the v5e XLA's compile time, not the graph, sets the
wall: the medium graph took 867 s cold of which 852 s were compilation,
and the 10M-edge bench graph (rmat n=2^20, the smallest with a
reference cut that crosses the ``1 << 22`` edge-slot gates) took 1743 s
cold, 1637 s of them compilation (my chip runs, PR 21, in CHANGES.md).
Compile cost goes with the number of distinct level shapes, not with
their size: a graph at 2^22 edge slots adds at least one level (60-90 s
of LP-clustering compile alone) and the gated programs to the medium
graph's ~870 s, so none is expected to fit the limit cold (an estimate
from those two runs; no size in between was measured); the smoke says
so in its result (``size_note``).  Telemetry stays on for the
warm run: turning it off changes the loop carries (telemetry/progress.py)
and so mints every loop executable a second time.

Every partition is checked by the repository's own means
(``graphs/host.host_partition_metrics``), its cut is recomputed once
more by three lines of numpy, and it must be feasible and no worse than
the reference binary's cut (``BASELINE_CPU.json``).  Every run report
must show that nothing degraded on the way.  Any failed check raises:
the exit code is non-zero and no result line is printed.

The last line of standard output is one JSON object with exactly the
keys the driver reads and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Everything measured on the way (walls, compile seconds, cache hits, the
Pallas compilers' answers) is the ``chip_smoke: result:`` line before it
and ``chiprun_out/chip_smoke/result.json``, next to the run reports.

Usage:  python3 chip_smoke.py        (from the root of a checkout)
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

MEDIUM = "gen:rmat;n=65536;m=600000;seed=3"
K, EPS, SEED = 16, 0.03, 1
DIST_DEVICES = 4
SIZE_NOTE = (
    "medium bench graph (1,083,716 directed edge slots, padded to 2^21): "
    "below the 1 << 22 size gates (delta rounds, device extend), "
    "because a graph at or above them is not "
    "expected to compile inside the 1200 s limit from a cold cache "
    "(medium: ~870 s cold, 10M edges: 1743 s, nothing in between "
    "measured); the 10M-edge run that crosses them is recorded in "
    "CHANGES.md (PR 21)"
)


class SmokeFailure(AssertionError):
    """One check of the smoke failed; the message names it."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# checks (tests/test_chip_smoke.py calls these directly on a small graph)
# ---------------------------------------------------------------------------


def check_partition(graph, part, k, eps, ref_cut, reported_cut) -> dict:
    """Shape, label range, cut (the repository's host metrics, a second
    numpy recomputation and the run's own reported figure must agree),
    balance, and the reference binary's cut as an upper bound (`ref_cut`
    None: no bound)."""
    import numpy as np

    from kaminpar_tpu.graphs.host import host_partition_metrics

    part = np.asarray(part)
    require(part.shape == (graph.n,),
            f"partition shape {part.shape} != ({graph.n},)")
    require(np.issubdtype(part.dtype, np.integer),
            f"partition dtype {part.dtype} is not an integer type")
    require(int(part.min()) >= 0 and int(part.max()) < k,
            f"labels outside [0, {k}): [{part.min()}, {part.max()}]")
    metrics = host_partition_metrics(graph, part, k)
    cut = int(metrics["cut"])
    # the cut once more, independent of graphs/host.py's helpers
    src = np.repeat(np.arange(graph.n), np.diff(graph.xadj))
    crossing = part[src] != part[graph.adjncy]
    recut = int(graph.edge_weight_array()[crossing].sum()) // 2
    require(cut == recut,
            f"host_partition_metrics cut {cut} != numpy recomputation {recut}")
    require(cut == int(reported_cut),
            f"recomputed cut {cut} != the run's reported cut {reported_cut}")
    total = int(graph.node_weight_array().sum())
    cap = (1 + eps) * -(-total // k)
    max_bw = int(metrics["block_weights"].max())
    require(max_bw <= cap,
            f"max block weight {max_bw} > (1+eps)*ceil(W/k) = {cap}")
    require(ref_cut is None or cut <= ref_cut,
            f"cut {cut} is worse than the reference binary's {ref_cut}")
    return {"cut": cut, "imbalance": float(metrics["imbalance"]),
            "feasible": True, "max_block_weight": max_bw}


def check_report(report: dict) -> None:
    """Nothing degraded on the way: no `degraded` event, the memory
    ladder dormant or at rung 0, integrity clean, output gate valid."""
    degraded = report["degraded"]
    require(not degraded, f"degraded events: {json.dumps(degraded)}")
    mem = report["memory_budget"]
    require(not mem.get("enabled") or int(mem.get("rung", 0)) == 0,
            f"memory ladder engaged: {json.dumps(mem)}")
    integrity = report["integrity"]
    require(integrity.get("verdict") == "clean",
            f"integrity verdict: {json.dumps(integrity)}")
    gate = report["output_gate"]
    require(bool(gate.get("checked")) and bool(gate.get("valid")),
            f"output gate: {json.dumps(gate)}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def require_tpu() -> dict:
    """Print what JAX sees; exit non-zero unless it is a TPU."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"chip_smoke: jax {jax.__version__} devices={devices} "
          f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        sys.exit(
            f"chip_smoke: FAIL: no TPU: jax.devices()[0] is "
            f"{dev.platform}:{dev.device_kind}. This script only passes "
            "on a TPU; run it through the chip tool."
        )
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def require_on_tpu(arrays, what: str) -> None:
    """Do not trust the environment: the arrays themselves sit on a TPU."""
    for arr in arrays:
        platforms = {d.platform for d in arr.devices()}
        require(platforms == {"tpu"}, f"{what} sits on {arr.devices()}")


def block_until_ready_blocks() -> dict:
    """Does `block_until_ready` wait for the device?  Time a launch that
    takes a while to its return, to `block_until_ready`, and to a scalar
    readback after it: if it blocks, the readback adds ~nothing."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(0, 64, lambda _, a: jnp.sort(a[::-1]), x)

    x = jnp.arange(1 << 22, dtype=jnp.int32)
    int(work(x)[0])  # compile and run once
    t0 = time.perf_counter()
    y = work(x)
    t_dispatch = time.perf_counter() - t0
    y.block_until_ready()
    t_blocked = time.perf_counter() - t0
    int(y[0])
    t_readback = time.perf_counter() - t0
    out = {"dispatch_s": round(t_dispatch, 6),
           "block_until_ready_s": round(t_blocked, 6),
           "readback_after_s": round(t_readback, 6),
           "blocks": bool(t_blocked > 10 * t_dispatch
                          and t_readback - t_blocked < 0.1 * t_blocked)}
    print(f"chip_smoke: block_until_ready: {json.dumps(out)}", flush=True)
    return out


def compile_totals(report: dict) -> dict:
    t = report["compile"]["totals"]
    return {"compile_s": round(t["trace_s"] + t["lower_s"] + t["compile_s"], 3),
            "backend_compile_s": t["compile_s"],
            "compiles": t["compiles"],
            "persistent_cache_hits": t["persistent_cache_hits"],
            "persistent_cache_misses": t["persistent_cache_misses"],
            "cache_requests": t["cache_requests"]}


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def memory_per_device(devices) -> dict:
    return {str(d.id): {key: int(d.memory_stats()[key])
                        for key in ("bytes_in_use", "peak_bytes_in_use")}
            for d in devices}


def run_medium(baseline: dict) -> dict:
    """The medium graph through `kaminpar_tpu.cli.main` in-process (the
    cold run), then through the facade (the warm run)."""
    import jax
    import numpy as np

    from kaminpar_tpu import KaMinPar, cli, telemetry
    from kaminpar_tpu.graphs.csr import device_graph_from_host
    from kaminpar_tpu.graphs.factories import generate
    from kaminpar_tpu.io.partition import read_partition
    from kaminpar_tpu.telemetry.report import build_run_report

    graph = generate(MEDIUM)
    dgraph = device_graph_from_host(graph)
    require_on_tpu([dgraph.row_ptr, dgraph.src, dgraph.dst, dgraph.edge_w,
                    dgraph.node_w], "the uploaded DeviceGraph")
    del dgraph

    report_path = os.path.join(OUT_DIR, "medium.report.json")
    part_path = os.path.join(OUT_DIR, "medium.partition")
    t0 = time.perf_counter()
    rc = cli.main([MEDIUM, "-k", str(K), "-e", str(EPS), "-s", str(SEED),
                   "--report-json", report_path, "-o", part_path])
    wall_cold = time.perf_counter() - t0
    require(rc == 0, f"cli.main returned {rc} on {MEDIUM}")
    with open(report_path) as f:
        report = json.load(f)
    check_report(report)
    require(report["environment"]["platform"] == "tpu",
            f"the report's platform is {report['environment']['platform']}")
    cold = read_partition(part_path)
    ref_cut = baseline["medium_edge_cut"]
    checked = check_partition(graph, cold, K, EPS, ref_cut,
                              report["result"]["cut"])

    # the CLI left telemetry on (--report-json); it stays on, so the
    # warm run reuses the cold run's executables
    require(telemetry.enabled(), "the CLI run left telemetry off")
    t0 = time.perf_counter()
    warm = KaMinPar("default").set_graph(graph).compute_partition(
        k=K, epsilon=EPS, seed=SEED)
    wall_warm = time.perf_counter() - t0
    warm_report = build_run_report()
    with open(os.path.join(OUT_DIR, "medium.warm.report.json"), "w") as f:
        json.dump(warm_report, f, indent=1)
    check_report(warm_report)
    check_partition(graph, warm, K, EPS, ref_cut,
                    warm_report["result"]["cut"])
    require(np.array_equal(cold, warm),
            "the cold (CLI) and warm (facade) partitions differ in "
            f"{int((np.asarray(cold) != np.asarray(warm)).sum())} labels")
    return {"entry": "kaminpar_tpu.cli.main, then "
                     "KaMinPar('default').compute_partition",
            "graph": MEDIUM, "n": int(graph.n), "m": int(graph.m), "k": K,
            **checked, "reference_cut": ref_cut,
            "wall_cold_s": round(wall_cold, 3),
            "wall_warm_s": round(wall_warm, 3),
            "wall_note": "cold = CLI, XLA compilation included; warm = "
                         "facade, same process; telemetry on in both; "
                         "partitions bitwise equal",
            "cold": compile_totals(report),
            "warm": compile_totals(warm_report),
            "phases_warm_s": {
                name: node["elapsed_s"] for name, node in
                warm_report["scope_tree"]["partitioning"]["children"].items()},
            "peak_bytes_in_use": peak_bytes(jax.devices()[0])}


def run_dist(baseline: dict) -> dict:
    """The medium graph through dKaMinPar on a four-device mesh."""
    from kaminpar_tpu import telemetry
    from kaminpar_tpu.graphs.factories import generate
    from kaminpar_tpu.parallel import dKaMinPar
    from kaminpar_tpu.telemetry.report import build_run_report

    graph = generate(MEDIUM)
    telemetry.enable()
    solver = dKaMinPar("default", n_devices=DIST_DEVICES)
    ids = sorted(int(d.id) for d in solver.mesh.devices.flat)
    require(len(set(ids)) == DIST_DEVICES,
            f"the mesh holds device ids {ids}, not {DIST_DEVICES} distinct")
    t0 = time.perf_counter()
    part = solver.set_graph(graph).compute_partition(k=K, epsilon=EPS,
                                                     seed=SEED)
    wall = time.perf_counter() - t0
    report = build_run_report()
    with open(os.path.join(OUT_DIR, "dist-medium.report.json"), "w") as f:
        json.dump(report, f, indent=1)
    check_report(report)
    # no reference bound here: the dist `default` preset's cut on this
    # graph (482,559 on the CPU, PR 21) is 1.63x the reference binary's,
    # a quality gap and not a broken run; the ratio is reported instead
    checked = check_partition(graph, part, K, EPS, None,
                              report["result"]["cut"])
    ref_cut = baseline["medium_edge_cut"]
    per_device = memory_per_device(solver.mesh.devices.flat)
    idle = [i for i, st in per_device.items() if st["peak_bytes_in_use"] <= 0]
    require(not idle, f"devices {idle} never held a byte: {per_device}")
    return {"entry": f"dKaMinPar('default', n_devices={DIST_DEVICES})"
                     ".compute_partition",
            "graph": MEDIUM, "n": int(graph.n), "m": int(graph.m), "k": K,
            **checked, "reference_cut": ref_cut,
            "cut_vs_reference": round(checked["cut"] / ref_cut, 3),
            "device_ids": ids,
            "wall_cold_s": round(wall, 3), "wall_warm_s": "not measured",
            "cold": compile_totals(report), "memory_per_device": per_device}


def verdict_line(device: dict) -> str:
    """The last line of standard output: the driver takes a JSON object
    with exactly the keys `ok` and `device` (`platform`, `kind`, `count`);
    one key more and it refuses the run."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main() -> int:
    if len(sys.argv) > 1:
        sys.exit("chip_smoke: takes no arguments")
    t_start = time.perf_counter()
    device = require_tpu()

    from importlib.metadata import version

    import jax
    import jaxlib

    from kaminpar_tpu import native  # fails here in a bare directory
    from kaminpar_tpu.utils.platform import configure_compile_cache

    cache_dir = configure_compile_cache()
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(ROOT, "BASELINE_CPU.json")) as f:
        baseline = json.load(f)

    lib = native.get_lib()
    require(lib is not None,
            "the native library did not build or load (g++); initial "
            "partitioning and FM would run their numpy twins")

    result = {
        "device": device,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": version("libtpu"),
        "compile_cache_dir": cache_dir,
        "compile_cache_placed_by_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "native_library": lib._name,
        "block_until_ready": block_until_ready_blocks(),
        "size_note": SIZE_NOTE,
        "runs": [],
    }
    result["runs"].append(run_medium(baseline))
    print(f"chip_smoke: medium ok: {json.dumps(result['runs'][-1])}",
          flush=True)
    if device["count"] >= DIST_DEVICES:
        result["dist"] = run_dist(baseline)
    else:
        result["dist"] = f"skipped: {device['count']} device"
    result["cache_files"] = (
        len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0)
    result["total_wall_s"] = round(time.perf_counter() - t_start, 3)
    with open(os.path.join(OUT_DIR, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(f"chip_smoke: result: {json.dumps(result)}", flush=True)
    print(verdict_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
