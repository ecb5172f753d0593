#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, JAX once, no children.  In order: refuse anything but the
chips the cell asks for (before any graph is built; there is no CPU
mode); generate the cell's graph on the host from ``--seed`` with the
benchmark's own generator; one warm-up partition, which loads or
compiles every executable the request uses (set-up ends here); the
measured window, in which the same request is replayed back to back
(``harness/window.py``); the checks that decide ``correct``
(``harness/validate.py``: each partition on its own, and the replays
together as the configuration's ``guarantees.replay`` states); the result.

Program telemetry stays off in both kinds of run, so ``--trace 1`` loads
the very executables ``--trace 0`` compiled.  The traced run wraps the
window's first partition in ``jax.profiler.trace`` and reduces the trace
with ``harness/trace_reduce.py``; the per-layer metrics are read by the
files under ``layer_metrics/``, one each.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and last ``compared``: each number ``correct`` compared beside
its limit (also the last lines of standard error).  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics.  Any error before the window is a non-zero exit and no result
line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python can take it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from statistics import median  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".perfbench_out")  # .gitignore


def say(text: str) -> None:
    print(f"perfbench: {text}", flush=True)


def parse_args(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return parser.parse_args(argv)


def end_to_end(samples: list, setup_s: float) -> dict:
    from perfbench.harness.validate import median_cut

    walls = [s["wall_s"] for s in samples if not s.get("traced")]
    return {"partition_s": median(walls) if walls else None,
            "cut": median_cut(samples), "setup_s": setup_s}


def replay_of(registry, config: dict) -> dict:
    """The configuration's replay guarantee, or a non-zero exit where
    its file states one the benchmark does not know."""
    from perfbench.harness.validate import replay_guarantee

    cut_bound = next((m["bound"] for m in registry.manifest["end_to_end"]
                      if m["name"] == "cut"), None)
    try:
        return replay_guarantee(config.get("guarantees"), cut_bound)
    except ValueError as exc:
        sys.exit(f"perfbench: FAIL: configuration {config.get('name')!r}: "
                 f"{exc}")


def traced_report(registry, workload: str, chips: int, run: dict,
                  result: dict) -> None:
    """Reduce the traced partition's trace, let every per-layer metric of
    the cell read the run, and put what they give into ``result``."""
    from perfbench.harness import trace_reduce

    samples = run["samples"]
    traced = next((s for s in samples if s.get("traced")), None)
    reduced = None
    if traced is not None and traced["xplane"]:
        t0 = time.perf_counter()
        reduced = trace_reduce.reduce_file(traced["xplane"], chips=chips)
        say(f"trace {traced['xplane']} "
            f"({os.path.getsize(traced['xplane']) / 1e6:.1f} MB; written in "
            f"{traced['stop_s']:.2f} s, reduced in "
            f"{time.perf_counter() - t0:.2f} s)")
    run["trace"] = reduced
    run["traced_wall_s"] = traced["wall_s"] if traced else None
    for metric in registry.metrics_of("per_layer", workload):
        value = registry.layer_reader(metric["name"]).read(run)
        if value is not None:
            result["metrics"][metric["name"]] = {"value": value,
                                                 "unit": metric["unit"]}
    if traced is not None and run["trees"]:
        base = median(s["wall_s"] for s in samples if not s.get("traced"))
        say(f"traced partition {traced['wall_s']:.4f} s against the untraced "
            f"median {base:.4f} s: tracing overhead "
            f"{100 * (traced['wall_s'] / base - 1):+.1f} %")
    if reduced is None:
        say("INCORRECT: the trace holds no device operation")
        result["correct"] = False
        return
    say(f"device: {reduced['launches']:g} launches, busy "
        f"{reduced['device_busy_s']:.4f} s of {traced['wall_s']:.4f} s; own "
        "seconds by class: " + json.dumps(reduced["class_s"]))
    result["device"]["busy_s"] = reduced["device_busy_s"]
    result["device"]["window_s"] = traced["wall_s"]
    result["breakdown"] = {"device_ops": reduced["device_ops"],
                           "idle_gaps": reduced["idle_gaps"]}


def main(argv=None) -> int:
    args = parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "kaminpar_tpu")):
        sys.exit(f"perfbench: FAIL: no kaminpar_tpu package in {ROOT}: the "
                 "benchmark measures the program of its checkout")

    from perfbench.harness import timer_tree, validate
    from perfbench.harness.device import memory_peak_bytes, require_device
    from perfbench.harness.listeners import CompileListener
    from perfbench.harness.registry import Registry
    from perfbench.harness.window import Request, run_window

    registry = Registry(ROOT, BENCH)
    cell = registry.workload(args.workload)
    config = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    guarantee = replay_of(registry, config)
    chips = int(cell["chips"])
    device = require_device(chips)
    t_device = time.perf_counter()
    listener = CompileListener().install()

    # --- set-up -----------------------------------------------------------
    from kaminpar_tpu import native, telemetry

    if telemetry.enabled():
        sys.exit("perfbench: FAIL: program telemetry is on (KAMINPAR_TPU_* in "
                 "the environment?); it changes the executables")
    if native.get_lib() is None:
        sys.exit("perfbench: FAIL: the native library did not build or load "
                 "(g++); initial partitioning would run its numpy twin")
    graph_seed = int(config["graph_seed_base"]) + args.seed
    t0 = time.perf_counter()
    csr = registry.generator(config["generator"]).generate(
        config["params"], graph_seed)
    say(f"{args.workload}: {config['generator']} {config['params']} graph "
        f"seed {graph_seed}: n={len(csr['xadj']) - 1} "
        f"slots={len(csr['adjncy'])} in {time.perf_counter() - t0:.2f} s; "
        f"preset {config['preset']} k={traffic['k']} "
        f"epsilon={traffic['epsilon']} seed={args.seed}")
    t_graph = time.perf_counter()
    request = Request(csr, config["preset"], traffic["k"], traffic["epsilon"],
                      args.seed)
    warm = request.serve()
    if warm["errors"]:
        sys.exit(f"perfbench: FAIL: the warm-up partition: {warm['errors']}")
    setup = listener.phase_counts("setup")
    say(f"set-up: import and device {t_device - T_START:.2f} s, native "
        f"library and graph {t_graph - t_device:.2f} s, warm-up partition "
        f"{warm['wall_s']:.3f} s (cut {warm['cut']}), which asked for "
        f"{setup['executables']} executables in {setup['seconds']:.1f} s: "
        f"{setup['backend_compiles']} compiled, {setup['cache_loads']} "
        "loaded from the cache")

    # --- the window -------------------------------------------------------
    trace_dir = (os.path.join(OUT, "trace", args.workload)
                 if args.trace else None)
    listener.phase = "window"
    setup_s = time.perf_counter() - T_START
    window = run_window(request, args.seconds, trace_dir)
    listener.phase = "after"
    samples = window["samples"]
    peak_bytes = memory_peak_bytes(chips)

    walls = [s["wall_s"] for s in samples]
    say(f"window {window['elapsed_s']:.2f} s of {args.seconds:g}: "
        f"{len(samples)} partitions, wall min {min(walls, default=0):.4f} "
        f"median {median(walls) if walls else 0:.4f} "
        f"max {max(walls, default=0):.4f} s")
    window_compile = listener.phase_counts("window")
    served = [warm] + samples
    failed, reasons = validate.verdict(served, window["raised"],
                                       window_compile, guarantee)
    say(f"replay {guarantee['replay']}: "
        f"{validate.distinct_partitions(served)} distinct partitions among "
        f"the warm-up's and the window's {len(samples)}")

    attempted = len(samples) + (window["raised"] is not None)
    for reason in reasons:
        say(f"INCORRECT: {reason}")
    e2e = end_to_end(samples, setup_s)
    say("end to end: " + json.dumps(e2e)
        + f"; peak device memory {peak_bytes / 1e6:.3f} MB")
    untraced = [s for s in samples if not s.get("traced")]
    if untraced:
        say("timer tree of the last untraced partition:\n"
            + timer_tree.render(untraced[-1]["tree"]))

    result = {"correct": not reasons, "attempted": attempted,
              "failed": failed, "metrics": {},
              "device": dict(device, memory_peak_bytes=peak_bytes)}
    if not args.trace:
        units = {m["name"]: m["unit"]
                 for m in registry.metrics_of("end_to_end", args.workload)}
        result["metrics"] = {name: {"value": e2e[name], "unit": unit}
                             for name, unit in units.items()
                             if e2e.get(name) is not None}
    else:
        run = {"trees": [s["tree"] for s in untraced], "samples": samples,
               "compile": {"setup": setup, "window": window_compile},
               "memory_peak_bytes": peak_bytes}
        traced_report(registry, args.workload, chips, run, result)
    # last in the line and last on standard error: what was compared
    result["compared"] = validate.compared(served, failed, window_compile,
                                           guarantee)
    print(json.dumps(result), flush=True)
    for name, (number, limit) in result["compared"].items():
        print(f"perfbench: compared: {name} {number} limit {limit}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
