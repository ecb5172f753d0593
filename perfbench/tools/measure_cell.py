#!/usr/bin/env python3
"""Measure one cell as the driver does, in one call on the chip: the
first, compiling run; a traced run; then sets of untraced runs, each run
with another ``--seed``; and the spread of every end-to-end metric (the
distance between the quartiles over the median, per set).

    python3 perfbench/tools/measure_cell.py --workload <cell> \
        [--sets 2] [--runs 6] [--deadline-s 3000] [--out chiprun_out/perfbench]

This process never touches JAX (a parent that has would hold the chip):
it starts ``BENCHMARK.json``'s command once per run, one run at a time,
and waits for it.  Before each run it checks ``--deadline-s`` against the
longest run so far and stops by itself rather than run into a call's
time limit.  Every result line goes to ``<out>/<cell>.jsonl``, every
run's whole output to ``<out>/<cell>.<label>.log``."""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import subprocess
import sys
import time
from statistics import median, quantiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values: list) -> float:
    """Distance between the quartiles over the median."""
    if len(values) < 2 or not median(values):
        return 0.0
    q1, _, q3 = quantiles(values, n=4, method="inclusive")
    return (q3 - q1) / abs(median(values))


def one_run(command: list, cell: str, seed: int, seconds: int, trace: int,
            log_path: str) -> dict:
    argv = command + ["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    with open(log_path, "w") as f:
        f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr)
    record = {"seed": seed, "trace": trace, "rc": proc.returncode,
              "wall_s": wall, "result": None}
    lines = proc.stdout.strip().splitlines()
    if proc.returncode == 0 and lines and lines[-1].startswith("{"):
        record["result"] = json.loads(lines[-1])
    return record


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--deadline-s", type=float, default=3000.0)
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "perfbench"))
    args = parser.parse_args()
    t_start = time.perf_counter()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    command, seconds = manifest["command"], manifest["run_seconds"]
    cell = args.workload
    os.makedirs(args.out, exist_ok=True)
    records_path = os.path.join(args.out, f"{cell}.jsonl")
    longest_warm = 0.0

    def run(label: str, seed: int, trace: int):
        nonlocal longest_warm
        left = args.deadline_s - (time.perf_counter() - t_start)
        if longest_warm and left < 1.5 * longest_warm:
            print(f"measure_cell: {left:.0f} s left, the longest run took "
                  f"{longest_warm:.0f} s: stopping before {label}", flush=True)
            return None
        record = one_run(command, cell, seed, seconds, trace,
                         os.path.join(args.out, f"{cell}.{label}.log"))
        record["label"] = label
        if label != "first":
            longest_warm = max(longest_warm, record["wall_s"])
        with open(records_path, "a") as f:
            f.write(json.dumps(record) + "\n")
        result = record["result"]
        print(f"measure_cell: {cell} {label} seed {seed} trace {trace}: rc "
              f"{record['rc']} in {record['wall_s']:.1f} s: "
              + (json.dumps(result) if result else "NO RESULT LINE"),
              flush=True)
        return record

    first = run("first", 1, 0)
    if first["result"] is None or not first["result"]["correct"]:
        print("measure_cell: the first run failed; see its log", flush=True)
        return 1
    traced = run("traced", 1, 1)
    if traced is not None and traced["result"] is not None:
        keep_trace(cell, args.out)
    sets = []
    for s in range(args.sets):
        got = []
        for r in range(args.runs):
            record = run(f"set{s}.run{r}", 1 + s * args.runs + r, 0)
            if record is None:
                break
            if record["result"] is not None:
                got.append(record)
        sets.append(got)

    names = [m["name"] for m in manifest["end_to_end"]]
    summary = {"cell": cell, "seconds": seconds, "metrics": {}}
    for name in names:
        per_set = []
        for got in sets:
            values = [g["result"]["metrics"][name]["value"] for g in got
                      if name in g["result"]["metrics"]]
            if values:
                per_set.append({"n": len(values), "median": median(values),
                                "min": min(values), "max": max(values),
                                "spread": spread(values)})
        summary["metrics"][name] = per_set
    summary["all_correct"] = all(
        g["result"]["correct"] for got in sets for g in got)
    summary["first_run"] = first["result"]["metrics"]
    summary["wall_s"] = time.perf_counter() - t_start
    with open(os.path.join(args.out, f"{cell}.summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print("measure_cell: summary: " + json.dumps(summary), flush=True)
    return 0


def keep_trace(cell: str, out: str, limit_bytes: int = 30_000_000) -> None:
    """Bring the traced run's ``.xplane.pb`` back, gzipped, if small."""
    sys.path.insert(0, ROOT)
    from perfbench.harness.trace_reduce import newest_xplane  # no jax in it

    newest = newest_xplane(os.path.join(ROOT, ".perfbench_out", "trace", cell))
    if newest is None:
        return
    target = os.path.join(out, f"{cell}.xplane.pb.gz")
    with open(newest, "rb") as src, gzip.open(target, "wb") as dst:
        shutil.copyfileobj(src, dst)
    if os.path.getsize(target) > limit_bytes:
        os.remove(target)


if __name__ == "__main__":
    sys.exit(main())
