#!/usr/bin/env python3
"""Where a cell's set-up goes, from the program's own account.

    python3 perfbench/tools/setup_table.py --workload <cell> --seed <n> \
        [--replays 0] [--out chiprun_out/setup_table]

The set-up of ``run.py``: the same refusal of anything but the cell's
chips, the same generator and graph seed, the same request served once
(the process's request 1); no window.  Then the program's
``compile_account.render()``: seconds by scope and by layer, records per
request ordinal, the ten costliest executables by compile-or-load
seconds and by trace-and-lower seconds.  ``--replays n`` serves the
request n more times, which gives ``setup_unattributed_s`` its later
requests.  The last line of standard output is one JSON object with the
numbers (the four set-up metrics as their readers compute them, the
benchmark's own listener counts beside the account's, the clock's
stations); ``<out>/<cell>.seed<n>.json`` holds the whole summary and
every record.  Run it on an empty and on a filled compile cache: what
differs is compilation."""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def say(text: str) -> None:
    print(f"setup_table: {text}", flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--replays", type=int, default=0)
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "setup_table"))
    args = parser.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    from perfbench.harness.device import require_device
    from perfbench.harness.listeners import CompileListener
    from perfbench.harness.registry import Registry
    from perfbench.harness.window import Request
    from perfbench.layer_metrics import _setup_account

    registry = Registry(ROOT, BENCH)
    cell = registry.workload(args.workload)
    config = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    device = require_device(int(cell["chips"]))
    t_device = time.perf_counter()
    listener = CompileListener().install()

    from kaminpar_tpu import native
    from kaminpar_tpu.telemetry import compile_account

    if not hasattr(compile_account, "summary"):
        sys.exit("setup_table: FAIL: this program keeps no account of its "
                 "set-up (compile_account.summary, PR 34)")
    if native.get_lib() is None:
        sys.exit("setup_table: FAIL: the native library did not build or "
                 "load (g++)")
    csr = registry.generator(config["generator"]).generate(
        config["params"], int(config["graph_seed_base"]) + args.seed)
    t_graph = time.perf_counter()
    request = Request(csr, config["preset"], traffic["k"], traffic["epsilon"],
                      args.seed)
    warm = request.serve()
    if warm["errors"]:
        sys.exit(f"setup_table: FAIL: the first partition: {warm['errors']}")
    t_served = time.perf_counter()
    counts = listener.phase_counts("setup")
    listener.phase = "replays"
    replays = [request.serve()["wall_s"] for _ in range(args.replays)]

    account = compile_account.summary()
    numbers = {
        "workload": args.workload, "seed": args.seed, "device": device,
        "import_and_device_s": t_device - T_START,
        "native_and_graph_s": t_graph - t_device,
        "first_serve_s": warm["wall_s"], "cut": warm["cut"],
        "setup_s": t_served - T_START, "replay_s": replays,
        "listener": counts,
        "listener_replays": listener.phase_counts("replays"),
        "account": account["totals"],
        "layers": account["layers"], "by_request": account["by_request"],
        "metrics": {name: getattr(_setup_account, name)(account) for name in (
            "package_import_s", "first_request_s", "trace_lower_s",
            "setup_unattributed_s")},
    }
    say(f"{args.workload} seed {args.seed}: import and device "
        f"{numbers['import_and_device_s']:.2f} s, native library and graph "
        f"{numbers['native_and_graph_s']:.2f} s, first partition "
        f"{warm['wall_s']:.3f} s (cut {warm['cut']}); the benchmark's "
        f"listener: {counts['executables']} executables in "
        f"{counts['seconds']:.3f} s, {counts['backend_compiles']} compiled, "
        f"{counts['cache_loads']} loaded")
    print(compile_account.render(), flush=True)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.workload}.seed{args.seed}.json")
    with open(path, "w") as f:
        json.dump({"numbers": numbers, "summary": account,
                   "records": compile_account.records()}, f, indent=1)
    say(f"summary and records in {path}")
    print(json.dumps(numbers), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
