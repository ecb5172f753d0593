#!/usr/bin/env python
"""Replay the host k-way FM of `strong` (native/fm.cpp) without the
pipeline: a Delaunay mesh of --n points (the benchmark's generator), cut
into --k blocks by recursive coordinate bisection of the points (the
tests' plain reference, tests/mesh_reference.py), then --calls
`native.fm_refine` calls on one thread, each on the labels the last one
left, with the preset's `FMRefinementContext` and the request's cap
`int((1 + 0.03) * ceil(W / k))`.  Prints a line a call (seconds, returned
gain, cut after it) and the sha1 of the final int32 labels: an engine
change that is bit for bit the old one prints the same digest.  No
device: it sizes an FM change on any host (n = 131072, k = 16 is level 0
of `delaunay-n17-strong.k16`, n = 26901 its level 1).

Usage: python scripts/microbench_fm.py [--n 131072] [--k 16] [--seed 1] [--calls 2]
"""

from __future__ import annotations

import argparse
import hashlib
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

import numpy as np

from mesh_reference import delaunay_mesh, recursive_coordinate_bisection

from kaminpar_tpu import native
from kaminpar_tpu.graphs.host import host_partition_metrics
from kaminpar_tpu.presets import create_strong_context

EPSILON = 0.03


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--n", type=int, default=1 << 17)
    parser.add_argument("--k", type=int, default=16)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--calls", type=int, default=2)
    args = parser.parse_args(argv)
    if not native.available():
        print("microbench_fm: the native library is unavailable")
        return 1
    points, graph = delaunay_mesh(args.n, args.seed)
    part = recursive_coordinate_bisection(points, args.k)
    cap = np.full(
        args.k,
        int((1.0 + EPSILON) * math.ceil(graph.total_node_weight / args.k)),
        dtype=np.int64,
    )
    fm_ctx = create_strong_context().refinement.fm
    cut = host_partition_metrics(graph, part, args.k)["cut"]
    print(f"microbench_fm: n {graph.n} slots {graph.m} k {args.k} seed "
          f"{args.seed}: start cut {cut}")
    total = 0.0
    for call in range(args.calls):
        t0 = time.perf_counter()
        gain = native.fm_refine(graph, part, args.k, cap, fm_ctx, args.seed)
        seconds = time.perf_counter() - t0
        total += seconds
        cut = host_partition_metrics(graph, part, args.k)["cut"]
        print(f"microbench_fm: call {call}: {seconds:.3f} s gain {gain} "
              f"cut {cut}")
    print(f"microbench_fm: {args.calls} calls {total:.3f} s cut {cut} "
          f"sha1 {hashlib.sha1(part.tobytes()).hexdigest()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
