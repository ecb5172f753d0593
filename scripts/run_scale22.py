#!/usr/bin/env python
"""Scale-22 (RMAT n=2^22, ~38.7M undirected edges, k=64) end-to-end run.
Usage: python scripts/run_scale22.py [reps]"""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

reps = int(sys.argv[1]) if len(sys.argv) > 1 else 1

from kaminpar_tpu.graphs.factories import make_rmat
from kaminpar_tpu.graphs.host import host_partition_metrics
from kaminpar_tpu.kaminpar import KaMinPar
from kaminpar_tpu.utils.logger import OutputLevel

host = make_rmat(1 << 22, 40_000_000, seed=22)
print(f"graph: n={host.n} m={host.m}", flush=True)
for rep in range(reps):
    p = KaMinPar("default")
    p.set_output_level(OutputLevel.QUIET)
    t0 = time.perf_counter()
    part = p.set_graph(host).compute_partition(k=64, epsilon=0.03, seed=1)
    dt = time.perf_counter() - t0
    m = host_partition_metrics(host, part, 64)
    print(f"rep{rep}: {dt:.1f}s cut={m['cut']} imb={m['imbalance']:.4f}",
          flush=True)
