#!/usr/bin/env python
"""Plain (unprofiled) end-to-end 10M-edge runs — the honest wall-clock.
Usage: python scripts/run10m.py [reps] [preset] [fruitless_override]"""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

reps = int(sys.argv[1]) if len(sys.argv) > 1 else 2
preset = sys.argv[2] if len(sys.argv) > 2 else "default"
fruitless = int(sys.argv[3]) if len(sys.argv) > 3 else 0

from kaminpar_tpu.graphs.factories import make_rmat
from kaminpar_tpu.graphs.host import host_partition_metrics
from kaminpar_tpu.kaminpar import KaMinPar
from kaminpar_tpu.utils.logger import OutputLevel

host = make_rmat(1 << 20, 10_000_000, seed=7)
for rep in range(reps):
    p = KaMinPar(preset)
    if fruitless:
        p.ctx.refinement.jet.num_fruitless_iterations = fruitless
    p.set_output_level(OutputLevel.QUIET)
    t0 = time.perf_counter()
    part = p.set_graph(host).compute_partition(k=16, epsilon=0.03, seed=1)
    dt = time.perf_counter() - t0
    m = host_partition_metrics(host, part, 16)
    print(f"rep{rep}: {dt:.1f}s cut={m['cut']} imb={m['imbalance']:.4f}",
          flush=True)
