#!/usr/bin/env python
"""Standalone Jet-iteration cost at the 10M-graph fine shape (warm)."""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
from kaminpar_tpu.utils.platform import configure_compile_cache
configure_compile_cache()
import jax.numpy as jnp
import numpy as np
from kaminpar_tpu.graphs.csr import device_graph_from_host
from kaminpar_tpu.graphs.factories import make_rmat
from kaminpar_tpu.context import JetRefinementContext
from kaminpar_tpu.ops.jet import jet_refine

host = make_rmat(1 << 20, 10_000_000, seed=7)
g = device_graph_from_host(host)
int(jnp.sum(g.src[:1]))
k = 16
rng = np.random.default_rng(1)
p0 = np.zeros(g.n_pad, np.int32)
p0[: host.n] = rng.integers(0, k, host.n)
p0 = jnp.asarray(p0)
nw = host.node_weight_array()
cap = jnp.full(k, int(1.03 * np.ceil(nw.sum() / k)), dtype=jnp.int32)
ctx = JetRefinementContext(num_iterations=8, num_fruitless_iterations=0)
for rep in range(3):
    t0 = time.perf_counter()
    out = jet_refine(g, p0, k, cap, jnp.int32(3), ctx, level=0)
    int(jnp.sum(out[:1]))
    dt = time.perf_counter() - t0
    print(f"rep{rep}: 8 iters = {dt:.2f}s  ({dt/8*1000:.0f} ms/iter)", flush=True)
