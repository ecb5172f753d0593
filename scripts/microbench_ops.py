#!/usr/bin/env python
"""Microbenchmark the TPU primitive ops the kernels are built from.

Timing: every measurement ends in a scalar readback (which waits for
the device), takes the MINIMUM of `reps` runs (steady state), and
subtracts nothing — the dispatch floor is part of what a kernel pays.

Usage: python scripts/microbench_ops.py [log2_m] [log2_n]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from kaminpar_tpu.utils.platform import configure_compile_cache

configure_compile_cache()

import jax.numpy as jnp
import numpy as np
from jax import lax

LOG_M = int(sys.argv[1]) if len(sys.argv) > 1 else 24
LOG_N = int(sys.argv[2]) if len(sys.argv) > 2 else 20
M = 1 << LOG_M
N = 1 << LOG_N
REPS = 4


def timeit(name, fn, *args):
    fn_j = jax.jit(fn)
    out = fn_j(*args)  # compile
    int(jnp.sum(jax.tree_util.tree_leaves(out)[0].reshape(-1)[:1]))
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = fn_j(*args)
        int(jnp.sum(jax.tree_util.tree_leaves(out)[0].reshape(-1)[:1]))
        best = min(best, time.perf_counter() - t0)
    print(json.dumps({"op": name, "ms": round(best * 1e3, 1),
                      "ns_per_elem": round(best * 1e9 / M, 2)}), flush=True)
    return best


def main():
    rng = np.random.RandomState(0)
    src = jnp.asarray(np.sort(rng.randint(0, N, M)).astype(np.int32))
    dst = jnp.asarray(rng.randint(0, N, M).astype(np.int32))
    w = jnp.asarray(rng.randint(1, 100, M).astype(np.int32))
    labels = jnp.asarray(rng.randint(0, N, N).astype(np.int32))
    ew = jnp.asarray(rng.randint(1, 100, M).astype(np.int32))
    print(f"== M=2^{LOG_M} ({M}), N=2^{LOG_N} ({N}) ==", flush=True)

    timeit("noop_scalar", lambda x: jnp.sum(x[:8]), w)
    timeit("elementwise_add", lambda a, b: a + b, w, ew)
    timeit("cumsum", jnp.cumsum, w)
    timeit("gather_m_from_n", lambda l, d: l[d], labels, dst)
    timeit("gather_m_from_n_sorted_idx", lambda l, s: l[s], labels, src)
    timeit(
        "segment_sum_to_n",
        lambda v, s: jax.ops.segment_sum(v, s, num_segments=N), w, src,
    )
    timeit(
        "segment_sum_to_n_unsorted",
        lambda v, d: jax.ops.segment_sum(v, d, num_segments=N), w, dst,
    )
    k = 16
    flat16 = (src * k + (dst % k)).astype(jnp.int32)
    timeit(
        "segment_sum_flat_nk16",
        lambda v, f: jax.ops.segment_sum(v, f, num_segments=N * k), w, flat16,
    )
    timeit("sort_1key", lambda a: lax.sort((a,), num_keys=1), dst)
    timeit(
        "sort_2key_1val",
        lambda a, b, c: lax.sort((a, b, c), num_keys=2), src, dst, w,
    )
    timeit(
        "sort_3key_1val",
        lambda a, b, c, d: lax.sort((a, b, c, d), num_keys=3),
        src, dst, w, ew,
    )
    timeit(
        "scatter_set_m_to_m",
        lambda v, i: jnp.zeros(M, jnp.int32).at[i].set(v),
        w, jnp.asarray(rng.permutation(M).astype(np.int32)),
    )


if __name__ == "__main__":
    main()
