#!/usr/bin/env python
"""Utilization probe for the irregular ops that dominate the pipeline.

Round-3's verdict: the "structural per-slot floor" argument was asserted
from one number (9.6 ns/slot gather).  This script measures what fraction
of HBM peak each primitive actually achieves and probes the design space
around the floor:

  * scalar gather m-from-n          (the LP/Jet hot op: labels[dst])
  * row gathers (n, r) tables, r in {2, 4, 8, 16, 128}
    -> if cost is per-INDEX, packing more payload per index is free and
       kernels should gather wider rows instead of more arrays
  * scatter-add, scalar vs wide rows (the conn-table delta op)
  * one-hot matmul rating vs segment_sum (MXU vs scatter for (n, k))
  * dtype sensitivity (int8/int16/int32 gathers)
  * table-size sensitivity (VMEM-resident vs HBM tables)

Achieved bandwidth counts useful bytes only: payload read + payload
written + 4B per index read.  HBM peak for v5e-1 is ~819 GB/s.

Usage: python scripts/microbench_gather.py [log2_m] [log2_n]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from kaminpar_tpu.telemetry.perf import device_peaks
from kaminpar_tpu.utils.platform import configure_compile_cache

configure_compile_cache()

import jax.numpy as jnp
import numpy as np

LOG_M = int(sys.argv[1]) if len(sys.argv) > 1 else 24
LOG_N = int(sys.argv[2]) if len(sys.argv) > 2 else 20
M = 1 << LOG_M
N = 1 << LOG_N
REPS = 4
HBM_PEAK_GBS = device_peaks(jax.devices()[0].device_kind)[0]


def timeit(name, fn, useful_bytes, *args):
    fn_j = jax.jit(fn)
    out = fn_j(*args)  # compile
    int(jnp.sum(jax.tree_util.tree_leaves(out)[0].reshape(-1)[:1]))
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = fn_j(*args)
        int(jnp.sum(jax.tree_util.tree_leaves(out)[0].reshape(-1)[:1]))
        best = min(best, time.perf_counter() - t0)
    gbs = useful_bytes / best / 1e9
    print(
        json.dumps(
            {
                "op": name,
                "ms": round(best * 1e3, 1),
                "ns_per_index": round(best * 1e9 / M, 2),
                "GB_s": round(gbs, 2),
                "pct_hbm_peak": round(100.0 * gbs / HBM_PEAK_GBS, 2),
            }
        ),
        flush=True,
    )
    return best


def main():
    rng = np.random.RandomState(0)
    dst = jnp.asarray(rng.randint(0, N, M).astype(np.int32))
    labels = jnp.asarray(rng.randint(0, N, N).astype(np.int32))
    print(f"== M=2^{LOG_M} ({M}), N=2^{LOG_N} ({N}) ==", flush=True)

    # --- scalar gather baseline -----------------------------------------
    timeit("gather_scalar_i32", lambda l, d: l[d], M * 12, labels, dst)

    # --- row gathers: same index count, wider payload -------------------
    for r in (2, 4, 8, 16, 32):
        tab = jnp.asarray(
            rng.randint(0, 100, (N, r)).astype(np.int32)
        )
        timeit(
            f"gather_rows_r{r}_i32",
            lambda t, d: t[d],
            M * (4 + 8 * r),
            tab,
            dst,
        )

    # --- dtype sensitivity ----------------------------------------------
    lab16 = labels.astype(jnp.int16)
    lab8 = labels.astype(jnp.int8)
    timeit("gather_scalar_i16", lambda l, d: l[d], M * 8, lab16, dst)
    timeit("gather_scalar_i8", lambda l, d: l[d], M * 6, lab8, dst)

    # --- small-table gather (table fits VMEM) ---------------------------
    for log_small in (10, 14):
        ns = 1 << log_small
        small = jnp.asarray(rng.randint(0, 100, ns).astype(np.int32))
        dsts = jnp.asarray(rng.randint(0, ns, M).astype(np.int32))
        timeit(
            f"gather_scalar_from_2^{log_small}",
            lambda l, d: l[d],
            M * 12,
            small,
            dsts,
        )

    # --- one-hot matmul instead of gather, small table ------------------
    # labels[dst] for a SMALL label table (n <= 2^14) as
    # one_hot(dst) @ labels — MXU does the "gather"
    ns = 1 << 12
    small = jnp.asarray(rng.randint(0, 100, ns).astype(np.int32))
    dsts = jnp.asarray(rng.randint(0, ns, M).astype(np.int32))

    def onehot_gather(l, d):
        oh = jax.nn.one_hot(d, ns, dtype=jnp.bfloat16)
        return (oh @ l.astype(jnp.bfloat16)).astype(jnp.int32)

    timeit("gather_onehot_mxu_2^12", onehot_gather, M * 12, small, dsts)

    # --- scatter-add: scalar vs wide rows -------------------------------
    vals = jnp.asarray(rng.randint(0, 100, M).astype(np.int32))
    timeit(
        "scatter_add_scalar",
        lambda v, d: jnp.zeros(N, jnp.int32).at[d].add(v),
        M * 12 + N * 8,
        vals,
        dst,
    )
    for r in (2, 8):
        valr = jnp.asarray(rng.randint(0, 100, (M, r)).astype(np.int32))
        timeit(
            f"scatter_add_rows_r{r}",
            lambda v, d: jnp.zeros((N, r), jnp.int32).at[d].add(v),
            M * (4 + 8 * r) + N * r * 8,
            valr,
            dst,
        )

    # --- (n, k) rating build: segment_sum vs one-hot matmul -------------
    k = 16
    src = jnp.asarray(np.sort(rng.randint(0, N, M)).astype(np.int32))
    part = jnp.asarray(rng.randint(0, k, N).astype(np.int32))
    w = jnp.asarray(rng.randint(1, 100, M).astype(np.int32))

    def conn_segsum(src, dst, w, part):
        flat = src * k + part[dst]
        return jax.ops.segment_sum(w, flat, num_segments=N * k)

    timeit("conn_nk16_segment_sum", conn_segsum, M * 24 + N * k * 4,
           src, dst, w, part)

    def conn_onehot(src, dst, w, part):
        # one-hot the k-axis only (k small); still needs the dst gather
        # and an m-to-n segment reduction per k column via segment_sum of
        # w * onehot — expressed as a single segment_sum of (m, k) rows
        oh = jax.nn.one_hot(part[dst], k, dtype=jnp.int32) * w[:, None]
        return jax.ops.segment_sum(oh, src, num_segments=N)

    timeit("conn_nk16_onehot_rows", conn_onehot, M * 24 + N * k * 4,
           src, dst, w, part)


if __name__ == "__main__":
    main()
