#!/usr/bin/env python
"""Probe: is Mosaic's tpu.dynamic_gather fast on tall tables?

JAX 0.9.0 lowers jnp.take_along_axis(x, idx, axis=0) inside Pallas TPU
kernels to tpu.dynamic_gather when x.shape == idx.shape (2D).  Semantics:
out[s, l] = x[idx[s, l], l] — a per-LANE gather across sublanes.

If this runs near streaming speed for tall x (S in the thousands), the
LP/Jet `labels[dst]` gather (12.5 ns/index via XLA, 0.09% of HBM peak)
can be rebuilt as:
  1. one-time (per graph level, indices are static): route each flat
     index f to lane f % 128, pad lanes to equal height;
  2. per round: k grid steps of table-shaped dynamic_gather from the
     VMEM-resident table;
  3. no un-permute — downstream rating engines are order-agnostic
     (segment_sum / sort by src), so src rides the same static routing.

Usage: python scripts/probe_dynamic_gather.py [cpu|tpu]
"""

from __future__ import annotations

import functools
import json
import sys
import time

import os

if len(sys.argv) > 1 and sys.argv[1] == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from kaminpar_tpu.utils.platform import configure_compile_cache

configure_compile_cache()

import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

L = 128


def _kernel_axis0(table_ref, idx_ref, out_ref):
    out_ref[...] = jnp.take_along_axis(table_ref[...], idx_ref[...], axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lane_gather(table, idx, interpret=False):
    """out[c, s, l] = table[idx[c, s, l], l] for each chunk c."""
    S = table.shape[0]
    C = idx.shape[0] // S
    idx2 = idx.reshape(C, S, L)
    return pl.pallas_call(
        _kernel_axis0,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((S, L), lambda c: (0, 0)),  # table resident
            pl.BlockSpec((None, S, L), lambda c: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, S, L), lambda c: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, S, L), table.dtype),
        interpret=interpret,
    )(table, idx2)


def check_correct(S, interpret):
    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.randint(0, 1 << 30, (S, L)).astype(np.int32))
    idx = jnp.asarray(rng.randint(0, S, (2 * S, L)).astype(np.int32))
    got = np.asarray(lane_gather(table, idx, interpret=interpret))
    want = np.take_along_axis(
        np.asarray(table), np.asarray(idx).reshape(2 * S, L), axis=0
    ).reshape(2, S, L)
    ok = np.array_equal(got, want)
    print(json.dumps({"probe": f"correct_S{S}", "ok": bool(ok)}), flush=True)
    return ok


def bench(S, log_m):
    M = 1 << log_m
    rng = np.random.RandomState(1)
    table = jnp.asarray(rng.randint(0, 1 << 30, (S, L)).astype(np.int32))
    idx = jnp.asarray(rng.randint(0, S, (M // L, L)).astype(np.int32))
    out = lane_gather(table, idx)
    int(jnp.sum(out.reshape(-1)[:1]))
    best = float("inf")
    for _ in range(4):
        t0 = time.perf_counter()
        out = lane_gather(table, idx)
        int(jnp.sum(out.reshape(-1)[:1]))
        best = min(best, time.perf_counter() - t0)
    print(
        json.dumps(
            {
                "probe": f"lane_gather_S{S}_M2^{log_m}",
                "ms": round(best * 1e3, 2),
                "ns_per_index": round(best * 1e9 / M, 3),
            }
        ),
        flush=True,
    )


def bench_xla_baseline(log_m, log_n):
    M, N = 1 << log_m, 1 << log_n
    rng = np.random.RandomState(2)
    labels = jnp.asarray(rng.randint(0, 1 << 30, N).astype(np.int32))
    dst = jnp.asarray(rng.randint(0, N, M).astype(np.int32))
    f = jax.jit(lambda l, d: l[d])
    out = f(labels, dst)
    int(jnp.sum(out[:1]))
    best = float("inf")
    for _ in range(4):
        t0 = time.perf_counter()
        out = f(labels, dst)
        int(jnp.sum(out[:1]))
        best = min(best, time.perf_counter() - t0)
    print(
        json.dumps(
            {
                "probe": f"xla_gather_M2^{log_m}_N2^{log_n}",
                "ms": round(best * 1e3, 2),
                "ns_per_index": round(best * 1e9 / M, 3),
            }
        ),
        flush=True,
    )


def bench_lane_gather(log_m, log_n):
    """The real module: plan build + routed gather at the hot-op shape."""
    sys.path.insert(0, "/root/repo")
    from kaminpar_tpu.ops.lane_gather import build_gather_plan, lane_gather

    M, N = 1 << log_m, 1 << log_n
    rng = np.random.RandomState(3)
    idx = jnp.asarray(rng.randint(0, N, M).astype(np.int32))
    table = jnp.asarray(rng.randint(0, 1 << 30, N).astype(np.int32))
    t0 = time.perf_counter()
    plan = build_gather_plan(idx, N)
    int(jnp.sum(plan.q.reshape(-1)[:1]))
    plan_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    plan = build_gather_plan(idx, N)
    int(jnp.sum(plan.q.reshape(-1)[:1]))
    plan_warm = time.perf_counter() - t0
    out = lane_gather(table, plan)
    got = np.asarray(out)
    inv = np.asarray(plan.inv)
    ok = inv >= 0
    correct = bool(
        np.array_equal(got[ok], np.asarray(table)[np.asarray(idx)[inv[ok]]])
    )
    best = float("inf")
    for _ in range(4):
        t0 = time.perf_counter()
        out = lane_gather(table, plan)
        int(jnp.sum(out[:1]))
        best = min(best, time.perf_counter() - t0)
    print(
        json.dumps(
            {
                "probe": f"lane_gather_module_M2^{log_m}_N2^{log_n}",
                "correct": correct,
                "ms": round(best * 1e3, 2),
                "ns_per_index": round(best * 1e9 / M, 3),
                "routed_slots": plan.num_slots,
                "pad_overhead": round(plan.num_slots / M - 1, 3),
                "plan_build_cold_s": round(plan_cold, 2),
                "plan_build_warm_s": round(plan_warm, 3),
            }
        ),
        flush=True,
    )


def main():
    on_cpu = jax.devices()[0].platform == "cpu"
    print(f"platform: {jax.devices()[0].platform}", flush=True)
    if on_cpu:
        for S in (8, 512):
            check_correct(S, interpret=True)
        return
    # device: correctness at three heights, then timing
    for S in (8, 512, 8192):
        if not check_correct(S, interpret=False):
            print("INCORRECT — abort timing", flush=True)
            return
    bench_xla_baseline(24, 20)
    for S in (512, 2048, 8192):
        bench(S, 24)
    bench_lane_gather(24, 20)
    bench_lane_gather(24, 22)


if __name__ == "__main__":
    main()
