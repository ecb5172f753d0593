#!/usr/bin/env python
"""Per-call phase profiler for the deep pipeline.

Wraps the hot entry points (lp_cluster, contract_clustering, jet_refine,
lp_refine, balancers, extend_partition, host IP) with readback-synced
wall-clock timing and shape logging, then runs a full partition.  Every
wrapper forces a scalar readback (which waits for the device) before
reading the clock.

Usage:
  python scripts/profile_pipeline.py [gen-spec] [k] [preset]
  (defaults: rmat;n=1048576;m=10000000;seed=7  16  default)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from kaminpar_tpu.utils.platform import configure_compile_cache

configure_compile_cache()

EVENTS = []


def _sync(x):
    try:
        if isinstance(x, tuple):
            x = x[0]
        if hasattr(x, "graph"):  # CoarseGraph
            int(jnp.sum(x.graph.src[:1]))
        elif isinstance(x, jax.Array):
            int(jnp.sum(x.reshape(-1)[:1]))
    except Exception:
        pass


def wrap(mod, name, tag, shape_of=None):
    fn = getattr(mod, name)

    def wrapper(*args, **kwargs):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        _sync(out)
        dt = time.perf_counter() - t0
        info = {"phase": tag, "dt": round(dt, 3)}
        if shape_of is not None:
            try:
                info.update(shape_of(*args, **kwargs))
            except Exception:
                pass
        EVENTS.append(info)
        print(json.dumps(info), flush=True)
        return out

    wrapper.__wrapped__ = fn
    setattr(mod, name, wrapper)
    return wrapper


def graph_shape(graph, *a, **k):
    return {"n_pad": int(graph.n_pad), "m_pad": int(graph.src.shape[0])}


def main():
    spec = sys.argv[1] if len(sys.argv) > 1 else "rmat;n=1048576;m=10000000;seed=7"
    k = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    preset = sys.argv[3] if len(sys.argv) > 3 else "default"

    from kaminpar_tpu.graphs.factories import generate
    from kaminpar_tpu.ops import contraction as contraction_mod
    from kaminpar_tpu.ops import jet as jet_mod
    from kaminpar_tpu.ops import lp as lp_mod
    from kaminpar_tpu.ops import balancer as bal_mod
    from kaminpar_tpu.partitioning import coarsener as coarsener_mod
    from kaminpar_tpu.partitioning import deep as deep_mod
    from kaminpar_tpu.partitioning import refiner as refiner_mod
    from kaminpar_tpu import initial as initial_mod

    # --- wrap ops, then rebind the names modules imported at top level ---
    wrap(lp_mod, "lp_cluster", "lp_cluster", graph_shape)
    wrap(lp_mod, "lp_refine", "lp_refine", graph_shape)
    wrap(contraction_mod, "contract_clustering", "contract", graph_shape)
    wrap(jet_mod, "jet_refine", "jet", graph_shape)
    wrap(
        jet_mod,
        "_jet_chunk",
        "jet_chunk",
        lambda graph, *a, **k: {
            "n_pad": int(graph.n_pad),
            "m_pad": int(graph.src.shape[0]),
        },
    )
    wrap(bal_mod, "overload_balance", "overload_bal", graph_shape)
    wrap(bal_mod, "underload_balance", "underload_bal", graph_shape)
    coarsener_mod.lp_cluster = lp_mod.lp_cluster
    coarsener_mod.contract_clustering = contraction_mod.contract_clustering
    refiner_mod.lp_refine = lp_mod.lp_refine
    refiner_mod.balancer_ops = bal_mod

    # host-side phases
    orig_extend = deep_mod.DeepMultilevelPartitioner._extend_partition

    def extend_wrapper(self, dgraph, partition, spans, next_k, rng):
        t0 = time.perf_counter()
        out = orig_extend(self, dgraph, partition, spans, next_k, rng)
        _sync(out[0])
        info = {
            "phase": "extend_partition",
            "dt": round(time.perf_counter() - t0, 3),
            "n_pad": int(dgraph.n_pad),
            "next_k": next_k,
        }
        EVENTS.append(info)
        print(json.dumps(info), flush=True)
        return out

    deep_mod.DeepMultilevelPartitioner._extend_partition = extend_wrapper

    orig_bip = initial_mod.InitialMultilevelBipartitioner.bipartition

    def bip_wrapper(self, graph, max_w, rng):
        t0 = time.perf_counter()
        out = orig_bip(self, graph, max_w, rng)
        info = {
            "phase": "host_ip",
            "dt": round(time.perf_counter() - t0, 3),
            "n": int(graph.n),
        }
        EVENTS.append(info)
        print(json.dumps(info), flush=True)
        return out

    initial_mod.InitialMultilevelBipartitioner.bipartition = bip_wrapper
    deep_mod.InitialMultilevelBipartitioner = initial_mod.InitialMultilevelBipartitioner

    import kaminpar_tpu as ktp

    host = generate(spec)
    t0 = time.perf_counter()
    part = (
        ktp.KaMinPar(preset)
        .set_graph(host)
        .compute_partition(k=k, epsilon=0.03, seed=1)
    )
    total = time.perf_counter() - t0

    from kaminpar_tpu.graphs.host import host_partition_metrics

    m = host_partition_metrics(host, part, k)
    by_phase = {}
    for e in EVENTS:
        by_phase.setdefault(e["phase"], [0.0, 0])
        by_phase[e["phase"]][0] += e["dt"]
        by_phase[e["phase"]][1] += 1
    print("== SUMMARY ==", flush=True)
    print(
        json.dumps(
            {
                "total_s": round(total, 1),
                "cut": int(m["cut"]),
                "imbalance": float(m["imbalance"]),
                "phases": {
                    p: {"s": round(v[0], 1), "calls": v[1]}
                    for p, v in sorted(
                        by_phase.items(), key=lambda kv: -kv[1][0]
                    )
                },
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
