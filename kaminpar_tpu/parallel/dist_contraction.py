"""Sharded distributed cluster contraction over the device mesh.

Analog of the reference's global contraction
(kaminpar-dist/coarsening/contraction/global_cluster_contraction.cc, in
particular the coarse node/edge migration alltoalls at :1100+): build the
coarse graph from a clustering WITHOUT ever materializing the fine graph
on one device.  Per device inside `shard_map`:

  1. map the local edge shard to coarse endpoints (labels and the dense
     leader->coarse-id map are replicated — both are O(n) arrays the
     driver already holds);
  2. locally deduplicate (cu, cv) pairs with one sort-based
     aggregate_by_key — the per-PE rating-map dedup of the reference;
  3. MIGRATE: bucket the deduplicated rows by HASH(cu, cv) mod D and
     exchange them with ONE static [D, cap] all_to_all — the
     reference's sparse alltoall of coarse edges.  Hashing the PAIR
     (not cu ownership chunks) is the skew defense: a star-like
     clustering concentrates all coarse edges on one cu, but its
     (cu, cv) pairs still spread uniformly because cv varies — no
     single device's buckets can be flooded by one heavy coarse node
     (the reference instead rebalances explicit node ownership,
     global_cluster_contraction.cc:1100+; a uniform hash needs no
     balancing pass at all);
  4. merge rows arriving from different source devices with a second
     aggregate_by_key; every (cu, cv) pair now lives exactly once, on
     its hash owner.

The host driver assembles the per-shard results into the coarse CSR
(one lexsort of coarse-sized rows — shards hold disjoint pair sets but
interleaved cu ranges) — the coarse graph is geometrically smaller, and
the fine edge list never leaves its shards.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

from ..graphs.host import HostGraph
from ..ops.segments import ACC_DTYPE, aggregate_by_key, hash_u32
from .dist_graph import DistGraph
from .mesh import NODE_AXIS

# output rows per device = OUT_FACTOR * m_loc; with hash-bucketed pairs
# a device's merged coarse rows concentrate only if the HASH does, so
# this is a safety net, not a skew knob — the driver checks the returned
# count and raises rather than truncating
OUT_FACTOR = 2

# per-peer migrate bucket capacity = max(m_loc * BUCKET_SLACK // D,
# BUCKET_MIN): O(m_loc/D) per device instead of O(m_loc) per PEER, so
# total buffer memory stays O(m_loc * slack) — the point of sharding.
# Residual overflows (count per bucket) are detected and raise.
BUCKET_SLACK = 4
BUCKET_MIN = 1 << 16


@partial(jax.jit, static_argnames=("mesh",))
def _dist_contract_edges_impl(mesh, graph: DistGraph, labels, cmap_full):
    D = int(mesh.devices.size)
    n_pad = graph.n_pad

    def per_device(src_l, dst_l, ew_l, n, labels, cmap_full):
        cap = src_l.shape[0]  # m_loc
        # 1. coarse endpoints of the local edge shard
        lab_src = labels[jnp.clip(src_l, 0, n_pad - 1)]
        lab_dst = labels[jnp.clip(dst_l, 0, n_pad - 1)]
        cu = cmap_full[jnp.clip(lab_src, 0, n_pad - 1)]
        cv = cmap_full[jnp.clip(lab_dst, 0, n_pad - 1)]
        keep = (src_l < n) & (dst_l < n) & (cu != cv)

        # 2. local dedup (rows compacted to the front, sorted by (cu, cv)).
        # Invalid rows use a LARGE sentinel, not -1: aggregate_by_key sorts
        # groups by key ascending, and the valid rows must form the PREFIX
        big = jnp.int32(n_pad)
        seg = jnp.where(keep, cu, big)
        seg_g, key_g, w_g = aggregate_by_key(seg, jnp.where(keep, cv, big), ew_l)
        rows_valid = (seg_g >= 0) & (seg_g < big)

        # 3. migrate: bucket rows by hash(cu, cv) mod D — uniform across
        # devices regardless of coarse-degree skew (see module doc); the
        # same pair hashes identically everywhere, so duplicates still
        # meet.  Rows are re-sorted by target so the in-bucket position
        # is index minus the target's first index.  Bucket capacity is
        # O(m_loc/D) (+slack), not m_loc — total send+recv memory stays
        # O(m_loc), the point of a sharded contraction; residual
        # overflows are detected, not truncated
        bcap = max(cap * BUCKET_SLACK // D, BUCKET_MIN)
        pair_h = hash_u32(
            seg_g ^ (key_g * jnp.int32(-1640531527)), 0x5C0A
        )
        tgt = jnp.where(rows_valid, pair_h % D, D).astype(jnp.int32)
        tgt, seg_g, key_g, w_g = lax.sort(
            (tgt, seg_g, key_g, w_g), num_keys=1
        )
        rows_valid = tgt < D
        idx = jnp.arange(cap, dtype=jnp.int32)
        start = jax.ops.segment_min(
            jnp.where(rows_valid, idx, cap), tgt, num_segments=D + 1
        )
        pos = idx - start[jnp.clip(tgt, 0, D - 1)]
        overflow = jnp.sum(
            (rows_valid & (pos >= bcap)).astype(jnp.int32)
        )
        flat = jnp.where(
            rows_valid & (pos < bcap), tgt * bcap + pos, D * bcap
        )

        def to_buckets(vals, fill):
            buf = (
                jnp.full(D * bcap + 1, fill, dtype=vals.dtype)
                .at[flat]
                .set(jnp.where(rows_valid, vals, fill), mode="drop")
            )
            return buf[: D * bcap].reshape(D, bcap)

        send_cu = to_buckets(seg_g, jnp.int32(-1))
        send_cv = to_buckets(key_g, jnp.int32(-1))
        send_w = to_buckets(w_g, jnp.zeros((), ACC_DTYPE))
        from .mesh import account_collective

        account_collective(
            "all_to_all(contraction-edges)",
            sum(b.size * b.dtype.itemsize for b in (send_cu, send_cv, send_w)),
            shape=send_cu.shape,
        )
        recv_cu = lax.all_to_all(send_cu, NODE_AXIS, 0, 0, tiled=True)
        recv_cv = lax.all_to_all(send_cv, NODE_AXIS, 0, 0, tiled=True)
        recv_w = lax.all_to_all(send_w, NODE_AXIS, 0, 0, tiled=True)

        # 4. merge duplicates arriving from different source devices (the
        # same large-sentinel rule keeps valid rows as the prefix).  A
        # bucket overflow anywhere poisons `count` past out_cap so the
        # driver raises instead of silently dropping rows.
        seg2 = recv_cu.reshape(-1)
        cv2 = recv_cv.reshape(-1)
        seg_f, key_f, w_f = aggregate_by_key(
            jnp.where(seg2 >= 0, seg2, big),
            jnp.where(seg2 >= 0, cv2, big),
            recv_w.reshape(-1),
        )
        valid_f = (seg_f >= 0) & (seg_f < big)
        out_cap = OUT_FACTOR * cap
        total_overflow = lax.psum(overflow, NODE_AXIS)
        count = jnp.where(
            total_overflow > 0,
            jnp.int32(out_cap + 1),
            jnp.sum(valid_f.astype(jnp.int32)),
        )
        return seg_f[:out_cap], key_f[:out_cap], w_f[:out_cap], count[None]

    return _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
            P(), P(), P(),
        ),
        out_specs=(P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS)),
        check_vma=False,
    )(
        graph.src, graph.dst, graph.edge_w, graph.n,
        labels, cmap_full,
    )


def dist_contract_clustering(
    graph: DistGraph,
    dg_host_n: int,
    node_w: np.ndarray,
    labels: np.ndarray,
) -> Tuple[HostGraph, np.ndarray]:
    """Contract a clustering of the sharded graph; returns (coarse
    HostGraph, cmap fine->coarse).  The coarse edge list is produced by
    the sharded migrate kernel above; only coarse-sized data reaches the
    host."""
    n_pad = graph.n_pad
    lab = np.asarray(labels, dtype=np.int64)
    used = np.zeros(n_pad, dtype=bool)
    used[lab[:dg_host_n]] = True
    # coarse ids <= n, ID domain  # tpulint: disable=R3
    cmap_full = (np.cumsum(used) - 1).astype(np.int32)
    c_n = int(used.sum())
    cmap = cmap_full[lab[:dg_host_n]]

    cu_s, cv_s, w_s, counts = _dist_contract_edges_impl(
        graph.src.sharding.mesh, graph, jnp.asarray(lab, jnp.int32),
        jnp.asarray(cmap_full),
    )
    D = int(graph.src.sharding.mesh.devices.size)
    cu_s = np.asarray(cu_s).reshape(D, -1)
    cv_s = np.asarray(cv_s).reshape(D, -1)
    w_s = np.asarray(w_s).reshape(D, -1)
    counts = np.asarray(counts).reshape(-1)
    out_cap = cu_s.shape[1]
    if (counts > out_cap).any():
        raise RuntimeError(
            "sharded contraction overflow: a migrate bucket or a device's "
            f"merged coarse rows exceed capacity ({out_cap}); raise "
            "dist_contraction.OUT_FACTOR / BUCKET_SLACK"
        )
    # shards hold disjoint (cu, cv) pair sets but interleaved cu ranges
    # (hash bucketing), so canonicalize with one coarse-sized lexsort
    parts_cu = [cu_s[d, : counts[d]] for d in range(D)]
    parts_cv = [cv_s[d, : counts[d]] for d in range(D)]
    parts_w = [w_s[d, : counts[d]] for d in range(D)]
    g_cu = np.concatenate(parts_cu) if parts_cu else np.zeros(0, np.int64)
    g_cv = np.concatenate(parts_cv)
    g_w = np.concatenate(parts_w).astype(np.int64)
    order = np.lexsort((g_cv, g_cu))
    g_cu, g_cv, g_w = g_cu[order], g_cv[order], g_w[order]

    c_node_w = np.zeros(c_n, dtype=np.int64)
    np.add.at(c_node_w, cmap, np.asarray(node_w[:dg_host_n], dtype=np.int64))
    xadj = np.zeros(c_n + 1, dtype=np.int64)
    np.add.at(xadj, g_cu.astype(np.int64) + 1, 1)
    xadj = np.cumsum(xadj)
    coarse = HostGraph(
        xadj=xadj,
        adjncy=g_cv.astype(np.int32),
        node_weights=c_node_w,
        edge_weights=(
            g_w if len(g_w) and not (g_w == 1).all() else None
        ),
    )
    return coarse, cmap
