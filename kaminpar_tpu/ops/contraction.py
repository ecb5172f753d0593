"""Cluster contraction on device.

TPU re-design of kaminpar-shm/coarsening/contraction/ (BUFFERED/UNBUFFERED
cluster contraction, cluster_contraction.h:50-59 contract_clustering): given
per-node cluster labels, build the coarse graph whose nodes are clusters and
whose edges aggregate inter-cluster edge weights.

The reference remaps cluster ids to dense coarse ids with a parallel leader
mapping + prefix sum (cluster_contraction_preprocessing.cc:17,69
fill_leader_mapping), then deduplicates per-coarse-node edges through
per-thread rating maps (unbuffered_cluster_contraction.cc).  The TPU version
is two fused array programs around one host sync:

  part 1 (jit, fine shapes):  scatter-mark used labels -> prefix-sum dense
      ids (compact_unique), coarse node weights by segment sum, coarse edge
      endpoints (cu, cv) = (cmap[src], cmap[dst]) with self-loops and pad
      edges routed to a trailing sentinel, then one sorted segmented
      aggregation (ops/segments.aggregate_by_key) that yields the
      deduplicated coarse edge list in CSR order.

  host: read the coarse node/edge counts (the one unavoidable device->host
      sync per level — the multilevel driver needs them to pick the next
      shape bucket, SURVEY.md §7 'hard parts').

  part 2 (jit, coarse shapes): slice/pad the aggregated groups into the
      coarse shape bucket and rebuild row_ptr by counting sort.

Projection between levels (cluster_contraction.h:22-32 project_up/down) is
a single gather through the stored fine->coarse map.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ..graphs.csr import DeviceGraph, WEIGHT_DTYPE
from ..caching import pad_size
from .segments import ACC_DTYPE, aggregate_by_key


@jax.tree_util.register_dataclass
@dataclass
class CoarseGraph:
    """A coarse graph plus the fine->coarse projection map
    (analog of CoarseGraph in cluster_contraction.h:22-32)."""

    graph: DeviceGraph
    cmap: jax.Array  # i32[n_pad_fine]: coarse node id of each fine node

    def project_up(self, coarse_partition: jax.Array) -> jax.Array:
        """Coarse partition -> fine partition (project_up)."""
        return coarse_partition[self.cmap]

    def project_down(self, fine_partition: jax.Array) -> jax.Array:
        """Fine partition -> coarse partition by representative gather
        (project_down; consistent only if the fine partition is constant
        per cluster)."""
        n_pad_c = self.graph.n_pad
        first_fine = jax.ops.segment_min(
            jnp.arange(self.cmap.shape[0], dtype=jnp.int32),
            self.cmap,
            num_segments=n_pad_c,
        )
        safe = jnp.clip(first_fine, 0, self.cmap.shape[0] - 1)
        return fine_partition[safe]


@jax.jit
def _contract_part1(graph: DeviceGraph, labels: jax.Array):
    n_pad = graph.n_pad
    node_ids = jnp.arange(n_pad, dtype=jnp.int32)
    is_real = node_ids < graph.n

    # dense coarse ids (fill_leader_mapping + prefix sum analog)
    lab = jnp.clip(labels, 0, n_pad - 1)
    used = jnp.zeros(n_pad, dtype=jnp.int32).at[lab].max(
        is_real.astype(jnp.int32)
    )
    rank = jnp.cumsum(used) - used
    cmap = jnp.where(is_real, rank[lab], -1).astype(jnp.int32)
    # coarse-node count <= n, ID domain  # tpulint: disable=R3
    c_n = jnp.sum(used, dtype=jnp.int32)

    # coarse node weights over fine slots
    c_node_w = jax.ops.segment_sum(
        jnp.where(is_real, graph.node_w, 0).astype(ACC_DTYPE),
        jnp.clip(cmap, 0, n_pad - 1),
        num_segments=n_pad,
    ).astype(WEIGHT_DTYPE)

    # coarse edges: route self-loops and pad edges to a trailing
    # sentinel (aggregate_by_key sorts by (cu, cv), so slot order is free)
    sentinel = jnp.int32(n_pad)
    cu = jnp.where(graph.src < graph.n, cmap[jnp.clip(graph.src, 0, n_pad - 1)], sentinel)
    cv = jnp.where(graph.dst < graph.n, cmap[jnp.clip(graph.dst, 0, n_pad - 1)], sentinel)
    valid = (cu != cv) & (cu < sentinel) & (cv < sentinel)
    cu = jnp.where(valid, cu, sentinel)
    cv = jnp.where(valid, cv, sentinel)
    w = jnp.where(valid, graph.edge_w, 0)

    cu_g, cv_g, w_g = aggregate_by_key(cu, cv, w)
    group_valid = (cu_g >= 0) & (cu_g < sentinel)
    # coarse-edge count <= m_pad < 2^31 (device layout)  # tpulint: disable=R3
    c_m = jnp.sum(group_valid, dtype=jnp.int32)
    return cmap, c_n, c_node_w, cu_g, cv_g, w_g, group_valid, c_m


@partial(jax.jit, static_argnames=("n_pad_c", "m_pad_c"))
def _contract_part2(
    n_pad_c: int,
    m_pad_c: int,
    cmap,
    c_n,
    c_node_w,
    cu_g,
    cv_g,
    w_g,
    group_valid,
    c_m,
):
    pad_node = n_pad_c - 1
    m_pad_f = cu_g.shape[0]

    def fit_edges(x, fill):
        if m_pad_c <= m_pad_f:
            return x[:m_pad_c]
        return jnp.concatenate(
            [x, jnp.full(m_pad_c - m_pad_f, fill, dtype=x.dtype)]
        )

    slot = jnp.arange(m_pad_c, dtype=jnp.int32)
    in_range = slot < c_m
    src_c = jnp.where(in_range, fit_edges(cu_g, 0), pad_node).astype(jnp.int32)
    dst_c = jnp.where(in_range, fit_edges(cv_g, 0), pad_node).astype(jnp.int32)
    w_c = jnp.where(in_range, fit_edges(w_g, 0), 0).astype(WEIGHT_DTYPE)

    counts = jax.ops.segment_sum(
        in_range.astype(jnp.int32),
        jnp.clip(src_c, 0, n_pad_c - 1),
        num_segments=n_pad_c,
    )
    # pad-node slot may have absorbed counts from pad edges; real coarse
    # nodes are < c_n so zero counts beyond c_n
    counts = jnp.where(jnp.arange(n_pad_c) < c_n, counts, 0)
    row_ptr = jnp.concatenate(
        # row_ptr tops out at m_pad < 2^31 (device layout contract);
        # host xadj stays int64  # tpulint: disable=R3
        [jnp.zeros(1, dtype=jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )

    n_pad_f = c_node_w.shape[0]

    def fit_nodes(x, fill):
        if n_pad_c <= n_pad_f:
            return x[:n_pad_c]
        return jnp.concatenate(
            [x, jnp.full(n_pad_c - n_pad_f, fill, dtype=x.dtype)]
        )

    node_w_c = jnp.where(
        jnp.arange(n_pad_c) < c_n, fit_nodes(c_node_w, 0), 0
    ).astype(WEIGHT_DTYPE)
    cmap_final = jnp.where(cmap >= 0, cmap, pad_node).astype(jnp.int32)

    coarse = DeviceGraph(
        row_ptr=row_ptr,
        src=src_c,
        dst=dst_c,
        edge_w=w_c,
        node_w=node_w_c,
        n=c_n.astype(jnp.int32),
        m=c_m.astype(jnp.int32),
    )
    return coarse, cmap_final


def contract_clustering(
    graph: DeviceGraph, labels: jax.Array
) -> Tuple[CoarseGraph, int, int]:
    """Contract `labels` over `graph`; returns (CoarseGraph, c_n, c_m).

    Two device programs around one host sync for the coarse sizes (see
    module docstring).  The coarse graph lands in pad_size shape buckets so
    repeated contractions reuse compiled executables.
    """
    # `device-oom` chaos injection point (contraction mints the largest
    # fresh buffers of a level) — handled by the recovery ladder
    from ..resilience import maybe_inject

    maybe_inject("device-oom")
    cmap, c_n, c_node_w, cu_g, cv_g, w_g, group_valid, c_m = _contract_part1(
        graph, labels
    )
    from ..graphs.csr import shape_floors

    c_n_i, c_m_i = int(c_n), int(c_m)
    n_floor, m_floor = shape_floors()
    n_pad_c = pad_size(c_n_i + 1, n_floor)
    m_pad_c = pad_size(max(c_m_i, 1), m_floor)
    from ..caching import record_padding

    record_padding(n=c_n_i + 1, n_pad=n_pad_c, m=c_m_i, m_pad=m_pad_c)
    coarse, cmap_final = _contract_part2(
        n_pad_c, m_pad_c, cmap, c_n, c_node_w, cu_g, cv_g, w_g, group_valid, c_m
    )
    return CoarseGraph(graph=coarse, cmap=cmap_final), c_n_i, c_m_i
