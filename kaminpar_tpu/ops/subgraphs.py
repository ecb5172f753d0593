"""Device-side block-induced subgraph extraction.

The TPU counterpart of the reference's preallocated-SubgraphMemory
extraction (kaminpar-shm/graphutils/subgraph_extractor.h:36-177), used by
deep multilevel's extend_partition (helper.cc:220,349).  Round 2 extracted
subgraphs on the host, which meant a FULL graph readback (hundreds of
MB) at every k-doubling.  Here the extraction is one device program:

  * nodes are permuted block-major (one n-wide stable sort by block id),
    giving each node a local index inside its block;
  * edges are filtered to intra-block and sorted by (block, local src)
    (one m-wide 2-key sort), giving each block a contiguous CSR slice;
  * per-block node/edge counts and block weights come back to the host in
    ONE small readback (k-length arrays) — the only host<->device traffic
    that scales with k, not with the graph.

Each block's subgraph is then packaged into the standard padded
DeviceGraph layout by `slice_block` (per-shape-bucket programs shared
across blocks and levels), and the doubled partition is assembled back on
device by `assemble_extended_partition` — the inverse permutation never
leaves the device.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..graphs.csr import DeviceGraph, NODE_DTYPE
from ..caching import pad_size
from .segments import ACC_DTYPE


class BlockExtraction(NamedTuple):
    """Device-side extraction state (all arrays stay on device).

    b         : i32[n_pad]   block of each node (k for pad nodes)
    new_id    : i32[n_pad]   local index of each node within its block
    node_start: i32[k+2]     prefix starts of the block-major node order
    edge_start: i32[k+2]     prefix starts of the block-major edge order
    ls_s/ld_s : i32[m_pad]   block-sorted edges, LOCAL endpoint ids
    w_s       : [m_pad]      block-sorted edge weights
    node_w_s  : [n_pad]      block-major node weights
    rowcount_s: i32[n_pad]   block-major per-node intra-block degree
    node_counts/edge_counts/block_weights : host numpy [k+1]
    """

    b: jax.Array
    new_id: jax.Array
    node_start: jax.Array
    edge_start: jax.Array
    ls_s: jax.Array
    ld_s: jax.Array
    w_s: jax.Array
    node_w_s: jax.Array
    rowcount_s: jax.Array
    node_counts: np.ndarray
    edge_counts: np.ndarray
    block_weights: np.ndarray


@partial(jax.jit, static_argnames=("k",))
def _extract_kernel(graph: DeviceGraph, partition: jax.Array, k: int):
    n_pad = graph.n_pad
    m_pad = graph.m_pad
    node_ids = jnp.arange(n_pad, dtype=NODE_DTYPE)
    is_real = node_ids < graph.n
    b = jnp.where(is_real, jnp.clip(partition, 0, k - 1), k).astype(
        NODE_DTYPE
    )

    # ---- block-major node order (stable: ids stay ascending per block)
    b_s, perm = lax.sort((b, node_ids), num_keys=1)
    node_counts = jax.ops.segment_sum(
        jnp.ones(n_pad, dtype=NODE_DTYPE), b, num_segments=k + 1
    )
    node_start = jnp.concatenate(
        [jnp.zeros(1, NODE_DTYPE), jnp.cumsum(node_counts)]
    ).astype(NODE_DTYPE)
    pos = jnp.arange(n_pad, dtype=NODE_DTYPE)
    new_id_sorted = pos - node_start[b_s]
    new_id = (
        jnp.zeros(n_pad, dtype=NODE_DTYPE)
        .at[perm]
        .set(new_id_sorted, mode="drop")
    )
    node_w_s = graph.node_w[perm]
    block_weights = jax.ops.segment_sum(
        graph.node_w.astype(ACC_DTYPE), b, num_segments=k + 1
    )

    # ---- intra-block edges, block-major, local endpoints
    bs = b[graph.src]
    bd = b[graph.dst]
    valid = graph.edge_mask()
    keep = valid & (bs == bd) & (bs < k)
    ekey = jnp.where(keep, bs, k).astype(NODE_DTYPE)
    ls = jnp.where(keep, new_id[graph.src], 0).astype(NODE_DTYPE)
    ld = jnp.where(keep, new_id[graph.dst], 0).astype(NODE_DTYPE)
    w = jnp.where(keep, graph.edge_w, 0)
    ekey_s, ls_s, ld_s, w_s = lax.sort((ekey, ls, ld, w), num_keys=2)
    edge_counts = jax.ops.segment_sum(
        jnp.ones(m_pad, dtype=NODE_DTYPE), ekey, num_segments=k + 1
    )
    edge_start = jnp.concatenate(
        [jnp.zeros(1, NODE_DTYPE), jnp.cumsum(edge_counts)]
    ).astype(NODE_DTYPE)

    # ---- per-node intra-block degree in block-major order
    edge_pos = jnp.where(keep, node_start[bs] + new_id[graph.src], n_pad)
    rowcount_s = jax.ops.segment_sum(
        jnp.ones(m_pad, dtype=NODE_DTYPE), edge_pos, num_segments=n_pad + 1
    )[:n_pad]

    return (
        b, new_id, node_start, edge_start, ls_s, ld_s, w_s, node_w_s,
        rowcount_s, node_counts, edge_counts, block_weights,
    )


def extract_blocks_device(
    graph: DeviceGraph, partition: jax.Array, k: int
) -> BlockExtraction:
    """Run the extraction kernel; one small host readback for the counts."""
    (
        b, new_id, node_start, edge_start, ls_s, ld_s, w_s, node_w_s,
        rowcount_s, node_counts_d, edge_counts_d, block_weights_d,
    ) = _extract_kernel(graph, partition, k)
    return BlockExtraction(
        b=b,
        new_id=new_id,
        node_start=node_start,
        edge_start=edge_start,
        ls_s=ls_s,
        ld_s=ld_s,
        w_s=w_s,
        node_w_s=node_w_s,
        rowcount_s=rowcount_s,
        node_counts=np.asarray(node_counts_d),
        edge_counts=np.asarray(edge_counts_d),
        block_weights=np.asarray(block_weights_d),
    )


@partial(jax.jit, static_argnames=("n_pad_sub", "m_pad_sub"))
def _slice_block_kernel(
    ls_s: jax.Array,
    ld_s: jax.Array,
    w_s: jax.Array,
    node_w_s: jax.Array,
    rowcount_s: jax.Array,
    node_start_b: jax.Array,
    n_b: jax.Array,
    edge_start_b: jax.Array,
    m_b: jax.Array,
    n_pad_sub: int,
    m_pad_sub: int,
):
    """Package one block's slice of the block-major arrays into the
    standard padded DeviceGraph layout (pad node = n_pad_sub - 1)."""
    pad_node = n_pad_sub - 1
    ni = jnp.arange(n_pad_sub, dtype=NODE_DTYPE)
    n_mask = ni < n_b
    npos = jnp.clip(node_start_b + ni, 0, node_w_s.shape[0] - 1)
    node_w = jnp.where(n_mask, node_w_s[npos], 0).astype(node_w_s.dtype)
    rowcount = jnp.where(n_mask, rowcount_s[npos], 0).astype(NODE_DTYPE)
    row_ptr = jnp.concatenate(
        [jnp.zeros(1, NODE_DTYPE), jnp.cumsum(rowcount).astype(NODE_DTYPE)]
    )
    row_ptr = jnp.minimum(row_ptr, m_b).astype(NODE_DTYPE)

    ei = jnp.arange(m_pad_sub, dtype=NODE_DTYPE)
    e_mask = ei < m_b
    epos = jnp.clip(edge_start_b + ei, 0, ls_s.shape[0] - 1)
    src = jnp.where(e_mask, ls_s[epos], pad_node).astype(NODE_DTYPE)
    dst = jnp.where(e_mask, ld_s[epos], pad_node).astype(NODE_DTYPE)
    edge_w = jnp.where(e_mask, w_s[epos], 0).astype(w_s.dtype)
    return row_ptr, src, dst, edge_w, node_w


def slice_block(
    ext: BlockExtraction, block: int, n_floor: int, m_floor: int
) -> Tuple[DeviceGraph, int, int]:
    """Build block `block`'s subgraph as a padded DeviceGraph.
    Returns (subgraph, n_b, m_b)."""
    n_b = int(ext.node_counts[block])
    m_b = int(ext.edge_counts[block])
    n_pad_sub = pad_size(n_b + 1, n_floor)
    m_pad_sub = pad_size(max(m_b, 1), m_floor)
    from ..caching import record_padding

    record_padding(n=n_b + 1, n_pad=n_pad_sub, m=m_b, m_pad=m_pad_sub)
    row_ptr, src, dst, edge_w, node_w = _slice_block_kernel(
        ext.ls_s, ext.ld_s, ext.w_s, ext.node_w_s, ext.rowcount_s,
        ext.node_start[block], jnp.int32(n_b),
        ext.edge_start[block], jnp.int32(m_b),
        n_pad_sub, m_pad_sub,
    )
    sub = DeviceGraph(
        row_ptr=row_ptr,
        src=src,
        dst=dst,
        edge_w=edge_w,
        node_w=node_w,
        n=jnp.int32(n_b),
        m=jnp.int32(m_b),
    )
    return sub, n_b, m_b


def host_graph_from_padded(sub: DeviceGraph, n_b: int, m_b: int):
    """Download a (small) padded subgraph and trim on the host.  A plain
    array transfer — no per-shape device slicing programs."""
    from ..graphs.host import HostGraph

    xadj = np.asarray(sub.row_ptr)[: n_b + 1].astype(np.int64)
    adjncy = np.asarray(sub.dst)[:m_b].astype(np.int32)
    edge_w = np.asarray(sub.edge_w)[:m_b].astype(np.int64)
    node_w = np.asarray(sub.node_w)[:n_b].astype(np.int64)
    return HostGraph(
        xadj=xadj,
        adjncy=adjncy,
        node_weights=None if (node_w == 1).all() else node_w,
        edge_weights=None if m_b == 0 or (edge_w == 1).all() else edge_w,
    )


@partial(jax.jit, static_argnames=("k",))
def assemble_extended_partition(
    b: jax.Array,
    new_id: jax.Array,
    node_start: jax.Array,
    bp_global: jax.Array,
    base_id: jax.Array,
    is_split: jax.Array,
    k: int,
) -> jax.Array:
    """new_part[v] = base_id[b(v)] + (bp of v if its block was split).

    `bp_global` holds each split block's bipartition in block-major node
    order (see scatter in the driver); non-split blocks read 0."""
    bv = jnp.clip(b, 0, k - 1)
    pos = jnp.clip(node_start[bv] + new_id, 0, bp_global.shape[0] - 1)
    side = jnp.where(is_split[bv], bp_global[pos], 0)
    return (base_id[bv] + side).astype(jnp.int32)


@partial(jax.jit, static_argnames=("n_pad_sub",))
def scatter_block_bipartition(
    bp_global: jax.Array,
    bp_sub: jax.Array,
    node_start_b: jax.Array,
    n_b: jax.Array,
    n_pad_sub: int,
) -> jax.Array:
    """Write one block's bipartition (padded local array) into the
    block-major global buffer."""
    ni = jnp.arange(n_pad_sub, dtype=NODE_DTYPE)
    tgt = jnp.where(ni < n_b, node_start_b + ni, bp_global.shape[0])
    return bp_global.at[tgt].set(
        jnp.where(ni < n_b, bp_sub[:n_pad_sub].astype(jnp.int32), 0),
        mode="drop",
    )
