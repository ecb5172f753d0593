"""Distributed greedy node balancer over the device mesh.

Analog of the reference's NodeBalancer
(kaminpar-dist/refinement/balancer/node_balancer.cc): overloaded blocks
shed their lowest-loss border nodes into blocks with headroom until the
partition is feasible.  The reference merges per-PE candidate priority
queues through a binary reduction tree (balancer/reductions.h) and picks
moves on rank 0; the TPU version keeps the same shape with two
static-size collectives per round:

  * each device rates its owned nodes from the ghost-halo partition
    state (no replicated arrays) and locally sorts out its TOP-T move
    candidates by relative gain — the per-PE priority queue;
  * the [T] candidate tuples are all_gather'd (O(D*T) volume, the
    reduction-tree replacement) and EVERY device runs the identical
    capacity-respecting prefix commit
    (ops/segments.accept_prefix_by_capacity), so no broadcast is needed;
  * owners apply their accepted rows and push the changed labels to
    ghosts via mesh.halo_exchange (O(interface)).

A round therefore never moves an O(n) array across the mesh; if more
than T nodes per device must move, the next round picks the next batch —
exactly the reference's round structure (node_balancer.cc rounds).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

from ..ops.balancer import relative_gain_key
from ..ops.segments import (
    ACC_DTYPE,
    accept_prefix_by_capacity,
    aggregate_by_key,
    argmax_per_segment,
    connection_to_label,
)
from .dist_graph import DistGraph
from .mesh import account_collective, NODE_AXIS, halo_exchange

# Per-device candidate budget per round (the per-PE PQ size).  Small
# enough that the gathered tuple set stays KBs; the round loop batches
# larger rebalances (the loop runs until feasibility or a dry round, so
# the cap bounds per-round volume, not total throughput).
BALANCER_CANDIDATES_PER_DEVICE = 4096


def topk_candidate_commit(
    target_l, order_l, w_l, srcb_l, overload, headroom, T, k, d,
):
    """Shared top-T candidate protocol of the distributed balancers: sort
    the local candidates by `order_l` (ascending = best), all_gather the
    top-T tuples (O(D*T) — the reduction-tree replacement), run the
    identical capacity-respecting two-sided prefix commit on every
    device, and hand back this device's accepted rows.

    `target_l` must be -1 for non-candidates.  Returns (accepted_T
    bool[T], tgt_T i32[T], lid_T i32[T], accept [D*T], cw_g, tgt_g,
    src_block over the gathered rows) — callers apply their rows and
    derive post-move weights from the gathered arrays."""
    n_loc = target_l.shape[0]
    sort_key = jnp.where(target_l >= 0, order_l, jnp.float32(jnp.inf))
    lid = jnp.arange(n_loc, dtype=jnp.int32)
    key_s, tgt_s, w_s, lid_s = lax.sort(
        (sort_key, target_l, w_l, lid), num_keys=1
    )
    key_T, tgt_T, w_T, lid_T = key_s[:T], tgt_s[:T], w_s[:T], lid_s[:T]
    srcb_T = jnp.where(tgt_T >= 0, srcb_l[jnp.clip(lid_T, 0, n_loc - 1)], -1)

    tgt_g = lax.all_gather(tgt_T, NODE_AXIS, tiled=True)
    key_g = lax.all_gather(key_T, NODE_AXIS, tiled=True)
    w_g = lax.all_gather(w_T, NODE_AXIS, tiled=True)
    srcb_g = lax.all_gather(srcb_T, NODE_AXIS, tiled=True)

    src_block = jnp.where(tgt_g >= 0, jnp.clip(srcb_g, 0, k - 1), -1)
    accept_out = accept_prefix_by_capacity(
        src_block, key_g, w_g, overload, reach=True
    )
    target2 = jnp.where(accept_out, tgt_g, -1)
    accept_in = accept_prefix_by_capacity(target2, key_g, w_g, headroom)
    accept = accept_out & accept_in
    mine = lax.dynamic_slice(accept, (d * T,), (T,))
    accepted_T = mine & (tgt_T >= 0)
    return accepted_T, tgt_T, lid_T, accept, w_g, tgt_g, src_block


def dist_balance_round(
    src_l, dst_l, dstloc_l, ew_l, nw_l, n, part_l, ghost_part,
    send_idx_l, recv_map_l, k, cap, salt,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One balancing round, executed per device inside shard_map.

    Operates on the owner-sharded partition (part_l i32[n_loc] + ghost
    slice ghost_part i32[g_loc]); returns (new part_l, new ghost_part,
    global #moved, still_overloaded).  A round moves at most D*T nodes;
    the caller's loop keys on (moved, still_overloaded) so larger
    rebalances batch across rounds instead of being dropped."""
    n_loc = nw_l.shape[0]
    g_loc = ghost_part.shape[0]
    d = lax.axis_index(NODE_AXIS)
    offset = (d * n_loc).astype(jnp.int32)
    node_ids_l = offset + jnp.arange(n_loc, dtype=jnp.int32)
    seg = src_l - offset
    tab = jnp.concatenate([part_l, ghost_part])

    bw = lax.psum(
        jax.ops.segment_sum(
            nw_l.astype(ACC_DTYPE), jnp.clip(part_l, 0, k - 1), num_segments=k
        ),
        NODE_AXIS,
    )
    overload = jnp.maximum(bw - cap, 0)
    headroom = jnp.maximum(cap - bw, 0)

    in_overloaded = (overload[jnp.clip(part_l, 0, k - 1)] > 0) & (
        node_ids_l < n
    )

    # local candidate rating (node_balancer.cc: highest relative gain into a
    # non-overloaded block with room)
    neigh_block = tab[jnp.clip(dstloc_l, 0, n_loc + g_loc - 1)]
    seg_g, key_g, w_g = aggregate_by_key(seg, neigh_block, ew_l)
    key_c = jnp.clip(key_g, 0, k - 1)
    seg_c = jnp.clip(seg_g, 0, n_loc - 1)
    tgt_ok = (
        (seg_g >= 0)
        & (key_g != part_l[seg_c])
        & (overload[key_c] == 0)
        & (nw_l[seg_c].astype(ACC_DTYPE) <= headroom[key_c])
    )
    best, best_w = argmax_per_segment(
        seg_g, key_g, w_g, n_loc, tie_salt=salt, feasible=tgt_ok
    )
    w_own = connection_to_label(seg_g, key_g, w_g, part_l, n_loc)

    fallback = jnp.argmax(headroom).astype(jnp.int32)
    fallback_ok = nw_l.astype(ACC_DTYPE) <= headroom[fallback]
    use_fallback = (best < 0) & fallback_ok
    target_l = jnp.where(use_fallback, fallback, best)
    gain_l = jnp.where(use_fallback, -w_own, best_w - w_own)
    mover_l = in_overloaded & (target_l >= 0)
    target_l = jnp.where(mover_l, target_l, -1)

    # ---- shared top-T gather + identical deterministic commit ----------
    order_l = -relative_gain_key(gain_l, nw_l)  # ascending = best first
    T = min(BALANCER_CANDIDATES_PER_DEVICE, n_loc)
    do, tgt_T, lid_T, accept, w_g, tgt_g, src_block = topk_candidate_commit(
        target_l, order_l, nw_l, part_l, overload, headroom, T, k, d,
    )

    # ---- apply my accepted rows; push changed labels to ghosts ---------
    new_part_l = part_l.at[lid_T].set(
        jnp.where(
            do, jnp.clip(tgt_T, 0, k - 1),
            part_l[jnp.clip(lid_T, 0, n_loc - 1)],
        ),
        mode="drop",
    )
    new_ghost = halo_exchange(new_part_l, send_idx_l, recv_map_l, g_loc)
    # post-move overload status from the gathered accepted rows, so the
    # round loop can run to feasibility without a second weight reduction
    moved_w = jnp.where(accept, w_g, 0).astype(ACC_DTYPE)
    delta_in = jax.ops.segment_sum(
        moved_w, jnp.clip(tgt_g, 0, k - 1), num_segments=k
    )
    delta_out = jax.ops.segment_sum(
        moved_w, jnp.clip(src_block, 0, k - 1), num_segments=k
    )
    still_overloaded = jnp.any(
        bw - delta_out + delta_in > cap
    )
    return (
        new_part_l, new_ghost, jnp.sum(accept.astype(jnp.int32)),
        still_overloaded,
    )


@partial(jax.jit, static_argnames=("mesh", "k", "max_rounds"))
def _dist_node_balance_impl(mesh, graph, partition, k, cap, seed, max_rounds):
    def per_device(src_l, dst_l, dstloc_l, ew_l, nw_l, n, ghost_gid_l,
                   send_idx_l, recv_map_l, part0, cap, seed):
        n_loc = nw_l.shape[0]
        d = lax.axis_index(NODE_AXIS)
        offset = (d * n_loc).astype(jnp.int32)
        part_l0 = lax.dynamic_slice(part0, (offset,), (n_loc,))
        ghost0 = part0[jnp.clip(ghost_gid_l, 0, part0.shape[0] - 1)]

        def cond(state):
            i, _, _, moved, still = state
            return (i < max_rounds) & (moved != 0) & still

        def body(state):
            i, part_l, ghost, _, _ = state
            salt = (seed.astype(jnp.int32) * 62089911 + i * 7919) & 0x7FFFFFFF
            part_l, ghost, moved, still = dist_balance_round(
                src_l, dst_l, dstloc_l, ew_l, nw_l, n, part_l, ghost,
                send_idx_l, recv_map_l, k, cap, salt,
            )
            return (i + 1, part_l, ghost, moved, still)

        _, part_l, _, _, _ = lax.while_loop(
            cond, body,
            (jnp.int32(0), part_l0, ghost0, jnp.int32(1), jnp.array(True)),
        )
        # ONE O(n) gather at loop exit
        account_collective(
            "all_gather(partition)", part_l.size * 4, shape=part_l.shape
        )
        return lax.all_gather(part_l, NODE_AXIS, tiled=True)

    return _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
            P(NODE_AXIS), P(), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
            P(), P(), P(),
        ),
        out_specs=P(),
        check_vma=False,
    )(
        graph.src, graph.dst, graph.dst_local, graph.edge_w, graph.node_w,
        graph.n, graph.ghost_gid, graph.send_idx, graph.recv_map,
        partition, cap, seed,
    )


def dist_node_balance(
    graph: DistGraph,
    partition: jax.Array,
    k: int,
    max_block_weights,
    seed,
    max_rounds: int = 64,
) -> jax.Array:
    """Balance an infeasible partition on the mesh (NodeBalancer analog).
    Returns the replicated balanced partition.  The loop exits as soon as
    the partition is feasible or a round moves nothing, so the higher
    round cap only spends launches when a big overload needs batching
    through the per-round D*T candidate budget."""
    return _dist_node_balance_impl(
        graph.src.sharding.mesh,
        graph,
        jnp.asarray(partition, jnp.int32),
        k,
        jnp.asarray(max_block_weights, ACC_DTYPE),
        jnp.asarray(seed),
        max_rounds,
    )
