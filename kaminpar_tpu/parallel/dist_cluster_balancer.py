"""Distributed cluster balancer over the device mesh.

Analog of the reference's ClusterBalancer
(kaminpar-dist/refinement/balancer/cluster_balancer.cc, move-cluster
construction in balancer/clusters.cc): when single-node moves cannot
rebalance an overloaded block — a border node is too heavy, or every
individual move has prohibitive loss — whole *move clusters* of connected
nodes are relocated at once.

The reference builds move clusters locally per PE (clusters.cc; clusters
never span PEs) and selects moves globally through per-block priority
queues merged over a binary reduction tree.  The TPU redesign keeps both
halves but expresses them bulk-synchronously:

  build    per device, a few LP-style merge rounds agglomerate the owned
           nodes of overloaded blocks into clusters no heavier than the
           per-block shed limit — the segmented-reduction form of
           clusters.cc's greedy cluster growing.  Clusters never span
           devices or blocks, exactly like the reference's.

  rate     per cluster: connection weight to every adjacent block via one
           aggregate_by_key keyed by (cluster leader, neighbor block);
           intra-cluster edges are excluded (they move with the cluster),
           edges to the home block are the loss term (the reference's
           cluster gain, cluster_balancer.cc ClustersMemoryContext).

  select   each device locally sorts out its TOP-T cluster candidates by
           relative gain (the per-PE priority queue) and all_gathers the
           [T] candidate tuples — O(D*T) volume, not O(n); every device
           runs the identical capacity-respecting prefix commit
           (ops/segments.accept_prefix_by_capacity) — the collective
           replacement for the reduction tree + rank-0 pick + broadcast.

  apply    members adopt their leader's accepted target locally (clusters
           never span devices); one O(interface) mesh.halo_exchange
           republishes the changed labels to ghosts.  The single O(n)
           all_gather runs at loop exit.

Used by the hybrid refinement pipeline when the node balancer alone cannot
reach feasibility (factories.cc HYBRID_CLUSTER_BALANCER lineage).
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
    hash_u32,
)
from .dist_balancer import topk_candidate_commit
from .dist_graph import DistGraph
from .mesh import NODE_AXIS, halo_exchange


def _build_local_clusters(
    src_l, dst_l, ew_l, nw_l, offset, n_loc, part_l, part_tab,
    in_overloaded, limit_of_block, k, salt, merge_rounds, dstloc_c,
):
    """Agglomerate owned overloaded-block nodes into move clusters.

    Returns i32[n_loc] cluster labels in *global node id* space: every
    participating node points at a leader owned by this device, within its
    own block; non-participants keep label -1.  Cluster weight never
    exceeds the block's shed limit (`limit_of_block`), mirroring the
    reference's cluster size strategy (clusters.cc build options).
    """
    node_ids_l = offset + jnp.arange(n_loc, dtype=jnp.int32)
    # local-local edges inside one overloaded block participate in merging
    dst_local = (dst_l >= offset) & (dst_l < offset + n_loc)
    seg = jnp.clip(src_l - offset, 0, n_loc - 1)
    labels = jnp.where(in_overloaded, node_ids_l, -1)
    # per-cluster weight, indexed by local leader slot
    cw = jnp.where(in_overloaded, nw_l, 0).astype(ACC_DTYPE)
    limit_l = limit_of_block[jnp.clip(part_l, 0, k - 1)]

    def body(i, state):
        labels, cw = state
        rsalt = (salt + i * jnp.int32(0x9E37)) & 0x7FFFFFFF
        lab_src = labels[seg]
        lab_dst = jnp.where(
            dst_local,
            labels[jnp.clip(dst_l - offset, 0, n_loc - 1)],
            -1,
        )
        same_block = dst_local & (part_tab[dstloc_c] == part_l[seg])
        # rate cluster-to-cluster: rows live at the *leader's* slot, so a
        # cluster weighs all its members' edges when picking a merge target
        key = jnp.where(
            same_block & (lab_dst >= 0) & (lab_src >= 0) & (lab_dst != lab_src),
            lab_dst,
            -1,
        )
        seg_m = jnp.where(key >= 0, lab_src - offset, -1)
        seg_g, key_g, w_g = aggregate_by_key(seg_m, key, ew_l)
        seg_gc = jnp.clip(seg_g, 0, n_loc - 1)
        my_lab = seg_g + offset  # group rows sit at leader slots
        fits = (
            cw[jnp.clip(key_g - offset, 0, n_loc - 1)] + cw[seg_gc]
            <= limit_l[seg_gc]
        )
        # hashed merge direction: 2-cycles become merges, not swaps
        dir_ok = hash_u32(key_g, rsalt) < hash_u32(my_lab, rsalt)
        feasible = (seg_g >= 0) & (key_g >= 0) & fits & dir_ok
        best, _ = argmax_per_segment(
            seg_g, key_g, w_g, n_loc, tie_salt=rsalt, feasible=feasible
        )
        is_leader = labels == node_ids_l
        wants = is_leader & (best >= 0)
        # accept under the target cluster's remaining limit headroom, so
        # simultaneous joins cannot blow past the shed limit
        headroom = jnp.maximum(limit_l - cw, 0)
        target_slot = jnp.where(wants, best - offset, -1)
        prio = hash_u32(node_ids_l, rsalt ^ 0x7F4A7C15)
        accept = accept_prefix_by_capacity(target_slot, prio, cw, headroom)
        # break chains: if the target leader itself joins someone this
        # round, cancel joins into it — accepted joins then have depth 1
        # and members can follow with a single pointer hop
        accept = accept & ~accept[jnp.clip(best - offset, 0, n_loc - 1)]
        new_leader_of_leader = jnp.where(accept, best, node_ids_l)
        lab_c = jnp.clip(labels - offset, 0, n_loc - 1)
        new_labels = jnp.where(
            labels >= 0, new_leader_of_leader[lab_c], labels
        )
        new_cw = jax.ops.segment_sum(
            jnp.where(new_labels >= 0, nw_l, 0).astype(ACC_DTYPE),
            jnp.clip(new_labels - offset, 0, n_loc - 1),
            num_segments=n_loc,
        )
        return new_labels, new_cw

    labels, cw = lax.fori_loop(0, merge_rounds, body, (labels, cw))
    return labels, cw


CLUSTER_CANDIDATES_PER_DEVICE = 2048


def dist_cluster_balance_round(
    src_l, dst_l, dstloc_l, ew_l, nw_l, n, part_l, ghost_part,
    send_idx_l, recv_map_l, k, cap, salt, merge_rounds,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One cluster-balancing round inside shard_map: build clusters, rate,
    top-T candidate gather + identical commit, apply locally.  Operates on
    the owner-sharded partition; returns (part_l, ghost_part, #moved,
    still_overloaded)."""
    n_loc = nw_l.shape[0]
    g_loc = ghost_part.shape[0]
    d = lax.axis_index(NODE_AXIS)
    offset = (d * n_loc).astype(jnp.int32)
    node_ids_l = offset + jnp.arange(n_loc, dtype=jnp.int32)
    seg = src_l - offset
    dstloc_c = jnp.clip(dstloc_l, 0, n_loc + g_loc - 1)
    part_tab = jnp.concatenate([part_l, ghost_part])

    bw = lax.psum(
        jax.ops.segment_sum(
            nw_l.astype(ACC_DTYPE), jnp.clip(part_l, 0, k - 1), num_segments=k
        ),
        NODE_AXIS,
    )
    overload = jnp.maximum(bw - cap, 0)
    headroom = jnp.maximum(cap - bw, 0)
    max_headroom = jnp.max(headroom)
    # shed limit: clusters heavier than the block's overload overshoot the
    # rebalance; heavier than every target's headroom are unplaceable
    limit_of_block = jnp.maximum(jnp.minimum(overload, max_headroom), 1)

    in_overloaded = (overload[jnp.clip(part_l, 0, k - 1)] > 0) & (
        node_ids_l < n
    )

    labels_l, cw_l = _build_local_clusters(
        src_l, dst_l, ew_l, nw_l, offset, n_loc, part_l, part_tab,
        in_overloaded, limit_of_block, k, salt, merge_rounds, dstloc_c,
    )

    # -- rate clusters against adjacent blocks ---------------------------
    seg_c = jnp.clip(seg, 0, n_loc - 1)
    lab_of_src = labels_l[seg_c]
    dst_local = (dst_l >= offset) & (dst_l < offset + n_loc)
    lab_of_dst = jnp.where(
        dst_local, labels_l[jnp.clip(dst_l - offset, 0, n_loc - 1)], -2
    )
    intra = (lab_of_src >= 0) & (lab_of_dst == lab_of_src)
    # rating rows live at the *leader's* local slot
    leader_slot = jnp.where(lab_of_src >= 0, lab_of_src - offset, -1)
    key_block = jnp.where(
        (lab_of_src >= 0) & ~intra & (dst_l < n), part_tab[dstloc_c], -1
    )
    seg_m = jnp.where(key_block >= 0, leader_slot, -1)
    seg_g, key_g, w_g = aggregate_by_key(seg_m, key_block, ew_l)
    seg_gc = jnp.clip(seg_g, 0, n_loc - 1)
    key_gc = jnp.clip(key_g, 0, k - 1)

    own_block = part_l[seg_gc]
    is_leader = (labels_l == node_ids_l) & (labels_l >= 0)
    tgt_ok = (
        (seg_g >= 0)
        & (key_g >= 0)
        & (key_g != own_block)
        & (overload[key_gc] == 0)
        & (cw_l[seg_gc] <= headroom[key_gc])
    )
    best, best_w = argmax_per_segment(
        seg_g, key_g, w_g, n_loc, tie_salt=salt ^ 0x2545F, feasible=tgt_ok
    )
    # loss term: external connection to the home block
    own_match = (seg_g >= 0) & (key_g == own_block)
    w_own = jax.ops.segment_max(
        jnp.where(own_match, w_g, 0),
        jnp.where(own_match, seg_g, n_loc),
        num_segments=n_loc + 1,
    )[:n_loc]
    w_own = jnp.maximum(w_own, 0)

    # a cluster with no adjacent feasible block may still shed into the
    # max-headroom block if it fits (the balancer's zero-gain fallback)
    fallback = jnp.argmax(headroom).astype(jnp.int32)
    fb_ok = (cw_l <= headroom[fallback]) & (fallback != part_l) & (
        overload[fallback] == 0
    )
    use_fb = (best < 0) & fb_ok
    target_l = jnp.where(use_fb, fallback, best)
    gain_l = jnp.where(use_fb, -w_own, best_w - w_own)

    cand = is_leader & (target_l >= 0)
    target_l = jnp.where(cand, target_l, -1)
    gain_l = jnp.where(cand, gain_l, 0)
    cwc_l = jnp.where(cand, cw_l, 0)

    # -- shared top-T gather + identical commit (see dist_balancer) ------
    order_l = -relative_gain_key(gain_l, cwc_l)
    T = min(CLUSTER_CANDIDATES_PER_DEVICE, n_loc)
    do, tgt_T, lid_T, accept, cw_g, tgt_g, src_block = topk_candidate_commit(
        target_l, order_l, cwc_l, part_l, overload, headroom, T, k, d,
    )

    # -- apply: members follow their leader (always local) ---------------
    accepted_leader = (
        jnp.zeros(n_loc, dtype=jnp.bool_)
        .at[lid_T]
        .set(do, mode="drop")
    )
    tgt_of_leader = (
        jnp.full(n_loc, -1, dtype=jnp.int32)
        .at[lid_T]
        .set(jnp.where(do, tgt_T, -1), mode="drop")
    )
    lab_slot = jnp.clip(labels_l - offset, 0, n_loc - 1)
    member_moves = (labels_l >= 0) & accepted_leader[lab_slot]
    new_part_l = jnp.where(
        member_moves, jnp.clip(tgt_of_leader[lab_slot], 0, k - 1), part_l
    )
    new_ghost = halo_exchange(new_part_l, send_idx_l, recv_map_l, g_loc)
    moved = jnp.sum(accept.astype(jnp.int32))
    # post-move block weights from the gathered accepted candidates —
    # saves the cond() a second cross-device weight reduction
    moved_w = jnp.where(accept, cw_g, 0)
    delta_in = jax.ops.segment_sum(
        moved_w, jnp.clip(tgt_g, 0, k - 1), num_segments=k
    )
    delta_out = jax.ops.segment_sum(
        moved_w, jnp.clip(src_block, 0, k - 1), num_segments=k
    )
    still_overloaded = jnp.any(bw - delta_out + delta_in > cap)
    return new_part_l, new_ghost, moved, still_overloaded


@partial(
    jax.jit, static_argnames=("mesh", "k", "max_rounds", "merge_rounds")
)
def _dist_cluster_balance_impl(
    mesh, graph, partition, k, cap, seed, max_rounds, merge_rounds
):
    def per_device(src_l, dst_l, dstloc_l, ew_l, nw_l, n, ghost_gid_l,
                   send_idx_l, recv_map_l, part0, cap, seed):
        n_loc = nw_l.shape[0]
        d = lax.axis_index(NODE_AXIS)
        offset = (d * n_loc).astype(jnp.int32)
        part_l0 = lax.dynamic_slice(part0, (offset,), (n_loc,))
        ghost0 = part0[jnp.clip(ghost_gid_l, 0, part0.shape[0] - 1)]

        def cond(state):
            i, _, _, moved, still_overloaded = state
            return (i < max_rounds) & (moved != 0) & still_overloaded

        def body(state):
            i, part_l, ghost, _, _ = state
            salt = (seed.astype(jnp.int32) * 48611 + i * 104729) & 0x7FFFFFFF
            part_l, ghost, moved, still = dist_cluster_balance_round(
                src_l, dst_l, dstloc_l, ew_l, nw_l, n, part_l, ghost,
                send_idx_l, recv_map_l, k, cap, salt, merge_rounds,
            )
            return (i + 1, part_l, ghost, moved, still)

        _, part_l, _, _, _ = lax.while_loop(
            cond, body,
            (jnp.int32(0), part_l0, ghost0, jnp.int32(1), jnp.array(True)),
        )
        # ONE O(n) gather at loop exit
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


def dist_cluster_balance(
    graph: DistGraph,
    partition: jax.Array,
    k: int,
    max_block_weights,
    seed,
    max_rounds: int = 8,
    merge_rounds: int = 3,
) -> jax.Array:
    """Rebalance by moving whole clusters of nodes (ClusterBalancer
    analog, kaminpar-dist/refinement/balancer/cluster_balancer.cc).
    No-op on already-feasible partitions.  Returns the replicated
    partition."""
    return _dist_cluster_balance_impl(
        graph.src.sharding.mesh,
        graph,
        jnp.asarray(partition, jnp.int32),
        k,
        jnp.asarray(max_block_weights, ACC_DTYPE),
        jnp.asarray(seed),
        max_rounds,
        merge_rounds,
    )
