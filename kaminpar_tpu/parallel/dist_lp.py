"""Distributed bulk-synchronous label propagation over a device mesh.

The TPU re-design of the reference's distributed LP
(kaminpar-dist/distributed_label_propagation.h + coarsening/clustering/lp/
global_lp_clusterer.cc): where the reference interleaves local async LP
chunks with two communication steps per chunk —

  * `control_cluster_weights` (weight-delta sparse alltoall + allreduce,
    global_lp_clusterer.cc:429,174), and
  * `synchronize_ghost_node_clusters` (interface→PE sparse alltoall,
    global_lp_clusterer.cc:585-594)

— this kernel runs whole-graph bulk-synchronous rounds inside `shard_map`
where those two exchanges become exactly two XLA collectives per round:

  * a `psum` of per-cluster join demand + weight deltas (weight control),
  * an O(interface) halo exchange of the interface nodes' labels
    (mesh.halo_exchange — ghost sync; labels are owner-sharded, one
    all_gather runs at loop exit only).

Cluster-weight safety across devices uses demand throttling instead of the
reference's overshoot-and-rollback: each round every device computes its
local join demand per cluster, the global demand is `psum`'d, and each
device's local capacity share is scaled by headroom/demand before the
capacity-respecting prefix commit (ops/segments.accept_prefix_by_capacity).
Total accepted weight per cluster is then provably <= headroom, so the max
cluster weight is never exceeded — strictly stronger than the reference's
relaxed protocol, which tolerates transient overshoot.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map as _shard_map

from ..ops.lp import LPConfig
from ..telemetry import progress as progress_mod
from ..ops.segments import (
    ACC_DTYPE,
    INT32_MIN,
    accept_prefix_by_capacity,
    aggregate_by_key,
    argmax_per_segment,
    best_from_dense,
    best_from_rating_table,
    dense_block_ratings,
    connection_to_label,
    connection_to_own_label,
    hash_u32,
    hashed_rating_table,
    move_weight_delta,
)
from .dist_graph import DistGraph
from .mesh import NODE_AXIS, halo_exchange, throttled_local_capacity


def _dist_lp_round(
    src_l: jax.Array,
    dst_l: jax.Array,
    dstloc_l: jax.Array,
    ew_l: jax.Array,
    nw_l: jax.Array,
    n: jax.Array,
    labels_l: jax.Array,
    ghost_lab: jax.Array,
    send_idx_l: jax.Array,
    recv_map_l: jax.Array,
    weights: jax.Array,
    cap: jax.Array,
    active_l: jax.Array,
    movable_l: jax.Array,
    salt: jax.Array,
    cfg: LPConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One round, executed per device inside shard_map — ghost-halo model.

    Labels are OWNER-SHARDED: labels_l i32[n_loc] holds the owned nodes'
    labels, ghost_lab i32[g_loc] the (synchronized) labels of this
    device's ghost nodes, and the local label table concat(labels_l,
    ghost_lab) is indexed by dstloc_l.  Label values stay GLOBAL cluster
    ids.  The per-round collectives are the O(interface) halo exchanges
    (mesh.halo_exchange — the synchronize_ghost_node_clusters analog) and
    one dense psum of per-cluster weight deltas; nothing is all_gather'd.
    weights/cap i32[C] stay replicated (the dense-reduce weight-control
    tradeoff: a psum rides ICI at reduction bandwidth, while the
    reference's sparse owner messages have no static-shape XLA form).

    Returns (labels_l, ghost_lab, weights, active_l, num_wanting).
    """
    n_loc = nw_l.shape[0]
    g_loc = ghost_lab.shape[0]
    C = weights.shape[0]
    d = lax.axis_index(NODE_AXIS)
    offset = (d * n_loc).astype(jnp.int32)
    node_ids_l = offset + jnp.arange(n_loc, dtype=jnp.int32)
    lab_tab = jnp.concatenate([labels_l, ghost_lab])

    # -- rate: per-owned-node best cluster over the local edge shard,
    # same engine dispatch as the single-chip lp_round (ops/lp.py): the
    # device holds every edge of its owned nodes, so hashed winner sums
    # and dense tables are exact locally
    from ..ops.rating import select_engine

    neighbor_cluster = lab_tab[jnp.clip(dstloc_l, 0, n_loc + g_loc - 1)]
    seg = src_l - offset
    if cfg.rating == "sort2":
        # sort2 needs CSR row spans, which the sharded COO layout does not
        # carry — reject the explicit request rather than silently running
        # a different engine
        raise ValueError(
            "rating='sort2' is not available on the distributed path; "
            "use 'scatter', 'hash', 'sort', or 'auto'"
        )
    # the engine flag is trace-time static: cfg threads through
    # shard_map as a closure constant, so every device compiles the
    # same engine (row_spans=False removes the sort2 row engines)
    engine, _ = select_engine(
        cfg.rating, C, n_loc, src_l.shape[0],
        num_slots=cfg.num_slots, row_spans=False,
    )
    barred_l = jnp.zeros(n_loc, dtype=bool)
    if engine == "scatter":
        # scatter-add slot tables (ops/rating.py): each device holds
        # every edge of its owned nodes, so the per-row elimination
        # passes are exact locally; still-contested rows are barred
        # from moving this round, and the round falls back to the
        # exact sort rating when too many owned rows are barred (the
        # predicate is LOCAL by design — a lax.cond inside shard_map
        # must not branch on a collective, and per-device engine
        # divergence is fine: the commit protocol is engine-agnostic)
        from ..ops.rating import best_from_slots, scatter_slot_ratings

        in_range = (seg >= 0) & (seg < n_loc)
        # rows are the n_loc OWNED nodes, labels are GLOBAL cluster ids
        # (C-wide) — label_space keeps the winner packing and clipping
        # in the global domain.  The weight cap is decided per edge,
        # as in ops/lp.lp_round: weights and cap are replicated C-wide,
        # the neighbour's cluster is in hand, and the sharded COO has
        # no row spans, so the owner's weight and label are gathers at
        # edge width too
        seg_c = jnp.clip(seg, 0, n_loc - 1)
        room = (cap - weights.astype(ACC_DTYPE))[
            jnp.clip(neighbor_cluster, 0, C - 1)
        ]
        joinable = (nw_l[seg_c].astype(ACC_DTYPE) <= room) | (
            neighbor_cluster == labels_l[seg_c]
        )
        slot_label, slot_w, fully_rated = scatter_slot_ratings(
            seg_c, neighbor_cluster, ew_l, n_loc, cfg.num_slots, salt,
            valid=in_range, label_space=C, joinable=joinable,
        )
        label_range = None
        if cfg.dist_local_only:
            label_range = (offset, offset + n_loc)

        def scatter_rate(_):
            b, bw, w_own = best_from_slots(
                slot_label, slot_w, labels_l, salt,
                label_range=label_range,
            )
            return b, bw, w_own, ~fully_rated

        def sort_rate(_):
            seg_g, key_g, w_g = aggregate_by_key(
                jnp.where(in_range, seg, -1), neighbor_cluster, ew_l
            )
            key_c = jnp.clip(key_g, 0, C - 1)
            seg_c = jnp.clip(seg_g, 0, n_loc - 1)
            fits = (
                weights[key_c].astype(ACC_DTYPE)
                + nw_l[seg_c].astype(ACC_DTYPE)
                <= cap[key_c]
            )
            feasible = (seg_g >= 0) & (key_g != labels_l[seg_c]) & fits
            if cfg.dist_local_only:
                owned = (key_g >= offset) & (key_g < offset + n_loc)
                feasible = feasible & owned
            b, bw = argmax_per_segment(
                seg_g, key_g, w_g, n_loc, tie_salt=salt, feasible=feasible
            )
            w_own = connection_to_label(seg_g, key_g, w_g, labels_l, n_loc)
            return b, bw, w_own, jnp.zeros(n_loc, dtype=bool)

        # local node counts <= n_loc, ID domain  # tpulint: disable=R3
        n_bar = jnp.sum(active_l & ~fully_rated, dtype=jnp.int32)
        # local node counts <= n_loc, ID domain  # tpulint: disable=R3
        n_act = jnp.sum(active_l, dtype=jnp.int32)
        use_scatter = n_bar.astype(jnp.float32) <= (
            jnp.float32(cfg.scatter_fallback) * n_act.astype(jnp.float32)
        )
        best, best_w, w_cur, barred_l = lax.cond(
            use_scatter, scatter_rate, sort_rate, None
        )
        best = jnp.where(barred_l, -1, best)
        best_w = jnp.where(barred_l, INT32_MIN, best_w)
    elif engine == "dense":
        conn = dense_block_ratings(
            seg, jnp.clip(dstloc_l, 0, n_loc + g_loc - 1), ew_l, lab_tab,
            n_loc, C,
        )
        allowed = None
        if cfg.dist_local_only:
            # LocalLPClusterer: only clusters led by owned nodes
            col = jnp.arange(C, dtype=jnp.int32)
            allowed = (col >= offset) & (col < offset + n_loc)
        best, best_w, w_cur = best_from_dense(
            conn, labels_l, weights, nw_l, cap, salt, allowed=allowed
        )
    elif engine == "hash":
        slot_label, slot_w = hashed_rating_table(
            seg, neighbor_cluster, ew_l, n_loc, cfg.num_slots, salt
        )
        label_range = None
        if cfg.dist_local_only:
            # LocalLPClusterer semantics: only join clusters led by an
            # owned node, so clusters never span device boundaries
            label_range = (offset, offset + n_loc)
        best, best_w = best_from_rating_table(
            slot_label, slot_w, labels_l, weights, nw_l, cap,
            salt ^ 0x51AB, label_range=label_range,
        )
        w_cur = connection_to_own_label(
            seg, neighbor_cluster, ew_l, labels_l, n_loc
        )
    else:  # sort
        seg_g, key_g, w_g = aggregate_by_key(seg, neighbor_cluster, ew_l)
        key_c = jnp.clip(key_g, 0, C - 1)
        seg_c = jnp.clip(seg_g, 0, n_loc - 1)
        fits = (
            weights[key_c].astype(ACC_DTYPE) + nw_l[seg_c].astype(ACC_DTYPE)
            <= cap[key_c]
        )
        feasible = (seg_g >= 0) & (key_g != labels_l[seg_c]) & fits
        if cfg.dist_local_only:
            owned = (key_g >= offset) & (key_g < offset + n_loc)
            feasible = feasible & owned
        best, best_w = argmax_per_segment(
            seg_g, key_g, w_g, n_loc, tie_salt=salt, feasible=feasible
        )
        w_cur = connection_to_label(seg_g, key_g, w_g, labels_l, n_loc)

    # -- select (same policy as the single-chip lp_round) ----------------
    gain = best_w - w_cur
    tie_dir_ok = hash_u32(best, salt ^ 0x5BD1) < hash_u32(labels_l, salt ^ 0x5BD1)
    if cfg.refinement:
        improves = gain > 0
    else:
        improves = (gain > 0) | (
            cfg.allow_tie_moves & (gain == 0) & (best_w > 0) & tie_dir_ok
        )
    participate = hash_u32(node_ids_l, salt ^ 0x27D4) < jnp.int32(
        cfg.participation * 2147483647.0
    )
    wants = (
        (best >= 0)
        & (best != labels_l)
        & improves
        & active_l
        & movable_l
        & (node_ids_l < n)
    )
    target_l = jnp.where(wants & participate, best, -1)

    dstloc_c = jnp.clip(dstloc_l, 0, n_loc + g_loc - 1)
    if cfg.refinement:
        # afterburner (shared with ops/lp.py lp_round): bulk-synchronous
        # adjacent moves can jointly increase the cut; costs one halo-
        # exchange pair per round (gain + target of interface nodes).
        # `wants` stays unmasked so filtered or unsampled nodes remain in
        # the convergence count/active set.
        # NOTE: INT32_MIN must stay the module-level import — a local
        # re-import here would shadow it for the WHOLE function and
        # break the scatter engine's earlier use (UnboundLocalError)
        from ..ops.segments import afterburner_filter

        gain_cand_l = jnp.where(target_l >= 0, gain, INT32_MIN)
        # exchanged ghost slots all receive real values (send lists are
        # complete); slots never referenced by any edge keep the scatter
        # fill, which no contribution reads.  One stacked launch for both.
        ghost_gain, ghost_target = halo_exchange(
            jnp.stack([gain_cand_l, target_l]), send_idx_l, recv_map_l, g_loc
        )
        gain_tab = jnp.concatenate([gain_cand_l, ghost_gain])
        target_tab = jnp.concatenate([target_l, ghost_target])
        adj_gain = afterburner_filter(
            seg, dstloc_c, ew_l, labels_l[jnp.clip(seg, 0, n_loc - 1)],
            neighbor_cluster, gain_tab, target_tab, seg, n_loc,
            # ordering must be a TOTAL order across devices: use global ids
            src_order=src_l, dst_order=dst_l,
        )
        target_l = jnp.where(adj_gain > 0, target_l, -1)

    # -- weight control: psum'd demand, throttled local capacity ---------
    local_cap = throttled_local_capacity(target_l, nw_l, weights, cap)

    prio_l = hash_u32(node_ids_l, salt ^ 0x165667B1)
    accept_l = accept_prefix_by_capacity(target_l, prio_l, nw_l, local_cap)

    # -- apply + the collectives (halo sync / weight control) ------------
    new_labels_l = jnp.where(accept_l, target_l, labels_l)
    moved_l = accept_l.astype(jnp.int32)
    if cfg.use_active_set:
        # labels + moved flags share one stacked exchange
        new_ghost_lab, ghost_moved = halo_exchange(
            jnp.stack([new_labels_l, moved_l]), send_idx_l, recv_map_l, g_loc
        )
    else:
        new_ghost_lab = halo_exchange(
            new_labels_l, send_idx_l, recv_map_l, g_loc
        )
        ghost_moved = None

    from .mesh import account_collective

    delta_l = move_weight_delta(labels_l, target_l, accept_l, nw_l, C)
    account_collective(
        "psum(weight-delta)",
        delta_l.size * delta_l.dtype.itemsize,
        shape=delta_l.shape,
    )
    delta = lax.psum(delta_l, NODE_AXIS)
    new_weights = (weights.astype(ACC_DTYPE) + delta).astype(weights.dtype)

    # -- active set (label_propagation.h:507-513 analog) -----------------
    if cfg.use_active_set:
        moved_tab = jnp.concatenate([moved_l, ghost_moved])
        neigh_moved = jax.ops.segment_max(
            moved_tab[dstloc_c], seg, num_segments=n_loc
        )
        # barred rows (scatter engine) stay active for the re-salted
        # slots next round — same retention rule as the shm kernel
        new_active_l = (
            ((moved_l | neigh_moved) > 0)
            | (wants & ~accept_l)
            | (barred_l & active_l)
        )
    else:
        new_active_l = jnp.ones_like(active_l)

    account_collective("psum(convergence)", 4, shape=())
    num_wanting = lax.psum(jnp.sum(wants.astype(jnp.int32)), NODE_AXIS)
    return new_labels_l, new_ghost_lab, new_weights, new_active_l, num_wanting


def _dist_lp_loop(
    mesh: Mesh,
    graph: DistGraph,
    labels0: jax.Array,
    weights0: jax.Array,
    cap: jax.Array,
    seed: jax.Array,
    cfg: LPConfig,
    iters: int,
    movable: Optional[jax.Array] = None,
    record: bool = False,
):
    """shard_map'd multi-round loop; returns replicated labels [n_pad]
    (plus a replicated progress buffer when `record`).

    `movable` (replicated bool[n_pad], optional) freezes nodes where False
    — used by the HEM+LP hybrid to pin matched pairs.

    `record` threads a per-round progress buffer through the carry
    (telemetry/progress.py).  The recorded stat — globally-wanting
    movers — is the already-psum'd convergence scalar, so the
    instrumented trace adds NO collectives; the buffer is replicated and
    rides the existing exit gather's launch.  False (the default) keeps
    the jaxpr identical to the uninstrumented loop."""
    if movable is None:
        movable = jnp.ones(graph.n_pad, dtype=bool)
    g_loc = graph.g_loc

    def per_device(src_l, dst_l, dstloc_l, ew_l, nw_l, n, ghost_gid_l,
                   send_idx_l, recv_map_l, labels0, weights0, cap, seed,
                   movable):
        n_loc = nw_l.shape[0]
        d = lax.axis_index(NODE_AXIS)
        offset = (d * n_loc).astype(jnp.int32)
        movable_l = lax.dynamic_slice(movable, (offset,), (n_loc,))
        # owner-sharded label state: owned slice + initial halo pull of
        # the ghosts' labels (labels0 is replicated only HERE, at entry)
        labels_l0 = lax.dynamic_slice(labels0, (offset,), (n_loc,))
        ghost_lab0 = labels0[jnp.clip(ghost_gid_l, 0, labels0.shape[0] - 1)]
        stats0 = progress_mod.new_buffer(iters, 1) if record else None

        def cond(state):
            i, _, _, _, _, moved, _ = state
            return (i < iters) & (moved != 0)

        def body(state):
            i, labels_l, ghost_lab, weights, active_l, _, stats = state
            salt = (seed.astype(jnp.int32) * 131071 + i * 1566083941) & 0x7FFFFFFF
            labels_l, ghost_lab, weights, active_l, moved = _dist_lp_round(
                src_l, dst_l, dstloc_l, ew_l, nw_l, n, labels_l, ghost_lab,
                send_idx_l, recv_map_l, weights, cap, active_l, movable_l,
                salt, cfg,
            )
            if stats is not None:  # trace-time guard (None adds no carry)
                stats = progress_mod.record(stats, i, moved)
            return (i + 1, labels_l, ghost_lab, weights, active_l, moved,
                    stats)

        active0 = jnp.ones(n_loc, dtype=bool)
        init = (
            jnp.int32(0), labels_l0, ghost_lab0, weights0, active0,
            jnp.int32(1), stats0,
        )
        _, labels_l, _, _, _, _, stats = lax.while_loop(cond, body, init)
        # ONE O(n) gather at loop exit — the per-round collectives above
        # are all O(interface)
        from .mesh import account_collective

        account_collective(
            "all_gather(labels)", labels_l.size * 4, shape=labels_l.shape
        )
        gathered = lax.all_gather(labels_l, NODE_AXIS, tiled=True)
        if stats is None:
            return gathered
        return gathered, stats

    mapped = _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
            P(NODE_AXIS), P(), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
            P(), P(), P(), P(), P(),
        ),
        out_specs=(P(), P()) if record else P(),
        check_vma=False,
    )
    return mapped(
        graph.src, graph.dst, graph.dst_local, graph.edge_w, graph.node_w,
        graph.n, graph.ghost_gid, graph.send_idx, graph.recv_map,
        labels0, weights0, cap, seed, movable,
    )


@partial(jax.jit, static_argnames=("mesh", "cfg", "num_iterations", "record"))
def _dist_lp_cluster_impl(mesh, graph, max_cluster_weight, seed, cfg,
                          num_iterations, record=False):
    n_pad = graph.n_pad
    labels0 = jnp.arange(n_pad, dtype=jnp.int32)
    weights0 = graph.node_w.astype(ACC_DTYPE)  # cluster c starts = node c
    cap = jnp.broadcast_to(
        jnp.asarray(max_cluster_weight, ACC_DTYPE), (n_pad,)
    )
    iters = num_iterations if num_iterations is not None else cfg.num_iterations
    return _dist_lp_loop(mesh, graph, labels0, weights0, cap, seed, cfg,
                         iters, record=record)


def dist_lp_cluster(
    graph: DistGraph,
    max_cluster_weight,
    seed,
    cfg: LPConfig = LPConfig(),
    num_iterations: Optional[int] = None,
) -> jax.Array:
    """Distributed size-constrained LP clustering (GlobalLPClusteringImpl
    analog, global_lp_clusterer.cc:54-594).  Returns i32[n_pad] cluster
    labels, replicated.  The singleton post-passes (two-hop /
    isolated-node clustering) run host-side on the replicated result —
    see dist_singleton_postpasses (the dist driver applies them per
    level)."""
    return progress_mod.instrumented(
        lambda rec: _dist_lp_cluster_impl(
            graph.src.sharding.mesh, graph,
            jnp.asarray(max_cluster_weight), jnp.asarray(seed), cfg,
            num_iterations, record=rec,
        ),
        "dist-lp", ("moved",), phase="cluster",
    )


@partial(jax.jit, static_argnames=("mesh", "cfg", "num_iterations", "record"))
def _dist_lp_cluster_from_impl(mesh, graph, labels0, movable,
                               max_cluster_weight, seed, cfg,
                               num_iterations, record=False):
    n_pad = graph.n_pad
    labels0 = jnp.asarray(labels0, jnp.int32)
    weights0 = jax.ops.segment_sum(
        graph.node_w.astype(ACC_DTYPE),
        jnp.clip(labels0, 0, n_pad - 1),
        num_segments=n_pad,
    )
    cap = jnp.broadcast_to(
        jnp.asarray(max_cluster_weight, ACC_DTYPE), (n_pad,)
    )
    iters = num_iterations if num_iterations is not None else cfg.num_iterations
    return _dist_lp_loop(
        mesh, graph, labels0, weights0, cap, seed, cfg, iters,
        movable=movable, record=record,
    )


def dist_lp_cluster_from(
    graph: DistGraph,
    labels0: jax.Array,
    movable: jax.Array,
    max_cluster_weight,
    seed,
    cfg: LPConfig = LPConfig(),
    num_iterations: Optional[int] = None,
) -> jax.Array:
    """LP clustering from a given initial clustering with frozen nodes
    (`movable == False`).  Used by the HEM+LP hybrid clusterer."""
    return progress_mod.instrumented(
        lambda rec: _dist_lp_cluster_from_impl(
            graph.src.sharding.mesh, graph, labels0, movable,
            jnp.asarray(max_cluster_weight), jnp.asarray(seed), cfg,
            num_iterations, record=rec,
        ),
        "dist-lp", ("moved",), phase="cluster-from",
    )


@partial(jax.jit,
         static_argnames=("mesh", "k", "cfg", "num_iterations", "record"))
def _dist_lp_refine_impl(mesh, graph, partition, k, max_block_weights, seed,
                         cfg, num_iterations, record=False):
    part0 = jnp.clip(partition, 0, k - 1).astype(jnp.int32)
    # replicated block weights via one psum'd local segment-sum
    def local_bw(nw_l, part):
        d = lax.axis_index(NODE_AXIS)
        n_loc = nw_l.shape[0]
        offset = (d * n_loc).astype(jnp.int32)
        part_l = lax.dynamic_slice(part, (offset,), (n_loc,))
        bw = jax.ops.segment_sum(
            nw_l.astype(ACC_DTYPE), part_l, num_segments=k
        )
        return lax.psum(bw, NODE_AXIS)

    bw0 = _shard_map(
        local_bw,
        mesh=mesh,
        in_specs=(P(NODE_AXIS), P()),
        out_specs=P(),
        check_vma=False,
    )(graph.node_w, part0)
    cap = jnp.asarray(max_block_weights, ACC_DTYPE)
    iters = num_iterations if num_iterations is not None else cfg.num_iterations
    return _dist_lp_loop(mesh, graph, part0, bw0, cap, seed, cfg, iters,
                         record=record)


def dist_lp_refine(
    graph: DistGraph,
    partition: jax.Array,
    k: int,
    max_block_weights,
    seed,
    cfg: LPConfig = LPConfig(refinement=True),
    num_iterations: Optional[int] = None,
) -> jax.Array:
    """Distributed LP refinement (the batched LP refiner analog,
    kaminpar-dist/refinement/lp/lp_refiner.cc): blocks fixed to k, moves
    need strictly positive gain under per-block max weights."""
    if not cfg.refinement:
        cfg = dataclasses.replace(cfg, refinement=True, allow_tie_moves=False)
    return progress_mod.instrumented(
        lambda rec: _dist_lp_refine_impl(
            graph.src.sharding.mesh, graph, partition, k,
            jnp.asarray(max_block_weights), jnp.asarray(seed), cfg,
            num_iterations, record=rec,
        ),
        "dist-lp", ("moved",), phase="refine",
    )


def dist_singleton_postpasses(
    host_graph,
    labels,
    max_cluster_weight: int,
    threshold: float = 0.5,
    materialize=None,
):
    """Two-hop + isolated-node post-passes for the DIST clustering path
    (label_propagation.h:872-1191 — the reference runs them wherever LP
    clusters, including the distributed clusterer).  Low-degree graphs
    under-coarsen on the mesh without them.

    Operates on the replicated label array the dist clusterer returns,
    host-side — the dist driver already holds the host graph to re-shard
    each level, so this is one more O(m) numpy pass, not a new
    device<->host round trip.  Mirrors the single-chip semantics: only
    fires when the singleton fraction exceeds `threshold`
    (lp_clusterer.cc two-hop gate); singletons sharing a FAVORED cluster
    merge into weight-capped bins; isolated nodes pack into weight-capped
    bins.  Bin membership is exact for arbitrary node weights: within
    each quotient bin a capacity-respecting prefix accepts members until
    the cap, and rejected (straddling) nodes stay singleton — the same
    exactness rule as the device pass (ops/lp.cluster_isolated_nodes).
    Returns the updated labels (modified copy).

    `host_graph` may be a still-compressed graph (it is only asked for
    n / node weights before the early-out); `materialize`, when given,
    supplies the plain-CSR graph lazily the first time the passes
    actually fire — the compressed dist ingestion path
    (dist_partitioner) uses this so a non-firing level never decodes.

    `labels` may be the device array straight off the clusterer: this
    function owns the device->host pull (the staged host boundary), so
    callers inside timed spans never carry a bare np.asarray.
    """
    import numpy as np

    cap = max(int(max_cluster_weight), 1)
    n = host_graph.n
    lab = np.asarray(labels[:n], dtype=np.int64).copy()
    node_w = host_graph.node_weight_array().astype(np.int64)
    sizes = np.bincount(lab, minlength=n)
    is_singleton = (lab == np.arange(n)) & (sizes[np.arange(n)] == 1)
    if is_singleton.sum() < threshold * n:
        out = np.asarray(labels).copy()
        out[:n] = lab
        return out
    if materialize is not None:
        host_graph = materialize()
    elif not hasattr(host_graph, "edge_sources"):
        # still-compressed graph with no materializer and the threshold
        # fired: decode once — the passes below walk plain CSR arrays
        host_graph = host_graph.decode()

    def _bin_merge(ids: np.ndarray, group: np.ndarray) -> None:
        """Merge `ids` (each currently singleton) into weight-capped bins
        WITHIN each `group` value: sub-bin by cumulative-weight quotient,
        then accept a capacity-respecting prefix per (group, sub-bin);
        the first accepted member leads, straddlers stay singleton."""
        if len(ids) == 0:
            return
        order = np.lexsort((ids, group))
        ids_s, grp_s = ids[order], group[order]
        w = node_w[ids_s]
        csum = np.cumsum(w)
        firstg = np.ones(len(ids_s), dtype=bool)
        firstg[1:] = grp_s[1:] != grp_s[:-1]
        base = np.where(firstg, csum - w, 0)
        np.maximum.accumulate(base, out=base)
        within = csum - base  # cumulative weight inside the group
        sub = (within - w) // cap  # quotient sub-bins
        # prefix-accept inside each (group, sub-bin): reject straddlers
        firstb = firstg | np.concatenate([[True], sub[1:] != sub[:-1]])
        base_b = np.where(firstb, csum - w, 0)
        np.maximum.accumulate(base_b, out=base_b)
        within_b = csum - base_b
        ok = within_b <= cap
        # leader: first ACCEPTED member of each (group, sub-bin)
        idx = np.arange(len(ids_s))
        lead = np.where(firstb & ok, idx, -1)
        np.maximum.accumulate(lead, out=lead)
        do = ok & (lead >= 0)
        lead_ids = ids_s[np.clip(lead, 0, len(ids_s) - 1)]
        do &= lead_ids != ids_s
        # reject members whose sub-bin leader was itself rejected: a
        # leader slot is valid only if its own `ok` holds (firstb & ok
        # produced it, so it does by construction)
        lab[ids_s[do]] = lab[lead_ids[do]]

    deg = host_graph.degrees()
    # --- isolated nodes: pack into one global sequence of bins ----------
    iso_ids = np.flatnonzero(is_singleton & (deg == 0))
    _bin_merge(iso_ids, np.zeros(len(iso_ids), dtype=np.int64))

    # --- two-hop: singletons grouped by FAVORED cluster -----------------
    sing_ids = np.flatnonzero(is_singleton & (deg > 0))
    if len(sing_ids):
        src = host_graph.edge_sources()
        ew = host_graph.edge_weight_array().astype(np.int64)
        sing_mask = np.zeros(n, dtype=bool)
        sing_mask[sing_ids] = True
        keep = sing_mask[src]
        s, c, w = src[keep], lab[host_graph.adjncy[keep]], ew[keep]
        # favored cluster per singleton: argmax summed connection
        key = s.astype(np.int64) * n + c
        order = np.argsort(key, kind="stable")
        key_s, s_s, c_s, w_s = key[order], s[order], c[order], w[order]
        if len(key_s):
            new_grp = np.empty(len(key_s), dtype=bool)
            new_grp[0] = True
            new_grp[1:] = key_s[1:] != key_s[:-1]
            gid = np.cumsum(new_grp) - 1
            g_w = np.bincount(gid, weights=w_s).astype(np.int64)
            g_s = s_s[new_grp]
            g_c = c_s[new_grp]
            order2 = np.lexsort((g_w, g_s))
            gs2 = g_s[order2]
            last = np.empty(len(gs2), dtype=bool)
            last[:-1] = gs2[:-1] != gs2[1:]
            last[-1] = True
            src_of_max = gs2[last]
            fav_of_max = g_c[order2][last]
            fav = fav_of_max[np.searchsorted(src_of_max, sing_ids)]
            _bin_merge(sing_ids, fav)

    out = np.asarray(labels).copy()
    out[:n] = lab
    return out
