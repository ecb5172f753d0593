"""Distributed Jet refinement over the device mesh.

Analog of the reference's distributed Jet refiner
(kaminpar-dist/refinement/jet/jet_refiner.cc), which runs the same
find/filter/execute/rebalance scheme as the shared-memory Jet
(see ops/jet.py) with ghost-synchronized block IDs.  Bulk-synchronous Jet
is already the natural fit for SPMD; the partition state is OWNER-SHARDED
(part_l i32[n_loc] + ghost slice i32[g_loc]) and every per-iteration
collective is O(interface) or O(k):

  1. find: candidate moves for owned nodes from the local edge shard +
     ghost block table (local segmented reductions);
  2. filter: the afterburner needs each interface neighbor's (candidate
     gain, destination) — one stacked mesh.halo_exchange, the reference's
     sparse alltoall (graphutils/communication.h:242);
  3. execute: accepted moves apply locally; one halo exchange republishes
     the changed labels to ghosts;
  4. rebalance with the distributed node balancer
     (parallel/dist_balancer.dist_balance_round — top-T candidate gather,
     O(D*T));
  5. best-partition snapshots by the psum'd edge cut, rollback at round
     end (jet_refiner.cc best-partition snapshots).

The one O(n) all_gather runs at loop exit.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

from ..context import JetRefinementContext
from ..telemetry import progress as progress_mod
from ..ops.segments import (
    ACC_DTYPE,
    INT32_MIN,
    aggregate_by_key,
    argmax_per_segment,
    connection_to_label,
)
from .dist_balancer import dist_balance_round
from .dist_graph import DistGraph
from .mesh import account_collective, NODE_AXIS, halo_exchange


def _local_cut(part_l, ghost_part, seg, dstloc_l, ew_l):
    """Global edge cut from the owner-sharded state: each undirected edge
    is stored at both endpoints, so the psum counts every cut edge twice."""
    n_loc = part_l.shape[0]
    tab = jnp.concatenate([part_l, ghost_part])
    own = part_l[jnp.clip(seg, 0, n_loc - 1)]
    nb = tab[jnp.clip(dstloc_l, 0, tab.shape[0] - 1)]
    local = jnp.sum(jnp.where(own != nb, ew_l, 0).astype(ACC_DTYPE))
    account_collective("psum(cut)", local.dtype.itemsize, shape=local.shape)
    return lax.psum(local, NODE_AXIS) // 2


def _jet_iteration_dist(
    src_l, dst_l, dstloc_l, ew_l, nw_l, n, part_l, ghost_part, lock_l,
    k, cap, gain_temp, salt, send_idx_l, recv_map_l,
):
    n_loc = nw_l.shape[0]
    g_loc = ghost_part.shape[0]
    d = lax.axis_index(NODE_AXIS)
    offset = (d * n_loc).astype(jnp.int32)
    node_ids_l = offset + jnp.arange(n_loc, dtype=jnp.int32)
    seg = src_l - offset
    seg_c = jnp.clip(seg, 0, n_loc - 1)
    dstloc_c = jnp.clip(dstloc_l, 0, n_loc + g_loc - 1)
    tab = jnp.concatenate([part_l, ghost_part])
    is_real_l = node_ids_l < n

    # ---- find (jet_refiner.cc:104-131) ----
    neigh_block = tab[dstloc_c]
    seg_g, key_g, w_g = aggregate_by_key(seg, neigh_block, ew_l)
    sgc = jnp.clip(seg_g, 0, n_loc - 1)
    is_ext = (seg_g >= 0) & (key_g != part_l[sgc])
    best, best_conn = argmax_per_segment(
        seg_g, key_g, w_g, n_loc, tie_salt=salt, feasible=is_ext
    )
    conn_own = connection_to_label(seg_g, key_g, w_g, part_l, n_loc)
    gain_l = best_conn - conn_own
    threshold = -jnp.floor(gain_temp * conn_own.astype(jnp.float32)).astype(
        jnp.int32
    )
    candidate_l = is_real_l & (best >= 0) & (lock_l == 0) & (gain_l > threshold)
    next_part_l = jnp.where(candidate_l, best, part_l)

    # ---- filter: afterburner — one stacked halo exchange publishes the
    # interface nodes' (candidate gain, destination) to their ghosts ----
    gain_cand_l = jnp.where(candidate_l, gain_l, INT32_MIN)
    ghost_gain, ghost_next = halo_exchange(
        jnp.stack([gain_cand_l, next_part_l]), send_idx_l, recv_map_l, g_loc
    )
    gain_tab = jnp.concatenate([gain_cand_l, ghost_gain])
    next_tab = jnp.concatenate([next_part_l, ghost_next])

    gain_u = gain_cand_l[seg_c]
    gain_v = gain_tab[dstloc_c]
    v_is_cand = gain_v > INT32_MIN
    # total order across devices: global ids break ties
    v_before_u = v_is_cand & (
        (gain_v > gain_u) | ((gain_v == gain_u) & (dst_l < src_l))
    )
    block_v = jnp.where(v_before_u, next_tab[dstloc_c], tab[dstloc_c])
    to_u = next_part_l[seg_c]
    from_u = part_l[seg_c]
    contrib = jnp.where(
        to_u == block_v, ew_l, jnp.where(from_u == block_v, -ew_l, 0)
    )
    adj_gain = jax.ops.segment_sum(
        jnp.where(candidate_l[seg_c], contrib, 0), seg_c, num_segments=n_loc
    )
    accept_l = candidate_l & (adj_gain > 0)

    # ---- execute ----
    new_part_l = jnp.where(accept_l, next_part_l, part_l)
    new_ghost = halo_exchange(new_part_l, send_idx_l, recv_map_l, g_loc)
    new_lock_l = accept_l.astype(jnp.int32)
    return new_part_l, new_ghost, new_lock_l


@partial(
    jax.jit,
    static_argnames=(
        "mesh", "k", "num_rounds", "max_iterations", "max_fruitless",
        "balancer_rounds", "record",
    ),
)
def _dist_jet_impl(
    mesh, graph, partition, k, cap, seed,
    initial_gain_temp, final_gain_temp, fruitless_threshold,
    num_rounds, max_iterations, max_fruitless, balancer_rounds,
    record=False,
):
    def per_device(src_l, dst_l, dstloc_l, ew_l, nw_l, n, ghost_gid_l,
                   send_idx_l, recv_map_l, part0, cap, seed):
        n_loc = nw_l.shape[0]
        d = lax.axis_index(NODE_AXIS)
        offset = (d * n_loc).astype(jnp.int32)
        seg = src_l - offset
        part_l0 = lax.dynamic_slice(part0, (offset,), (n_loc,))
        ghost0 = part0[jnp.clip(ghost_gid_l, 0, part0.shape[0] - 1)]

        def is_feasible(part_l):
            bw = lax.psum(
                jax.ops.segment_sum(
                    nw_l.astype(ACC_DTYPE),
                    jnp.clip(part_l, 0, k - 1),
                    num_segments=k,
                ),
                NODE_AXIS,
            )
            return jnp.all(bw <= cap)

        # best-partition snapshots track the best FEASIBLE cut; an
        # infeasible input must not pin the snapshot (its cut can be
        # arbitrarily low — e.g. everything in one block cuts nothing)
        best_cut0 = jnp.where(
            is_feasible(part_l0),
            _local_cut(part_l0, ghost0, seg, dstloc_l, ew_l),
            jnp.iinfo(ACC_DTYPE).max,
        )

        def round_body(rnd, carry):
            part_l, ghost, best_l, best_cut, round_stats = carry
            gain_temp = jnp.where(
                num_rounds > 1,
                initial_gain_temp
                + (final_gain_temp - initial_gain_temp)
                * rnd.astype(jnp.float32)
                / jnp.float32(max(num_rounds - 1, 1)),
                initial_gain_temp,
            )

            def iter_cond(state):
                i, fruitless, *_ = state
                return (i < max_iterations) & (fruitless < max_fruitless)

            def iter_body(state):
                (i, fruitless, part_l, ghost, lock_l, best_l, best_cut,
                 stats) = state
                salt = (
                    seed.astype(jnp.int32) * 31321
                    + rnd * 2221
                    + i * 1566083941
                ) & 0x7FFFFFFF
                part_l, ghost, lock_l = _jet_iteration_dist(
                    src_l, dst_l, dstloc_l, ew_l, nw_l, n, part_l, ghost,
                    lock_l, k, cap, gain_temp, salt, send_idx_l, recv_map_l,
                )

                # run the balancer to feasibility (or a dry round), not a
                # fixed count: a round moves at most D*T nodes, so big
                # post-move overloads need batching.  Feasible partitions
                # exit after the first (cheap) overload check.
                def bal_cond(state):
                    j, _, _, moved, still = state
                    return (j < 4 * balancer_rounds) & (moved != 0) & still

                def bal_body(state):
                    j, p, g_, _, _ = state
                    s = (salt + j * 7919) & 0x7FFFFFFF
                    p2, g2, moved, still = dist_balance_round(
                        src_l, dst_l, dstloc_l, ew_l, nw_l, n, p, g_,
                        send_idx_l, recv_map_l, k, cap, s,
                    )
                    return (j + 1, p2, g2, moved, still)

                _, part_l, ghost, _, _ = lax.while_loop(
                    bal_cond, bal_body,
                    (
                        jnp.int32(0), part_l, ghost, jnp.int32(1),
                        ~is_feasible(part_l),
                    ),
                )
                cut = _local_cut(part_l, ghost, seg, dstloc_l, ew_l)
                # sentinel-aware, as in ops/jet.py: until a feasible
                # partition exists, improvement = reaching feasibility
                has_best = best_cut < jnp.iinfo(ACC_DTYPE).max
                improved_enough = jnp.where(
                    has_best,
                    (best_cut - cut).astype(jnp.float32)
                    > (1.0 - fruitless_threshold)
                    * jnp.abs(best_cut).astype(jnp.float32),
                    is_feasible(part_l),
                )
                fruitless = jnp.where(improved_enough, 0, fruitless + 1)
                is_best = (cut <= best_cut) & is_feasible(part_l)
                best_l = jnp.where(is_best, part_l, best_l)
                best_cut = jnp.where(is_best, cut, best_cut)
                if stats is not None:  # trace-time guard (no extra carry)
                    # cut and fruitless are already psum'd/replicated, so
                    # the series adds NO collectives; rows are indexed by
                    # the global iteration across rounds
                    stats = progress_mod.record(
                        stats, rnd * max_iterations + i, cut, fruitless
                    )
                return (
                    i + 1, fruitless, part_l, ghost, lock_l, best_l,
                    best_cut, stats
                )

            lock0 = jnp.zeros(n_loc, dtype=jnp.int32)
            (_, _, part_l, ghost, _, best_l, best_cut,
             round_stats) = lax.while_loop(
                iter_cond,
                iter_body,
                (
                    jnp.int32(0), jnp.int32(0), part_l, ghost, lock0,
                    best_l, best_cut, round_stats,
                ),
            )
            # rollback to best; re-sync ghosts from it
            ghost_best = halo_exchange(best_l, send_idx_l, recv_map_l,
                                       ghost.shape[0])
            return (best_l, ghost_best, best_l, best_cut, round_stats)

        stats0 = (
            progress_mod.new_buffer(num_rounds * max_iterations, 2)
            if record else None
        )
        _, _, best_l, _, stats = lax.fori_loop(
            0, num_rounds, round_body,
            (part_l0, ghost0, part_l0, best_cut0, stats0),
        )
        # ONE O(n) gather at loop exit
        account_collective(
            "all_gather(partition)", best_l.size * 4, shape=best_l.shape
        )
        gathered = lax.all_gather(best_l, NODE_AXIS, tiled=True)
        if stats is None:
            return gathered
        return gathered, stats

    return _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
            P(NODE_AXIS), P(), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
            P(), P(), P(),
        ),
        out_specs=(P(), P()) if record else P(),
        check_vma=False,
    )(
        graph.src, graph.dst, graph.dst_local, graph.edge_w, graph.node_w,
        graph.n, graph.ghost_gid, graph.send_idx, graph.recv_map,
        partition, cap, seed,
    )


def dist_jet_refine(
    graph: DistGraph,
    partition: jax.Array,
    k: int,
    max_block_weights,
    seed,
    ctx: JetRefinementContext | None = None,
    level: int = 0,
    balancer_rounds: int = 4,
) -> jax.Array:
    """Distributed Jet refinement entry point (dist jet_refiner.cc analog);
    temperature schedule picked by level like the shm version."""
    if ctx is None:
        ctx = JetRefinementContext()
    if level > 0:
        rounds = ctx.num_rounds_on_coarse_level
        t0, t1 = (
            ctx.initial_gain_temp_on_coarse_level,
            ctx.final_gain_temp_on_coarse_level,
        )
    else:
        rounds = ctx.num_rounds_on_fine_level
        t0, t1 = (
            ctx.initial_gain_temp_on_fine_level,
            ctx.final_gain_temp_on_fine_level,
        )
    max_iterations = ctx.num_iterations if ctx.num_iterations > 0 else 64
    max_fruitless = (
        ctx.num_fruitless_iterations
        if ctx.num_fruitless_iterations > 0
        else 2**30
    )
    return progress_mod.instrumented(
        lambda rec: _dist_jet_impl(
            graph.src.sharding.mesh,
            graph,
            jnp.clip(jnp.asarray(partition, jnp.int32), 0, k - 1),
            k,
            jnp.asarray(max_block_weights, ACC_DTYPE),
            jnp.asarray(seed),
            jnp.float32(t0),
            jnp.float32(t1),
            jnp.float32(ctx.fruitless_threshold),
            int(rounds),
            int(max_iterations),
            int(max_fruitless),
            int(balancer_rounds),
            record=rec,
        ),
        "dist-jet", ("cut", "fruitless"),
        rounds=int(rounds), iterations_per_round=int(max_iterations),
    )
