"""Distributed colored LP refinement over the device mesh.

Analog of the reference's ColoredLPRefiner
(kaminpar-dist/refinement/lp/clp_refiner.cc): label propagation made
race-free by processing one color class of a greedy node coloring per
superstep — two adjacent nodes are never in the same class, so the gains
computed at the start of a superstep stay exact for every node moved in it
(the reference motivates the design the same way, clp_refiner.cc:1-70).

Per superstep (color c):
  1. nodes of color c rate adjacent blocks from the owner-sharded
     partition state (part_l + ghost slice — local segmented reduction
     over the device's edge shard);
  2. positive-gain moves under the per-block weight caps are selected;
  3. capacity safety across devices uses the same psum'd demand throttle as
     dist_lp (the reference instead commits probabilistically and rolls
     back, clp_refiner.cc `handle_node` + move rollback);
  4. one O(interface) mesh.halo_exchange republishes the changed labels
     to ghosts, one `psum` folds the block-weight deltas — the collective
     form of the reference's ghost-block sync
     (graphutils/synchronization.h:21).  The single O(n) all_gather runs
     at loop exit.

The whole refinement — coloring supersteps x iterations — is one
`shard_map`'d XLA program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

from ..ops.segments import (
    ACC_DTYPE,
    accept_prefix_by_capacity,
    aggregate_by_key,
    argmax_per_segment,
    connection_to_label,
    hash_u32,
    move_weight_delta,
)
from .dist_coloring import dist_greedy_coloring
from .dist_graph import DistGraph
from .mesh import account_collective, NODE_AXIS, halo_exchange, throttled_local_capacity


@partial(jax.jit, static_argnames=("mesh", "k", "num_iterations"))
def _dist_clp_impl(
    mesh,
    graph: DistGraph,
    partition: jax.Array,
    colors: jax.Array,
    num_colors: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    seed: jax.Array,
    num_iterations: int,
):
    def per_device(src_l, dst_l, dstloc_l, ew_l, nw_l, n, ghost_gid_l,
                   send_idx_l, recv_map_l, part0, colors, num_colors,
                   cap, seed):
        n_loc = nw_l.shape[0]
        g_loc = ghost_gid_l.shape[0]
        d = lax.axis_index(NODE_AXIS)
        offset = (d * n_loc).astype(jnp.int32)
        node_ids_l = offset + jnp.arange(n_loc, dtype=jnp.int32)
        seg = src_l - offset
        dstloc_c = jnp.clip(dstloc_l, 0, n_loc + g_loc - 1)
        colors_l = lax.dynamic_slice(colors, (offset,), (n_loc,))
        part_l0 = lax.dynamic_slice(part0, (offset,), (n_loc,))
        ghost0 = part0[jnp.clip(ghost_gid_l, 0, part0.shape[0] - 1)]

        bw0 = lax.psum(
            jax.ops.segment_sum(
                nw_l.astype(ACC_DTYPE),
                jnp.clip(part_l0, 0, k - 1),
                num_segments=k,
            ),
            NODE_AXIS,
        )

        def superstep(part_l, ghost, bw, c, salt):
            eligible = (colors_l == c) & (node_ids_l < n)

            neigh_block = jnp.concatenate([part_l, ghost])[dstloc_c]
            seg_g, key_g, w_g = aggregate_by_key(seg, neigh_block, ew_l)
            key_c = jnp.clip(key_g, 0, k - 1)
            seg_c = jnp.clip(seg_g, 0, n_loc - 1)
            fits = (
                bw[key_c] + nw_l[seg_c].astype(ACC_DTYPE) <= cap[key_c]
            )
            is_current = key_g == part_l[seg_c]
            feasible = (seg_g >= 0) & (is_current | fits)
            best, best_w = argmax_per_segment(
                seg_g, key_g, w_g, n_loc, tie_salt=salt, feasible=feasible
            )
            w_cur = connection_to_label(seg_g, key_g, w_g, part_l, n_loc)
            gain = best_w - w_cur
            wants = eligible & (best >= 0) & (best != part_l) & (gain > 0)
            target_l = jnp.where(wants, best, -1)

            local_cap = throttled_local_capacity(target_l, nw_l, bw, cap)
            prio_l = hash_u32(node_ids_l, salt ^ 0x165667B1)
            accept_l = accept_prefix_by_capacity(
                target_l, prio_l, nw_l, local_cap
            )

            new_part_l = jnp.where(accept_l, target_l, part_l)
            new_ghost = halo_exchange(
                new_part_l, send_idx_l, recv_map_l, g_loc
            )
            delta = lax.psum(
                move_weight_delta(part_l, target_l, accept_l, nw_l, k),
                NODE_AXIS,
            )
            return new_part_l, new_ghost, bw + delta

        def iter_body(i, carry):
            part_l, ghost, bw = carry

            def color_cond_body(state):
                c, part_l, ghost, bw = state
                salt = (
                    seed.astype(jnp.int32) * 48271
                    + i * 16807
                    + c * 1566083941
                ) & 0x7FFFFFFF
                part_l, ghost, bw = superstep(part_l, ghost, bw, c, salt)
                return (c + 1, part_l, ghost, bw)

            _, part_l, ghost, bw = lax.while_loop(
                lambda s: s[0] < num_colors,
                color_cond_body,
                (jnp.int32(0), part_l, ghost, bw),
            )
            return (part_l, ghost, bw)

        part_l, _, _ = lax.fori_loop(
            0, num_iterations, iter_body, (part_l0, ghost0, bw0)
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
            P(), P(), P(), P(), P(),
        ),
        out_specs=P(),
        check_vma=False,
    )(
        graph.src, graph.dst, graph.dst_local, graph.edge_w, graph.node_w,
        graph.n, graph.ghost_gid, graph.send_idx, graph.recv_map,
        partition, colors, num_colors, max_block_weights, seed,
    )


def dist_colored_lp_refine(
    graph: DistGraph,
    partition: jax.Array,
    k: int,
    max_block_weights,
    seed,
    num_iterations: int = 5,
    colors: jax.Array | None = None,
    num_colors: jax.Array | None = None,
) -> jax.Array:
    """Colored LP refinement (ColoredLPRefiner analog).  Computes a greedy
    coloring unless one is supplied, then runs `num_iterations` sweeps over
    the color classes.  Returns the refined partition, replicated."""
    if colors is None or num_colors is None:
        colors, num_colors = dist_greedy_coloring(graph, seed)
    part0 = jnp.clip(jnp.asarray(partition, jnp.int32), 0, k - 1)
    return _dist_clp_impl(
        graph.src.sharding.mesh,
        graph,
        part0,
        colors,
        num_colors,
        k,
        jnp.asarray(max_block_weights, ACC_DTYPE),
        jnp.asarray(seed),
        num_iterations,
    )
