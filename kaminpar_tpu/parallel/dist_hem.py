"""Distributed heavy-edge matching (HEM) clustering over the device mesh.

Analog of the reference's HEMClusterer
(kaminpar-dist/coarsening/clustering/hem/hem_clusterer.h:15): contract
heavy edges by matching each node to its heaviest available neighbor.  The
reference orders nodes with a greedy coloring and matches color classes in
supersteps; the TPU version uses bulk-synchronous *handshake* rounds, the
classic SPMD matching scheme:

  round: every unmatched node proposes to its heaviest unmatched neighbor
  (weight-cap permitting); mutual proposals (u -> v and v -> u) become
  matches, labelled min(u, v).

Handshaking matches at least every locally-heaviest mutual edge per round,
so a few rounds capture most of the matching weight (the reference runs one
pass per color class for the same effect).  `dist_hem_lp_cluster` is the
HEM+LP hybrid (HEMLPClusterer analog): matching first, then LP rounds with
the matched pairs frozen, which lets low-degree leftovers agglomerate.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

from ..ops.lp import LPConfig
from ..ops.segments import (
    ACC_DTYPE,
    aggregate_by_key,
    argmax_per_segment,
)
from .dist_graph import DistGraph
from .mesh import NODE_AXIS, halo_exchange


@partial(jax.jit, static_argnames=("mesh", "num_rounds"))
def _dist_hem_impl(mesh, graph: DistGraph, max_cluster_weight, seed,
                   num_rounds: int):
    def per_device(src_l, dst_l, dstloc_l, ew_l, nw_l, n, ghost_gid_l,
                   send_idx_l, recv_map_l, cap, seed):
        n_loc = nw_l.shape[0]
        g_loc = ghost_gid_l.shape[0]
        d = lax.axis_index(NODE_AXIS)
        offset = (d * n_loc).astype(jnp.int32)
        node_ids_l = offset + jnp.arange(n_loc, dtype=jnp.int32)
        seg = src_l - offset
        seg_c = jnp.clip(seg, 0, n_loc - 1)
        dstloc_c = jnp.clip(dstloc_l, 0, n_loc + g_loc - 1)
        is_real_l = node_ids_l < n
        # static ghost node weights: one exchange at entry
        ghost_nw = halo_exchange(nw_l, send_idx_l, recv_map_l, g_loc)
        nw_tab = jnp.concatenate([nw_l, ghost_nw])

        def round_body(rnd, state):
            labels_l, avail_l, ghost_avail = state
            salt = (seed.astype(jnp.int32) * 69621 + rnd * 7919) & 0x7FFFFFFF
            avail_tab = jnp.concatenate([avail_l, ghost_avail])

            # propose: heaviest available neighbor under the weight cap.
            # Grouping key is the LOCAL slot so the chosen partner's own
            # proposal can be read from the halo table below
            seg_g, key_g, w_g = aggregate_by_key(seg, dstloc_c, ew_l)
            key_c = jnp.clip(key_g, 0, n_loc + g_loc - 1)
            feas_g = (
                (avail_tab[key_c] > 0)
                & (
                    nw_tab[key_c].astype(ACC_DTYPE)
                    + nw_l[jnp.clip(seg_g, 0, n_loc - 1)].astype(ACC_DTYPE)
                    <= cap
                )
                & (seg_g >= 0)
            )
            prop_slot_l, _ = argmax_per_segment(
                seg_g, key_g, w_g, n_loc, tie_salt=salt, feasible=feas_g
            )
            proposes = (avail_l > 0) & is_real_l & (prop_slot_l >= 0)
            slot_c = jnp.clip(prop_slot_l, 0, n_loc + g_loc - 1)
            # the partner's GLOBAL id, from the slot (owned or ghost)
            prop_gid_l = jnp.where(
                proposes,
                jnp.where(
                    prop_slot_l < n_loc,
                    offset + prop_slot_l,
                    ghost_gid_l[jnp.clip(prop_slot_l - n_loc, 0, g_loc - 1)],
                ),
                -1,
            )
            # publish proposals (as global ids) to ghosts, then handshake:
            # mutual proposals match; label both min(u, v)
            ghost_prop = halo_exchange(
                prop_gid_l, send_idx_l, recv_map_l, g_loc
            )
            prop_tab = jnp.concatenate([prop_gid_l, ghost_prop])
            partner_gid = jnp.where(
                proposes & (prop_tab[slot_c] == node_ids_l), prop_gid_l, -1
            )
            matched = partner_gid >= 0
            new_labels_l = jnp.where(
                matched, jnp.minimum(node_ids_l, partner_gid), labels_l
            )
            new_avail_l = jnp.where(matched, 0, avail_l)
            new_ghost_avail = halo_exchange(
                new_avail_l, send_idx_l, recv_map_l, g_loc
            )
            return (new_labels_l, new_avail_l, new_ghost_avail)

        labels0_l = node_ids_l
        avail0_l = is_real_l.astype(jnp.int32)
        ghost_avail0 = halo_exchange(avail0_l, send_idx_l, recv_map_l, g_loc)
        labels_l, _, _ = lax.fori_loop(
            0, num_rounds, round_body, (labels0_l, avail0_l, ghost_avail0)
        )
        # exit-only O(n) gather
        return lax.all_gather(labels_l, NODE_AXIS, tiled=True)

    return _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
            P(NODE_AXIS), P(), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
            P(), P(),
        ),
        out_specs=P(),
        check_vma=False,
    )(
        graph.src, graph.dst, graph.dst_local, graph.edge_w, graph.node_w,
        graph.n, graph.ghost_gid, graph.send_idx, graph.recv_map,
        max_cluster_weight, seed,
    )


def dist_hem_cluster(
    graph: DistGraph,
    max_cluster_weight,
    seed,
    num_rounds: int = 5,
) -> jax.Array:
    """Heavy-edge matching clustering (HEMClusterer analog).  Returns
    i32[n_pad] cluster labels, replicated: matched pairs share min(u, v),
    unmatched nodes stay singletons."""
    return _dist_hem_impl(
        graph.src.sharding.mesh,
        graph,
        jnp.asarray(max_cluster_weight, ACC_DTYPE),
        jnp.asarray(seed),
        num_rounds,
    )


def dist_hem_lp_cluster(
    graph: DistGraph,
    max_cluster_weight,
    seed,
    hem_rounds: int = 5,
    cfg: LPConfig = LPConfig(),
) -> jax.Array:
    """HEM followed by LP with matched pairs frozen (HEMLPClusterer
    analog): matching grabs the heavy edges exactly, LP agglomerates the
    leftovers."""
    from .dist_lp import dist_lp_cluster_from

    labels = dist_hem_cluster(graph, max_cluster_weight, seed,
                              num_rounds=hem_rounds)
    movable = labels == jnp.arange(graph.n_pad, dtype=jnp.int32)
    # leaders that received a partner must stay put as well
    adopted = jnp.zeros(graph.n_pad, dtype=jnp.int32).at[
        jnp.clip(labels, 0, graph.n_pad - 1)
    ].max((~movable).astype(jnp.int32))
    movable = movable & (adopted == 0)
    return dist_lp_cluster_from(
        graph, labels, movable, max_cluster_weight, seed, cfg
    )
