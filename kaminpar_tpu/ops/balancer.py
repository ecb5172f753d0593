"""Overload / underload balancers on device.

Analog of kaminpar-shm/refinement/balancer/:
  * OverloadBalancer (overload_balancer.h:25): the reference keeps one
    priority queue per overloaded block, ordered by *relative gain*
    (relative_gain.h: gain > 0 ? gain * weight : gain / weight) and pops
    until the block is feasible.  The TPU version is bulk-synchronous
    rounds: for every node of an overloaded block compute its best feasible
    target block, rank movers per source block by relative gain, accept
    per-source prefixes that cover the overload and per-target prefixes
    that fit the headroom (both via sorted prefix sums).
  * UnderloadBalancer: symmetric — pull weight into blocks below their min
    weight from neighboring blocks.

The device loop makes fast progress but may stall on adversarial instances
(e.g. when all movers of an overloaded block are individually too heavy for
every target); partitioning/refiner.py falls back to the exact host balancer
(`host_balance`) to provide the reference's strict balance guarantee
(README.MD:18).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..graphs.csr import DeviceGraph
from ..telemetry import progress as progress_mod
from .segments import (
    ACC_DTYPE,
    INT32_MIN,
    accept_prefix_by_capacity,
    aggregate_by_key,
    argmax_per_segment,
    best_from_dense,
    connection_to_label,
    count_conn_engine,
    csr_block_ratings,
)

# Above this k a dense (n, k) rating table is shape-infeasible (the
# reference's large-k regime, sparse/compact gain caches —
# kaminpar-shm/refinement/gains/compact_hashing_gain_cache.h:34); the
# balancer rates via edge aggregation instead.
BALANCER_DENSE_MAX_K = 256


def relative_gain_key(gain: jax.Array, weight: jax.Array) -> jax.Array:
    """Sortable surrogate for compute_relative_gain (relative_gain.h):
    gain>0 -> gain*weight, else gain/weight.  Returned as a float32 to be
    used as a *descending* priority."""
    w = jnp.maximum(weight.astype(jnp.float32), 1.0)
    g = gain.astype(jnp.float32)
    return jnp.where(g > 0, g * w, g / w)


def _block_weights(graph: DeviceGraph, partition: jax.Array, k: int) -> jax.Array:
    return jax.ops.segment_sum(
        graph.node_w.astype(ACC_DTYPE),
        jnp.clip(partition, 0, k - 1),
        num_segments=k,
    )


def overload_balance_round(
    graph: DeviceGraph,
    partition: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    salt: jax.Array,
    conn: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """One bulk-synchronous balancing round; returns (partition, moved).

    `conn` is an optional PRE-BUILT dense (n, k) connection table for
    `partition` (the Jet refiner maintains one incrementally); when given,
    the round does NO edge-wide work at all — rating, commit, and weight
    arithmetic are all O(n*k)/O(n)."""
    n_pad = graph.n_pad
    node_ids = jnp.arange(n_pad, dtype=jnp.int32)
    is_real = node_ids < graph.n
    part = jnp.clip(partition, 0, k - 1).astype(jnp.int32)
    bw = _block_weights(graph, part, k)
    cap = max_block_weights.astype(ACC_DTYPE)
    overload = jnp.maximum(bw - cap, 0)
    headroom = jnp.maximum(cap - bw, 0)

    in_overloaded = (overload[part] > 0) & is_real

    # best feasible target per node: highest-connection non-overloaded block
    # with room for the node.  Small k: dense (n, k) rating (one
    # segment_sum, no sort).  Large k: the dense table is
    # shape-infeasible — rate by edge aggregation (sort-based, the
    # compact-gain-cache regime).
    if k <= BALANCER_DENSE_MAX_K:
        if conn is None:
            conn = csr_block_ratings(graph, part, k)
        best, best_w, w_own = best_from_dense(
            conn, part, bw, graph.node_w, cap, salt
        )
    else:
        neigh_block = part[graph.dst]
        seg_g, key_g, w_g = aggregate_by_key(graph.src, neigh_block, graph.edge_w)
        key_c = jnp.clip(key_g, 0, k - 1)
        seg_c = jnp.clip(seg_g, 0, n_pad - 1)
        fits = (
            bw[key_c] + graph.node_w[seg_c].astype(ACC_DTYPE) <= cap[key_c]
        )
        feasible = (seg_g >= 0) & (key_g != part[seg_c]) & fits
        best, best_w = argmax_per_segment(
            seg_g, key_g, w_g, n_pad, tie_salt=salt, feasible=feasible
        )
        w_own = connection_to_label(seg_g, key_g, w_g, part, n_pad)
        # zero-connection escape (the dense table rates every block; the
        # edge aggregation only rates ADJACENT ones): movers with no
        # feasible neighbor block go to the max-headroom block if they fit
        headroom_now = jnp.maximum(cap - bw, 0)
        fallback = jnp.argmax(headroom_now).astype(jnp.int32)
        fb_ok = (
            graph.node_w.astype(ACC_DTYPE) <= headroom_now[fallback]
        ) & (part != fallback)
        use_fb = (best < 0) & fb_ok
        best = jnp.where(use_fb, fallback, best)
        best_w = jnp.where(use_fb, 0, best_w)

    # (no separate fallback needed: the dense table rates every fitting
    # block, including zero-connection ones, so best < 0 already means no
    # block can take the node)
    target = best
    gain = best_w - w_own

    mover = in_overloaded & (target >= 0)
    target = jnp.where(mover, target, -1)

    # per-source-block: accept movers by descending relative gain until the
    # overload is covered.  Encode descending order as ascending int key.
    rel = relative_gain_key(gain, graph.node_w)
    order_key = -rel  # float32; ascending sort = best relative gain first
    src_block = jnp.where(mover, part, -1)
    accept_out = accept_prefix_by_capacity(
        src_block, order_key, graph.node_w, overload, reach=True
    )

    # per-target-block: STRICT headroom admission — a previously feasible
    # block must never become overloaded by incoming movers
    target2 = jnp.where(accept_out, target, -1)
    accept_in = accept_prefix_by_capacity(
        target2, order_key, graph.node_w, headroom
    )
    accept = accept_out & accept_in

    new_part = jnp.where(accept, target, part)
    # moved-node count <= n, ID domain  # tpulint: disable=R3
    return new_part, jnp.sum(accept, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("k", "max_rounds"))
def _overload_balance_impl(
    graph: DeviceGraph,
    partition: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    seed: jax.Array,
    max_rounds: int = 8,
    stats=None,
):
    """Balancing rounds until feasible or stalled (OverloadBalancer::
    balance analog).  `stats` is an optional progress buffer (see
    telemetry/progress.py); None keeps the jaxpr identical to the
    uninstrumented loop.  The record variant carries the violation mass
    so the series costs no extra reduction: the body computes it once
    per round and the loop condition reuses the carried scalar."""

    def _violation(part):
        bw = _block_weights(graph, part, k)
        return jnp.sum(
            jnp.maximum(bw - max_block_weights.astype(ACC_DTYPE), 0)
        )

    def _round(i, part):
        salt = (seed.astype(jnp.int32) * 48271 + i * 1566083941) & 0x7FFFFFFF
        return overload_balance_round(
            graph, part, k, max_block_weights, salt
        )

    part0 = jnp.clip(partition, 0, k - 1)
    if stats is None:
        def cond(state):
            i, part, moved = state
            return (i < max_rounds) & (_violation(part) > 0) & (moved != 0)

        def body(state):
            i, part, _ = state
            part, moved = _round(i, part)
            return (i + 1, part, moved)

        _, part, _ = lax.while_loop(
            cond, body, (jnp.int32(0), part0, jnp.int32(1))
        )
        return part

    def cond(state):
        i, part, moved, stats, over = state
        return (i < max_rounds) & (over > 0) & (moved != 0)

    def body(state):
        i, part, _, stats, _ = state
        part, moved = _round(i, part)
        over = _violation(part)
        stats = progress_mod.record(stats, i, moved, over)
        return (i + 1, part, moved, stats, over)

    _, part, _, stats, _ = lax.while_loop(
        cond, body,
        (jnp.int32(0), part0, jnp.int32(1), stats, _violation(part0)),
    )
    return part, stats


def overload_balance(
    graph: DeviceGraph,
    partition: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    seed: jax.Array,
    max_rounds: int = 8,
) -> jax.Array:
    """Public entry: runs the fused loop, emitting a per-round progress
    series (moved nodes, residual violation mass) when telemetry is on."""
    if k <= BALANCER_DENSE_MAX_K:
        count_conn_engine(graph, k)
    return progress_mod.instrumented(
        lambda stats: _overload_balance_impl(
            graph, partition, k, max_block_weights, seed, max_rounds, stats
        ),
        "balancer", ("moved", "violation"), rows=max_rounds,
        direction="overload",
    )


@partial(jax.jit, static_argnames=("k", "max_rounds"))
def _underload_balance_impl(
    graph: DeviceGraph,
    partition: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    min_block_weights: jax.Array,
    seed: jax.Array,
    max_rounds: int = 8,
    stats=None,
):
    """UnderloadBalancer analog: pull weight into blocks below their min
    weight, taking the cheapest movers from blocks with surplus
    (weight > min).  `stats`: optional progress buffer; the record
    variant carries the deficit mass like _overload_balance_impl."""

    def _deficit_mass(part):
        bw = _block_weights(graph, part, k)
        return jnp.sum(
            jnp.maximum(min_block_weights.astype(ACC_DTYPE) - bw, 0)
        )

    def _round(i, part):
        salt = (seed.astype(jnp.int32) * 16807 + i * 1566083941) & 0x7FFFFFFF
        n_pad = graph.n_pad
        node_ids = jnp.arange(n_pad, dtype=jnp.int32)
        is_real = node_ids < graph.n
        bw = _block_weights(graph, part, k)
        deficit = jnp.maximum(min_block_weights.astype(ACC_DTYPE) - bw, 0)
        surplus = jnp.maximum(bw - min_block_weights.astype(ACC_DTYPE), 0)

        # candidates: nodes in surplus blocks adjacent to a deficit block
        # (dense rating restricted to deficit columns; large k rates by
        # edge aggregation — see BALANCER_DENSE_MAX_K)
        if k <= BALANCER_DENSE_MAX_K:
            conn = csr_block_ratings(graph, part, k)
            best, best_w, _ = best_from_dense(
                conn, part, bw, graph.node_w, bw, salt,
                require_fit=False, allowed=deficit > 0,
            )
        else:
            neigh_block = part[graph.dst]
            seg_g, key_g, w_g = aggregate_by_key(
                graph.src, neigh_block, graph.edge_w
            )
            key_c = jnp.clip(key_g, 0, k - 1)
            seg_c = jnp.clip(seg_g, 0, n_pad - 1)
            feasible = (
                (seg_g >= 0)
                & (key_g != part[seg_c])
                & (deficit[key_c] > 0)
            )
            best, best_w = argmax_per_segment(
                seg_g, key_g, w_g, n_pad, tie_salt=salt, feasible=feasible
            )
        # fallback for deficit blocks with no adjacent candidates (e.g. an
        # empty block): pull arbitrary nodes into the most-deficient block
        fallback = jnp.argmax(deficit).astype(jnp.int32)
        use_fallback = (best < 0) & (deficit[fallback] > 0) & (part != fallback)
        best = jnp.where(use_fallback, fallback, best)
        best_w = jnp.where(use_fallback, 0, best_w)
        mover = (
            is_real
            & (best >= 0)
            & (surplus[part] >= graph.node_w.astype(ACC_DTYPE))
        )
        target = jnp.where(mover, best, -1)
        rel = relative_gain_key(best_w, graph.node_w)
        order_key = -rel
        # take out no more than the surplus, put in no more than the deficit
        accept_out = accept_prefix_by_capacity(
            jnp.where(mover, part, -1), order_key, graph.node_w, surplus
        )
        target2 = jnp.where(accept_out, target, -1)
        accept_in = accept_prefix_by_capacity(
            target2, order_key, graph.node_w, deficit, reach=True
        )
        accept = accept_out & accept_in
        new_part = jnp.where(accept, target, part)
        # moved-node count <= n, ID domain  # tpulint: disable=R3
        return new_part, jnp.sum(accept, dtype=jnp.int32)

    part0 = jnp.clip(partition, 0, k - 1)
    if stats is None:
        def cond(state):
            i, part, moved = state
            return (
                (i < max_rounds) & (_deficit_mass(part) > 0) & (moved != 0)
            )

        def body(state):
            i, part, _ = state
            part, moved = _round(i, part)
            return (i + 1, part, moved)

        _, part, _ = lax.while_loop(
            cond, body, (jnp.int32(0), part0, jnp.int32(1))
        )
        return part

    def cond(state):
        i, part, moved, stats, deficit = state
        return (i < max_rounds) & (deficit > 0) & (moved != 0)

    def body(state):
        i, part, _, stats, _ = state
        part, moved = _round(i, part)
        deficit = _deficit_mass(part)
        stats = progress_mod.record(stats, i, moved, deficit)
        return (i + 1, part, moved, stats, deficit)

    _, part, _, stats, _ = lax.while_loop(
        cond, body,
        (jnp.int32(0), part0, jnp.int32(1), stats, _deficit_mass(part0)),
    )
    return part, stats


def underload_balance(
    graph: DeviceGraph,
    partition: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    min_block_weights: jax.Array,
    seed: jax.Array,
    max_rounds: int = 8,
) -> jax.Array:
    """Public entry (see overload_balance): per-round moved nodes and
    residual deficit mass land on the progress stream when telemetry is
    enabled."""
    if k <= BALANCER_DENSE_MAX_K:
        count_conn_engine(graph, k)
    return progress_mod.instrumented(
        lambda stats: _underload_balance_impl(
            graph, partition, k, max_block_weights, min_block_weights,
            seed, max_rounds, stats,
        ),
        "balancer", ("moved", "violation"), rows=max_rounds,
        direction="underload",
    )


def host_balance(
    node_w: np.ndarray,
    adjacency: Tuple[np.ndarray, np.ndarray, np.ndarray],
    partition: np.ndarray,
    max_block_weights: np.ndarray,
) -> np.ndarray:
    """Exact greedy host balancer — the strict-balance guarantee backstop
    (README.MD:18).  Moves the relatively-cheapest nodes out of overloaded
    blocks one at a time until feasible; always terminates feasible when
    sum(node weights) <= sum(max block weights) and node weights fit."""
    xadj, adjncy, edge_w = adjacency
    part = partition.copy()
    n = len(part)
    k = len(max_block_weights)
    bw = np.zeros(k, dtype=np.int64)
    np.add.at(bw, part, node_w)

    # internal connection weight per node: cut damage of moving it away
    src = np.repeat(np.arange(n), np.diff(xadj))
    internal = np.zeros(n, dtype=np.int64)
    same = part[src] == part[adjncy]
    np.add.at(internal, src[same], edge_w[same])

    # movers ordered by (internal connection, weight): cheapest cut damage
    # first, light nodes first
    order = np.lexsort((node_w, internal))
    for _ in range(n * 2):
        over_blocks = np.flatnonzero(bw > max_block_weights)
        if len(over_blocks) == 0:
            break
        b = int(
            over_blocks[np.argmax(bw[over_blocks] - max_block_weights[over_blocks])]
        )
        movers = order[part[order] == b]
        moved = False
        for u in movers:
            # best target with room: max connection among roomy blocks
            room = max_block_weights - bw
            room[b] = -1
            lo, hi = int(xadj[u]), int(xadj[u + 1])
            conn = np.zeros(k, dtype=np.int64)
            np.add.at(conn, part[adjncy[lo:hi]], edge_w[lo:hi])
            conn[room < node_w[u]] = -1
            conn[b] = -1
            t = int(np.argmax(conn))
            if conn[t] < 0:  # no adjacent roomy block: any roomy block
                t = int(np.argmax(room))
                if room[t] < node_w[u]:
                    continue
            part[u] = t
            bw[b] -= node_w[u]
            bw[t] += node_w[u]
            moved = True
            break
        if not moved:
            break
    return part
