"""Bulk-synchronous size-constrained label propagation on device.

The TPU re-design of the reference's LP engine
(kaminpar-shm/label_propagation.h:83 LabelPropagation<...>).  The reference
runs an *asynchronous* LP: threads sweep shuffled chunks of nodes, rate each
node's adjacent clusters in a per-thread hash map
(find_best_cluster:461-541) and commit moves with CAS cluster-weight updates
(try_node_move:818, move_cluster_weight:2139).  Fine-grained CAS does not
map to TPUs, so this kernel makes the trade the reference's own Jet refiner
makes (refinement/jet/jet_refiner.cc:1-8): *bulk-synchronous rounds* of

  1. rate:    aggregate (node, neighbor-cluster) connection weights via the
              sorted segmented reduction in ops/segments.py;
  2. select:  per-node argmax over feasible clusters (weight cap), hashed
              random tie-breaking — the analog of find_best_cluster;
  3. commit:  capacity-respecting prefix acceptance per target cluster
              (ops/segments.accept_prefix_by_capacity), so the max cluster
              weight is *never* exceeded — stronger than the reference's
              relaxed CAS, which tolerates transient overshoot;
  4. apply:   scatter accepted labels, update cluster weights, refresh the
              active set (the analog of label_propagation.h:507-513).

Oscillation control (label_propagation.h avoids it by construction via
async updates; bulk-sync must handle it explicitly):
  * zero-gain ("tie") moves only follow a per-round hashed direction —
    of two clusters that rate equally, only the one with smaller hash may
    absorb the other, which turns 2-cycles into merges;
  * per-round random participation mask (cfg.participation < 1) — the
    bulk-sync analog of the reference's shuffled chunk scheduling
    (ChunkRandomLabelPropagation:1529), breaking symmetric flip patterns.

Whole multi-round loops run inside one jit via lax.while_loop with a
moved-count convergence test, so a full clustering is a single device
program launch.

Post-passes mirroring the reference:
  * cluster_isolated_nodes (label_propagation.h:872-917)
  * two-hop clustering of leftover singletons by favored cluster
    (label_propagation.h:919-1191)
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..graphs.csr import DeviceGraph
from ..telemetry import progress as progress_mod
from .rating import SCATTER_FALLBACK_FRAC
from .segments import (
    ACC_DTYPE,
    INT32_MIN,
    accept_prefix_by_capacity,
    aggregate_by_key,
    apply_move_weight_delta,
    argmax_per_segment,
    best_from_dense,
    best_from_rating_table,
    connection_to_label,
    connection_to_own_label,
    connection_to_own_rows,
    dense_block_ratings,
    expand_active_rows,
    expand_rows,
    hash_u32,
    hashed_rating_table,
    neighbor_any_true,
    packed_afterburner_gain,
    packed_afterburner_gain_rows,
    rating_top3_by_sort,
    rating_topk_rows,
)


@dataclass(frozen=True)
class LPConfig:
    """Knobs mirroring LabelPropagationConfig (label_propagation.h:36-74)
    plus the bulk-sync-specific ones."""

    num_iterations: int = 5  # lp_clusterer.cc default
    participation: float = 0.5  # per-round node participation probability
    allow_tie_moves: bool = True
    use_active_set: bool = True
    # post-pass toggles (two_hop_strategy / isolated_nodes_strategy enums)
    two_hop: bool = True
    cluster_isolated: bool = True
    # refinement mode: labels are blocks, moves need positive gain
    refinement: bool = False
    # distributed-only: restrict joins to clusters owned by the same device
    # (LocalLPClusterer analog, kaminpar-dist/.../local_lp_clusterer.cc —
    # no cross-PE clusters, so contraction needs no label migration)
    dist_local_only: bool = False
    # rating engine: "auto" delegates to ops/rating.select_engine (dense
    # for refinement-sized label spaces, the scatter-add slot engine
    # when the level's density fits the slot budget, sort2 rows
    # otherwise); "scatter"/"hash"/"sort"/"sort2"/"dense" force one
    rating: str = "auto"
    num_slots: int = 32  # hashed/scatter engine slots per node (per pass)
    # sort2: how many top clusters to read per node (n-sized reads, cheap)
    topk: int = 6
    # scatter engine: fall back to the exact sort rating when more than
    # this fraction of the round's active real nodes stay contested
    # (rationale at rating.SCATTER_FALLBACK_FRAC)
    scatter_fallback: float = SCATTER_FALLBACK_FRAC


def _select_engine(
    cfg: LPConfig,
    num_clusters: int,
    m_pad: int,
    has_communities: bool = False,
    n_pad: int | None = None,
) -> str:
    """Static (trace-time) rating engine choice — delegates to the
    density-adaptive rule in ops/rating.py (see its docstring for the
    selection order).  Inputs are shapes (host ints), so the choice is
    fixed per compiled executable.  The coarsener selects from MEASURED
    per-level density/skew instead and stamps the RESOLVED engine name
    into cfg.rating (never raw floats — cfg is a static jit argument,
    and per-level float stats would retrace every level)."""
    from .rating import select_engine

    engine, _ = select_engine(
        cfg.rating,
        num_clusters,
        n_pad if n_pad is not None else num_clusters,
        m_pad,
        num_slots=cfg.num_slots,
    )
    return engine


# Below this many edge slots a graph's full round is cheap enough that the
# delta machinery (extra programs, an n-wide scatter per round) is not
# worth compiling; shape-bucket floors put small levels at 2^20 anyway.
DELTA_MIN_EDGE_SLOTS = 1 << 22


def _delta_slots(graph: DeviceGraph, cfg: LPConfig, engine: str) -> int | None:
    """Static delta-round buffer size, or None when delta rounds are off.
    m_pad/4 covers active-edge fractions up to 25% at ~40% of a full
    round's cost (the crossover measured on v5e)."""
    if not cfg.use_active_set:
        return None
    if engine not in ("sort2", "dense", "scatter"):
        return None
    m_slots = graph.src.shape[0]
    # the scatter engine's per-round cost is segment-op bound, which
    # shrinks with buffer width immediately — its delta crossover sits
    # far lower than the sort engines' (measured in the round-9 CPU
    # profile; on v5e the sort2 crossover stays where it was).  min()
    # keeps the module-level knob authoritative when tests lower it.
    floor = (
        min(DELTA_MIN_EDGE_SLOTS, 1 << 20)
        if engine == "scatter" else DELTA_MIN_EDGE_SLOTS
    )
    if m_slots < floor:
        return None
    return m_slots // 4


def lp_round(
    graph: DeviceGraph,
    labels: jax.Array,
    cluster_weights: jax.Array,
    max_cluster_weight: jax.Array,
    active: jax.Array,
    salt: jax.Array,
    cfg: LPConfig,
    communities: jax.Array | None = None,
    rows=None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One bulk-synchronous LP round.

    Args:
      labels:            i32[n_pad] cluster id per node (clusters are node
                         ids during coarsening, block ids during refinement)
      cluster_weights:   i32[C] current weight per cluster
      max_cluster_weight:i32 scalar or i32[C] per-cluster cap
      active:            bool[n_pad] active set
      salt:              i32 per-round randomness salt
      rows:              optional expand_active_rows(...) result — a delta
                         round: only the active nodes' rows are rated, and
                         every edge-wide pass shrinks to the row buffer
                         (sort2/dense engines only)

    Returns (new_labels, new_cluster_weights, new_active, num_moved).
    """
    n_pad = graph.n_pad
    m_slots = graph.src.shape[0]
    C = cluster_weights.shape[0]
    cap = jnp.broadcast_to(max_cluster_weight, (C,))
    engine = _select_engine(
        cfg, C, graph.m_pad, communities is not None, n_pad=n_pad
    )
    if rows is not None and engine not in ("sort2", "dense", "scatter"):
        raise ValueError(f"delta rounds are not supported by engine {engine}")

    # nodes the rating engine could not rate exhaustively this round
    # (scatter engine only): they are barred from moving and stay active
    # so the next round's re-salted slots give them another chance
    barred = jnp.zeros(n_pad, dtype=bool)

    # -- shared row view: either the raw CSR edge arrays (full round; src
    # is CSR-sorted so rows are contiguous spans) or the compacted active-
    # row buffer (delta round)
    if engine in ("sort2", "dense", "scatter"):
        if rows is not None:
            owner_c, owner_key, edge_id, valid, start, end = rows
            eid = jnp.clip(edge_id, 0, m_slots - 1)
            dst_b = jnp.where(valid, graph.dst[eid], n_pad - 1)
            w_b = jnp.where(valid, graph.edge_w[eid], 0)
            deg_eff = end - start
        else:
            owner_c = graph.src
            owner_key = graph.src
            dst_b = graph.dst
            w_b = graph.edge_w
            start = graph.row_ptr[:-1]
            end = graph.row_ptr[1:]
            deg_eff = graph.degrees

    # -- rate: per-node best non-own cluster under the weight cap, plus
    # the exact connection to the own cluster.
    if engine == "sort2":
        # top-K rated clusters per row (two buffer-wide sorts, no
        # scatters), then node-level own-exclusion + feasibility +
        # community fallback chain.  The own-cluster connection is EXACT:
        # a streaming masked cumsum over the row spans (one extra gather
        # for the owner's label), replacing the old top-K upper-bound
        # estimate that silently under-moved on huge graphs.
        # On dense coarse levels (hundreds of adjacent clusters, most
        # near the weight cap) a deeper candidate list keeps merges
        # flowing — the reads are n-wide gathers, essentially free.
        avg_degree = graph.m_pad / max(C, 1)
        K = cfg.topk if avg_degree <= 32 else max(cfg.topk, 16)
        nb = jnp.where(valid, labels[dst_b], -1) if rows is not None else (
            labels[dst_b]
        )
        own_slot = labels[owner_c]
        topk = rating_topk_rows(owner_key, nb, w_b, end, deg_eff, salt, K)
        labs = topk[0::2]
        vals = topk[1::2]
        own = labels
        w_cur = connection_to_own_rows(nb, w_b, own_slot, start, end)

        def fits(lab):
            lab_c = jnp.clip(lab, 0, C - 1)
            ok = (lab >= 0) & (
                cluster_weights[lab_c].astype(ACC_DTYPE)
                + graph.node_w.astype(ACC_DTYPE)
                <= cap[lab_c]
            )
            if communities is not None:
                # v-cycle community restriction: a cluster label is a node
                # id, so the cluster's community is the label node's
                lab_n = jnp.clip(lab, 0, n_pad - 1)
                ok = ok & (communities[lab_n] == communities)
            return ok

        best = jnp.full(n_pad, -1, dtype=jnp.int32)
        best_w = jnp.full(n_pad, INT32_MIN, dtype=ACC_DTYPE)
        for lab_j, val_j in zip(reversed(labs), reversed(vals)):
            ok = (lab_j != own) & fits(lab_j)
            best = jnp.where(ok, lab_j, best)
            best_w = jnp.where(ok, val_j, best_w)
    elif engine == "scatter":
        # the one-launch scatter-add engine (ops/rating.py): TWO edge
        # gathers (labels[dst] and the room left in that cluster), then
        # segment-sum slot tables — no edge sort anywhere.  Rows the
        # two elimination passes could not rate exhaustively are barred
        # from moving; when too many rows are barred the whole round's
        # rating falls back to the exact sort engine via lax.cond
        # (collision-safe fallback — only the taken branch executes).
        from .rating import best_from_slots, scatter_slot_ratings

        nb = (
            jnp.where(valid, labels[dst_b], -1)
            if rows is not None
            else labels[dst_b]
        )
        valid_slots = valid if rows is not None else None
        seg_owner = (
            jnp.where(valid, owner_c, -1) if rows is not None else owner_c
        )
        node_ids0 = jnp.arange(n_pad, dtype=jnp.int32)
        is_real0 = node_ids0 < graph.n

        # may the edge's owner join its neighbour's cluster?  Decided
        # HERE, per edge: the cluster side rides one more dst gather of
        # an n-wide column, the owner side streams in CSR order (a
        # delta buffer indexes by its owner column).  The finished
        # table is no place to ask: it has n_pad * 2 * num_slots
        # entries, 4x the edge list at the coarsener's doubled slots,
        # and a gather is charged per index (49-72 ms a round there at
        # (2^16, 2^21) on v5e against 18.7 here; PERF.md, PR 27).  The
        # own label is exempt: its slot holds the row's w_cur.
        if rows is not None:
            def of_owner(values):
                return values[owner_c]
        else:
            def of_owner(values):
                return expand_rows(values, graph.row_ptr, m_slots)

        # n-wide: the room left under the cap in each node's cluster
        room = (cap - cluster_weights.astype(ACC_DTYPE))[
            jnp.clip(labels, 0, C - 1)
        ]
        joinable = of_owner(graph.node_w).astype(ACC_DTYPE) <= room[dst_b]
        if communities is not None:
            # clustering labels are node ids: a cluster's community is
            # its label node's community (same rule as every engine)
            joinable = joinable & (
                communities[jnp.clip(labels, 0, n_pad - 1)][dst_b]
                == of_owner(communities)
            )
        joinable = joinable | (nb == of_owner(labels))

        # the slot tables are built ONCE, outside the cond: the fallback
        # predicate needs fully_rated either way, and the taken branch
        # only reads the (n, 2S) table element-wise (the widest array
        # of the round: nothing irregular is asked of it)
        slot_label, slot_w, fully_rated = scatter_slot_ratings(
            owner_c, nb, w_b, n_pad, cfg.num_slots, salt,
            valid=valid_slots, spans=(start, end), joinable=joinable,
        )

        def scatter_rate(_):
            b, bw, w_own = best_from_slots(slot_label, slot_w, labels, salt)
            return b, bw, w_own, ~fully_rated

        def sort_rate(_):
            seg_g, key_g, w_g = aggregate_by_key(seg_owner, nb, w_b)
            key_c = jnp.clip(key_g, 0, C - 1)
            seg_c = jnp.clip(seg_g, 0, n_pad - 1)
            fits_g = (
                cluster_weights[key_c].astype(ACC_DTYPE)
                + graph.node_w[seg_c].astype(ACC_DTYPE)
                <= cap[key_c]
            )
            feasible = (seg_g >= 0) & (key_g != labels[seg_c]) & fits_g
            if communities is not None:
                key_n = jnp.clip(key_g, 0, n_pad - 1)
                feasible = feasible & (
                    communities[key_n] == communities[seg_c]
                )
            b, bw = argmax_per_segment(
                seg_g, key_g, w_g, n_pad, tie_salt=salt, feasible=feasible
            )
            w_own = connection_to_label(seg_g, key_g, w_g, labels, n_pad)
            return b, bw, w_own, jnp.zeros(n_pad, dtype=bool)

        # fallback predicate on values already in hand: barred fraction
        # of the ACTIVE real nodes (an n-wide reduce, no extra edge op)
        act_real = active & is_real0
        # node counts <= n, ID domain  # tpulint: disable=R3
        n_bar = jnp.sum(act_real & ~fully_rated, dtype=jnp.int32)
        # node counts <= n, ID domain  # tpulint: disable=R3
        n_act = jnp.sum(act_real, dtype=jnp.int32)
        use_scatter = n_bar.astype(jnp.float32) <= (
            jnp.float32(cfg.scatter_fallback) * n_act.astype(jnp.float32)
        )
        best, best_w, w_cur, barred = lax.cond(
            use_scatter, scatter_rate, sort_rate, None
        )
        best = jnp.where(barred, -1, best)
        best_w = jnp.where(barred, INT32_MIN, best_w)
    elif engine == "dense":
        conn = dense_block_ratings(owner_c, dst_b, w_b, labels, n_pad, C)
        best, best_w, w_cur = best_from_dense(
            conn, labels, cluster_weights, graph.node_w, cap, salt,
            communities=communities,
        )
    elif engine == "hash":
        neighbor_cluster = labels[graph.dst]
        slot_label, slot_w = hashed_rating_table(
            graph.src, neighbor_cluster, graph.edge_w, n_pad,
            cfg.num_slots, salt,
        )
        best, best_w = best_from_rating_table(
            slot_label, slot_w, labels, cluster_weights, graph.node_w,
            cap, salt ^ 0x51AB, communities=communities,
        )
        w_cur = connection_to_own_label(
            graph.src, neighbor_cluster, graph.edge_w, labels, n_pad
        )
    else:  # sort (exact enumeration of every adjacent cluster)
        neighbor_cluster = labels[graph.dst]
        seg_g, key_g, w_g = aggregate_by_key(
            graph.src, neighbor_cluster, graph.edge_w
        )
        key_c = jnp.clip(key_g, 0, C - 1)
        seg_c = jnp.clip(seg_g, 0, n_pad - 1)
        fits = (
            cluster_weights[key_c].astype(ACC_DTYPE)
            + graph.node_w[seg_c].astype(ACC_DTYPE)
            <= cap[key_c]
        )
        feasible = (seg_g >= 0) & (key_g != labels[seg_c]) & fits
        if communities is not None:
            # v-cycle community restriction: a cluster label is a node id,
            # so the cluster's community is the label node's community
            feasible = feasible & (communities[key_c] == communities[seg_c])
        best, best_w = argmax_per_segment(
            seg_g, key_g, w_g, n_pad, tie_salt=salt, feasible=feasible
        )
        w_cur = connection_to_label(seg_g, key_g, w_g, labels, n_pad)

    # -- select ----------------------------------------------------------
    gain = best_w - w_cur
    tie_dir_ok = hash_u32(best, salt ^ 0x5BD1) < hash_u32(labels, salt ^ 0x5BD1)
    if cfg.refinement:
        improves = gain > 0
    else:
        improves = (gain > 0) | (
            cfg.allow_tie_moves & (gain == 0) & (best_w > 0) & tie_dir_ok
        )
    node_ids = jnp.arange(n_pad, dtype=jnp.int32)
    participate = hash_u32(node_ids, salt ^ 0x27D4) < jnp.int32(
        cfg.participation * 2147483647.0
    )
    wants = (
        (best >= 0) & (best != labels) & improves & active & (node_ids < graph.n)
    )
    target = jnp.where(wants & participate, best, -1)

    if cfg.refinement:
        # afterburner (Jet's filter step, jet_refiner.cc:133-170): in a
        # bulk-synchronous round, simultaneous moves of ADJACENT nodes can
        # increase the cut even though each individual gain is positive;
        # keep only candidates whose adjusted gain stays positive.  The
        # async reference never needs this (moves see latest labels);
        # without it bulk-sync LP refinement can DOUBLE the cut.
        # `wants` is deliberately NOT masked: filtered/unsampled nodes
        # must stay in the convergence count and the active set.
        # Row-packed (n, 3) tables keep this at TWO edge-wide gathers
        # with EXACT gains (the naive six per-endpoint scalar gathers
        # were ~10x a Jet iteration at equal shape; gathers are charged
        # per index, so the 3-wide rows ride along free).
        candidate = target >= 0
        next_lab = jnp.where(candidate, target, labels)
        if rows is not None:
            # candidates are active, so every candidate's full row is in
            # the buffer — the filter shrinks to buffer width
            adj_gain, _, _ = packed_afterburner_gain_rows(
                owner_c, dst_b, w_b, start, end,
                labels, next_lab, gain, candidate, C,
            )
        else:
            adj_gain = packed_afterburner_gain(
                graph.src, graph.dst, graph.edge_w, graph.row_ptr,
                labels, next_lab, gain, candidate, C,
            )
        target = jnp.where(candidate & (adj_gain > 0), target, -1)

    # -- commit: never exceed the cap even under simultaneous joins ------
    headroom = jnp.maximum(cap - cluster_weights.astype(ACC_DTYPE), 0)
    prio = hash_u32(node_ids, salt ^ 0x165667B1)
    accept = accept_prefix_by_capacity(target, prio, graph.node_w, headroom)

    # -- apply -----------------------------------------------------------
    new_labels = jnp.where(accept, target, labels)
    new_cluster_weights = apply_move_weight_delta(
        cluster_weights, labels, target, accept, graph.node_w
    )

    # -- active set refresh (label_propagation.h:507-513): a node is active
    # next round iff it or one of its neighbors moved this round, or it
    # wanted a move but was unsampled/capacity-rejected.  This both
    # mirrors the reference's semantics AND feeds the delta rounds: the
    # next round's row buffer holds exactly these nodes' rows.
    if cfg.use_active_set:
        if rows is not None:
            # movers' rows are in the buffer; flag their endpoints with
            # one buffer-wide scatter
            moved_slot = accept[owner_c] & valid
            neigh_moved = (
                jnp.zeros(n_pad, dtype=jnp.int32)
                .at[dst_b]
                .max(moved_slot.astype(jnp.int32), mode="drop")
                > 0
            )
        else:
            # one edge gather + streaming row sums (scatter-free; see
            # segments.neighbor_any_true)
            neigh_moved = neighbor_any_true(accept, graph.dst, graph.row_ptr)
        # retention: a node stays active while it still has a USABLE
        # candidate — improving, or a positive-weight tie (clustering).
        # Gating retention on `wants` deactivated tie-blocked nodes
        # forever even though the hashed tie direction re-rolls every
        # round (the salt changes), which froze coarsening into ~20
        # limping levels on dense coarse graphs; unsampled
        # (participation) and capacity-rejected nodes are likewise kept.
        # `& active` keeps full and delta rounds bitwise-identical: a
        # deactivated node is reactivated ONLY by a neighbor's move in
        # both (a delta round never rates inactive rows, so a full round
        # must not resurrect them from its all-rows rating either)
        may_move_later = active & (best >= 0) & (best != labels) & (
            (gain > 0)
            | (
                (not cfg.refinement)
                & cfg.allow_tie_moves
                & (gain == 0)
                & (best_w > 0)
            )
        )
        # barred rows (scatter engine: still-contested after both
        # elimination passes) keep their active bit — the next round's
        # salt re-rolls their slots, so they get rated again
        new_active = (
            accept | neigh_moved | (may_move_later & ~accept)
            | (barred & active)
        )
    else:
        new_active = jnp.ones_like(active)

    # convergence is judged on *wanting* nodes, not sampled movers: a round
    # where the participation sample happens to move nobody must not stop
    # the loop while unsampled nodes still have improving moves
    # wanting-node count <= n, ID domain  # tpulint: disable=R3
    num_wanting = jnp.sum(wants, dtype=jnp.int32)
    return new_labels, new_cluster_weights, new_active, num_wanting


def _round_with_delta(
    graph: DeviceGraph,
    labels: jax.Array,
    weights: jax.Array,
    max_cluster_weight: jax.Array,
    active: jax.Array,
    salt: jax.Array,
    cfg: LPConfig,
    communities: jax.Array | None,
    i: jax.Array,
):
    """One LP round, delta-dispatched: after the first round, when the
    active nodes' rows fit the m_pad/4 buffer, run the round on the
    compacted buffer instead of the full edge list (lax.cond — only the
    taken branch executes).  The active set collapses to movers + their
    neighbors after round 1, so later rounds cost O(active rows), not m —
    the bulk-synchronous answer to the async reference's active-set
    work-skipping (label_propagation.h:507-513)."""
    C = weights.shape[0]
    engine = _select_engine(
        cfg, C, graph.m_pad, communities is not None, n_pad=graph.n_pad
    )
    dslots = _delta_slots(graph, cfg, engine)
    if dslots is None:
        return lp_round(
            graph, labels, weights, max_cluster_weight, active, salt, cfg,
            communities=communities,
        )
    deg = graph.degrees

    def delta_fn(op):
        labels, weights, active = op
        rows = expand_active_rows(graph.row_ptr, deg, active, dslots)
        return lp_round(
            graph, labels, weights, max_cluster_weight, active, salt, cfg,
            communities=communities, rows=rows,
        )

    def full_fn(op):
        labels, weights, active = op
        return lp_round(
            graph, labels, weights, max_cluster_weight, active, salt, cfg,
            communities=communities,
        )

    # active-degree total <= m_pad < 2^31 (device layout)
    # tpulint: disable=R3
    total = jnp.sum(jnp.where(active & (deg > 0), deg, 0), dtype=jnp.int32)
    pred = (i > 0) & (total <= dslots)
    return lax.cond(pred, delta_fn, full_fn, (labels, weights, active))


@partial(jax.jit, static_argnames=("cfg", "num_iterations", "has_communities"))
def _lp_cluster_impl(
    graph: DeviceGraph,
    max_cluster_weight: jax.Array,
    seed: jax.Array,
    communities: jax.Array,
    cfg: LPConfig,
    num_iterations: int | None,
    has_communities: bool,
    stats=None,
):
    iters = num_iterations if num_iterations is not None else cfg.num_iterations
    comm = communities if has_communities else None
    labels, weights, stats = _lp_cluster_fused_rounds(
        graph, max_cluster_weight, seed, comm, cfg, iters, stats
    )
    labels = _lp_cluster_postpasses_traced(
        graph, labels, weights, max_cluster_weight, seed, cfg,
        has_communities,
    )
    return labels if stats is None else (labels, stats)


def _lp_cluster_postpasses_traced(
    graph, labels, weights, max_cluster_weight, seed, cfg: LPConfig,
    has_communities: bool,
):
    if not has_communities:
        # community-restricted clustering (v-cycles) skips the singleton
        # post-passes: they could merge across community boundaries
        if cfg.cluster_isolated:
            labels, weights = cluster_isolated_nodes(
                graph, labels, weights, max_cluster_weight
            )
        if cfg.two_hop:
            labels, weights = two_hop_cluster(
                graph, labels, weights, max_cluster_weight, seed, cfg
            )
    return labels


_lp_cluster_postpasses = jax.jit(
    _lp_cluster_postpasses_traced,
    static_argnames=("cfg", "has_communities"),
)


def _lp_cluster_chunked(
    graph: DeviceGraph,
    max_cluster_weight: jax.Array,
    seed: jax.Array,
    comm,
    cfg: LPConfig,
    iters: int,
    has_communities: bool,
) -> jax.Array:
    """One clustering round per launch — the TPU-worker watchdog guard
    above the fused budget (a multi-round fused clustering loop at
    128M-slot shapes is a multi-minute single launch that reproducibly
    kills the worker; the Jet/LP-refine chunking already guards the
    same failure mode).  Lives OUTSIDE jit: the convergence exit reads
    `moved` back per round.  Visits identical states to the fused loop:
    the python salt masked to 31 bits equals the traced int32-wraparound
    product (bit 31 of an addend cannot reach lower sum bits), and all
    state is integer, so results are bitwise-equal (tested)."""
    from ..caching import record_transfer
    from ..telemetry import ledger

    n_pad = graph.n_pad
    labels = jnp.arange(n_pad, dtype=jnp.int32)
    weights = graph.node_w.astype(ACC_DTYPE)
    if weights is graph.node_w:
        # astype was a no-op alias (node weights already ACC_DTYPE);
        # round 0 donates the carry, so an aliased buffer would delete
        # the graph's own node weights — force a fresh copy
        weights = jnp.array(weights, copy=True)
    active = jnp.ones(n_pad, dtype=bool)
    # progress capture, host-side: the chunked driver already reads the
    # convergence scalar back every round, so the series costs one more
    # scalar readback per round (telemetry-enabled runs only)
    rec = progress_mod.capture()
    t0 = progress_mod.now()
    moved_series, active_series = [], []
    for i in range(iters):
        off = jnp.int32((i * 1566083941) & 0x7FFFFFFF)
        salt = (jnp.asarray(seed, jnp.int32) * 131071 + off) & 0x7FFFFFFF
        tok = ledger.donation_begin((labels, weights, active),
                                    kind="lp-round")
        labels, weights, active, moved = _lp_cluster_round_launch(
            graph, labels, weights, max_cluster_weight, active,
            salt, jnp.int32(i), cfg, comm,
        )
        ledger.donation_end(tok)
        record_transfer("d2h", getattr(moved, "nbytes", 8),
                        kind="stat-pull")
        if rec:
            moved_series.append(int(moved))
            active_series.append(int(jnp.sum(active)))
        if int(moved) == 0:
            break
    if rec:
        progress_mod.emit_host(
            "lp", {"moved": moved_series, "active": active_series},
            t0=t0, phase="cluster", launch="chunked",
        )
    return _lp_cluster_postpasses(
        graph, labels, weights, max_cluster_weight, seed, cfg,
        has_communities,
    )


# the round carry (labels, weights, active) is donated: each chunked
# round's outputs alias the previous round's buffers instead of
# doubling the carry footprint per launch.  The execution ledger's
# donation audit verifies the aliasing was honored (telemetry/ledger.py)
@partial(jax.jit, static_argnames=("cfg", "has_comm"),
         donate_argnums=(1, 2, 4))
def _lp_cluster_round_launch_jit(
    graph, labels, weights, max_cluster_weight, active, salt, i,
    cfg: LPConfig, communities, has_comm: bool,
):
    return _round_with_delta(
        graph, labels, weights, max_cluster_weight, active, salt, cfg,
        communities if has_comm else None, i,
    )


def _lp_cluster_round_launch(
    graph, labels, weights, max_cluster_weight, active, salt, i,
    cfg: LPConfig, comm,
):
    has_comm = comm is not None
    # the dummy is a 1-element array (never read when has_comm is False)
    return _lp_cluster_round_launch_jit(
        graph, labels, weights, max_cluster_weight, active, salt, i, cfg,
        comm if has_comm else jnp.zeros(1, dtype=jnp.int32),
        has_comm,
    )


def _lp_cluster_fused_rounds(
    graph, max_cluster_weight, seed, comm, cfg: LPConfig, iters: int,
    stats=None,
):
    """The fused multi-round clustering loop (one launch).

    `stats` is an optional progress buffer (telemetry/progress.py)
    threaded through the carry; None (the default) leaves the jaxpr
    bitwise-identical to the uninstrumented loop — the zero-overhead-
    when-disabled contract tests/test_telemetry.py pins."""
    n_pad = graph.n_pad
    labels0 = jnp.arange(n_pad, dtype=jnp.int32)
    weights0 = graph.node_w.astype(ACC_DTYPE)
    active0 = jnp.ones(n_pad, dtype=bool)

    def cond(state):
        i, _, _, _, moved, _ = state
        return (i < iters) & (moved != 0)

    def body(state):
        i, labels, weights, active, _, stats = state
        salt = (seed.astype(jnp.int32) * 131071 + i * 1566083941) & 0x7FFFFFFF
        labels, weights, active, moved = _round_with_delta(
            graph, labels, weights, max_cluster_weight, active, salt,
            cfg, comm, i,
        )
        if stats is not None:  # trace-time guard (None adds no carry)
            stats = progress_mod.record(
                stats, i, moved, jnp.sum(active)
            )
        return (i + 1, labels, weights, active, moved, stats)

    init = (jnp.int32(0), labels0, weights0, active0, jnp.int32(1), stats)
    _, labels, weights, _, _, stats = lax.while_loop(cond, body, init)
    return labels, weights, stats


def lp_cluster(
    graph: DeviceGraph,
    max_cluster_weight: jax.Array,
    seed: jax.Array,
    cfg: LPConfig = LPConfig(),
    num_iterations: int | None = None,
    communities: jax.Array | None = None,
) -> jax.Array:
    """Size-constrained LP clustering (analog of LPClustering::compute_
    clustering, lp_clusterer.cc:90-110): every node starts as a singleton,
    runs `num_iterations` rounds or until no node moves, then clusters
    isolated nodes and two-hop-merges leftover singletons.

    `communities` (optional i32[n_pad]) restricts clustering to within
    communities — nodes only join clusters whose label node shares their
    community (Clusterer::set_communities analog, used by v-cycles).

    Returns i32[n_pad] cluster labels (values are node ids; pad slots keep
    their own id)."""
    from .segments import MAX_FUSED_EDGE_SLOTS

    has_comm = communities is not None
    iters = (
        num_iterations if num_iterations is not None else cfg.num_iterations
    )
    if graph.src.shape[0] > MAX_FUSED_EDGE_SLOTS and iters > 1:
        # watchdog guard: the dispatch must stay OUTSIDE jit — the
        # chunked loop reads the convergence flag back per round
        return _lp_cluster_chunked(
            graph, max_cluster_weight, seed, communities, cfg, iters,
            has_comm,
        )
    if communities is None:
        communities = jnp.zeros(graph.n_pad, dtype=jnp.int32)
    return progress_mod.instrumented(
        lambda stats: _lp_cluster_impl(
            graph,
            max_cluster_weight,
            seed,
            communities,
            cfg,
            num_iterations,
            has_comm,
            stats,
        ),
        "lp", ("moved", "active"), rows=iters, phase="cluster",
    )


# round carry (part, bw, active) donated — see _lp_cluster_round_launch_jit
@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1, 2, 4))
def _lp_refine_round_launch(graph, part, bw, max_block_weights, active,
                            salt, i, cfg: LPConfig):
    return _round_with_delta(
        graph, part, bw, max_block_weights, active, salt, cfg, None, i,
    )


def lp_refine(
    graph: DeviceGraph,
    partition: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    seed: jax.Array,
    cfg: LPConfig = LPConfig(refinement=True),
    num_iterations: int | None = None,
) -> jax.Array:
    """LP refinement entry point.  Above MAX_FUSED_EDGE_SLOTS a
    multi-round fused launch runs for minutes and reproducibly kills the
    TPU worker (same failure mode Jet's chunking guards against), so
    huge graphs run one round per launch — keeping the fused path's
    active set and moved==0 convergence exit across launches."""
    from .segments import MAX_FUSED_EDGE_SLOTS

    iters = num_iterations if num_iterations is not None else cfg.num_iterations
    if not cfg.refinement:
        # normalize once for BOTH launch strategies so the chunked path
        # never runs with clustering semantics (tie moves, no positive-gain
        # restriction); replace() preserves the caller's engine settings
        cfg = replace(cfg, allow_tie_moves=False, refinement=True)
    if graph.src.shape[0] > MAX_FUSED_EDGE_SLOTS and iters > 1:
        from ..caching import record_transfer
        from ..telemetry import ledger

        rec = progress_mod.capture()
        t0 = progress_mod.now()
        part = jnp.clip(partition, 0, k - 1).astype(jnp.int32)
        bw = jax.ops.segment_sum(
            graph.node_w.astype(ACC_DTYPE), part, num_segments=k
        )
        active = jnp.ones(graph.n_pad, dtype=bool)
        moved_series, active_series = [], []
        for i in range(iters):
            # equivalent to the fused while_loop's traced int32-wraparound
            # `i * 1566083941`: the final & 0x7FFFFFFF drops bit 31, and
            # bit 31 of an addend cannot reach lower sum bits — so masking
            # the python product to 31 bits visits identical states
            off = jnp.int32((i * 1566083941) & 0x7FFFFFFF)
            salt = (jnp.asarray(seed, jnp.int32) * 92821 + off) & 0x7FFFFFFF
            tok = ledger.donation_begin((part, bw, active),
                                        kind="lp-round")
            part, bw, active, moved = _lp_refine_round_launch(
                graph, part, bw, max_block_weights, active, salt,
                jnp.int32(i), cfg
            )
            ledger.donation_end(tok)
            record_transfer("d2h", getattr(moved, "nbytes", 8),
                            kind="stat-pull")
            if rec:
                moved_series.append(int(moved))
                active_series.append(int(jnp.sum(active)))
            if int(moved) == 0:
                break
        if rec:
            progress_mod.emit_host(
                "lp", {"moved": moved_series, "active": active_series},
                t0=t0, phase="refine", launch="chunked",
            )
        return part
    return progress_mod.instrumented(
        lambda stats: _lp_refine_fused(
            graph, partition, k, max_block_weights, seed, cfg, iters,
            stats,
        ),
        "lp", ("moved", "active"), rows=iters, phase="refine",
    )


@partial(jax.jit, static_argnames=("cfg", "k", "num_iterations"))
def _lp_refine_fused(
    graph: DeviceGraph,
    partition: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    seed: jax.Array,
    cfg: LPConfig = LPConfig(refinement=True),
    num_iterations: int | None = None,
    stats=None,
):
    """LP refinement (analog of LabelPropagationRefiner,
    kaminpar-shm/refinement/lp/lp_refiner.cc): the LP kernel with clusters
    fixed to the k blocks, moves restricted to strictly positive gain under
    the per-block max weights.  Returns the refined partition (plus the
    progress buffer when one was threaded in — see
    _lp_cluster_fused_rounds on the stats/None contract)."""
    iters = num_iterations if num_iterations is not None else cfg.num_iterations
    if not cfg.refinement:
        cfg = replace(cfg, allow_tie_moves=False, refinement=True)
    n_pad = graph.n_pad
    part0 = jnp.clip(partition, 0, k - 1).astype(jnp.int32)
    bw0 = jax.ops.segment_sum(
        graph.node_w.astype(ACC_DTYPE), part0, num_segments=k
    )
    active0 = jnp.ones(n_pad, dtype=bool)
    def cond(state):
        i, _, _, _, moved, _ = state
        return (i < iters) & (moved != 0)

    def body(state):
        i, part, bw, active, _, stats = state
        salt = (seed.astype(jnp.int32) * 92821 + i * 1566083941) & 0x7FFFFFFF
        part, bw, active, moved = _round_with_delta(
            graph, part, bw, max_block_weights, active, salt, cfg, None, i,
        )
        if stats is not None:  # trace-time guard (None adds no carry)
            stats = progress_mod.record(
                stats, i, moved, jnp.sum(active)
            )
        return (i + 1, part, bw, active, moved, stats)

    init = (jnp.int32(0), part0, bw0, active0, jnp.int32(1), stats)
    _, part, _, _, _, stats = lax.while_loop(cond, body, init)
    return part if stats is None else (part, stats)


def cluster_isolated_nodes(
    graph: DeviceGraph,
    labels: jax.Array,
    cluster_weights: jax.Array,
    max_cluster_weight: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Merge isolated singleton nodes into shared clusters under the weight
    cap (label_propagation.h:872-917).

    Isolated nodes are ordered by id; node i's tentative bin is
    floor(prefix_weight / cap); within each bin the capacity-respecting
    prefix pass rejects overflow (exactness), rejected nodes stay singleton.
    The first member of each bin is its leader; members adopt the leader's
    label."""
    n_pad = graph.n_pad
    node_ids = jnp.arange(n_pad, dtype=jnp.int32)
    is_real = node_ids < graph.n
    deg = graph.degrees
    iso = (deg == 0) & is_real & (labels == node_ids)

    cap = jnp.maximum(jnp.broadcast_to(max_cluster_weight, ()).astype(ACC_DTYPE), 1)
    w = jnp.where(iso, graph.node_w, 0).astype(ACC_DTYPE)
    cum_before = jnp.cumsum(w) - w
    bin_id = jnp.where(iso, (cum_before // cap).astype(jnp.int32), -1)

    # leader of each bin = first isolated node in it
    first_in_bin = jax.ops.segment_min(
        jnp.where(iso, node_ids, jnp.iinfo(jnp.int32).max),
        jnp.clip(bin_id, 0, n_pad - 1),
        num_segments=n_pad,
    )
    leader = jnp.where(iso, first_in_bin[jnp.clip(bin_id, 0, n_pad - 1)], -1)
    # joiners (non-leaders) move into the leader's cluster, capacity-checked
    joiner = iso & (leader != node_ids) & (leader >= 0)
    target = jnp.where(joiner, leader, -1)
    headroom = jnp.maximum(
        jnp.broadcast_to(max_cluster_weight, (n_pad,)).astype(ACC_DTYPE)
        - cluster_weights.astype(ACC_DTYPE),
        0,
    )
    accept = accept_prefix_by_capacity(
        target, node_ids, graph.node_w, headroom
    )
    new_labels = jnp.where(accept, target, labels)
    return new_labels, apply_move_weight_delta(
        cluster_weights, labels, target, accept, graph.node_w
    )


def two_hop_cluster(
    graph: DeviceGraph,
    labels: jax.Array,
    cluster_weights: jax.Array,
    max_cluster_weight: jax.Array,
    seed: jax.Array,
    cfg: LPConfig = LPConfig(),
) -> Tuple[jax.Array, jax.Array]:
    """Two-hop clustering of leftover singletons (label_propagation.h:919-
    1191): singleton nodes that share the same *favored cluster* (their
    best-rated cluster, ignoring the weight cap) are merged with each other
    — they are two hops apart through that cluster.  The smallest singleton
    id per favored cluster becomes the leader; the rest join it under the
    weight cap."""
    n_pad = graph.n_pad
    node_ids = jnp.arange(n_pad, dtype=jnp.int32)
    is_real = node_ids < graph.n
    singleton = (
        (labels == node_ids)
        & (cluster_weights[jnp.clip(labels, 0, n_pad - 1)] == graph.node_w)
        & is_real
        & (graph.degrees > 0)
    )

    # favored cluster = unconstrained best-rated cluster (same engine
    # dispatch as lp_round; a singleton's own label never appears among
    # its neighbors' labels, so own-exclusion is harmless here)
    neighbor_cluster = labels[graph.dst]
    engine = _select_engine(
        cfg, cluster_weights.shape[0], graph.m_pad, n_pad=n_pad
    )
    if engine == "scatter":
        # favored cluster = unconstrained best rated cluster from the
        # scatter slot tables, with the same collision-safe fallback as
        # the round rating: when too many singleton rows stay contested
        # the exact sort rating takes over (lax.cond, one branch runs)
        from .rating import best_from_slots, scatter_slot_ratings

        slot_label, slot_w, fully_rated = scatter_slot_ratings(
            graph.src, neighbor_cluster, graph.edge_w, n_pad,
            cfg.num_slots, seed,
        )

        def scatter_fav(_):
            # a table built without `joinable`: rated ignoring the cap
            fav, fav_w, _ = best_from_slots(
                slot_label, slot_w, labels, seed
            )
            # zero-weight ratings (sparsified-away edges) are not real
            # favorites — same mask as the sort2/hash branches
            return jnp.where(fully_rated & (fav_w > 0), fav, -1)

        def sort_fav(_):
            seg_g, key_g, w_g = aggregate_by_key(
                graph.src, neighbor_cluster, graph.edge_w
            )
            fav, _ = argmax_per_segment(
                seg_g, key_g, w_g, n_pad, tie_salt=seed
            )
            return fav

        # singleton counts <= n, ID domain  # tpulint: disable=R3
        n_bad = jnp.sum(singleton & ~fully_rated, dtype=jnp.int32)
        # singleton counts <= n, ID domain  # tpulint: disable=R3
        n_sing = jnp.sum(singleton, dtype=jnp.int32)
        favored = lax.cond(
            n_bad.astype(jnp.float32)
            <= jnp.float32(cfg.scatter_fallback)
            * n_sing.astype(jnp.float32),
            scatter_fav, sort_fav, None,
        )
    elif engine == "sort2":
        # a singleton's own label never appears among its neighbors, so
        # the top-1 rated cluster IS the favored cluster; zero-weight
        # ratings (sparsified-away or pad edges) are not real favorites
        top = rating_top3_by_sort(graph, neighbor_cluster, seed, k_best=1)
        favored = jnp.where(top[1] > 0, top[0], -1)
    elif engine == "hash":
        slot_label, slot_w = hashed_rating_table(
            graph.src, neighbor_cluster, graph.edge_w, n_pad,
            cfg.num_slots, seed,
        )
        favored, fav_w = best_from_rating_table(
            slot_label, slot_w, labels, cluster_weights, graph.node_w,
            jnp.broadcast_to(max_cluster_weight, (cluster_weights.shape[0],)),
            seed, require_fit=False,
        )
        favored = jnp.where(fav_w > 0, favored, -1)
    else:
        seg_g, key_g, w_g = aggregate_by_key(
            graph.src, neighbor_cluster, graph.edge_w
        )
        favored, _ = argmax_per_segment(
            seg_g, key_g, w_g, n_pad, tie_salt=seed
        )

    fav = jnp.where(singleton & (favored >= 0), favored, -1)
    fav_c = jnp.clip(fav, 0, n_pad - 1)
    leader = jax.ops.segment_min(
        jnp.where(fav >= 0, node_ids, jnp.iinfo(jnp.int32).max),
        fav_c,
        num_segments=n_pad,
    )
    my_leader = jnp.where(fav >= 0, leader[fav_c], -1)
    joiner = (fav >= 0) & (my_leader != node_ids) & (my_leader >= 0)
    target = jnp.where(joiner, my_leader, -1)

    headroom = jnp.maximum(
        jnp.broadcast_to(max_cluster_weight, (n_pad,)).astype(ACC_DTYPE)
        - cluster_weights.astype(ACC_DTYPE),
        0,
    )
    accept = accept_prefix_by_capacity(target, node_ids, graph.node_w, headroom)
    new_labels = jnp.where(accept, target, labels)
    return new_labels, apply_move_weight_delta(
        cluster_weights, labels, target, accept, graph.node_w
    )
