"""Segmented sort/reduce primitives — the TPU-native RatingMap.

The reference accumulates neighbor→cluster ratings in per-thread adaptive
hash maps (kaminpar-common/datastructures/rating_map.h) inside a per-node
loop (kaminpar-shm/label_propagation.h:461-541 find_best_cluster).  On TPU
the same computation is expressed as whole-graph sort + segmented-reduction
programs over the COO edge list: XLA lowers sorts and segment ops onto the
vector units with static shapes, which beats any per-node control flow.

Primitives:
  * hash_u32               — stateless integer mixer for random tie-breaking
                             (replaces per-thread RNG in find_best_cluster)
  * aggregate_by_key       — group (seg, key) pairs, sum weights per group
  * argmax_per_segment     — per-segment argmax with hashed tie-breaking
  * accept_prefix_by_capacity — sort movers by (target, priority) and accept
                             the maximal prefix per target under a capacity;
                             the bulk-synchronous replacement for the
                             reference's CAS cluster-weight updates
                             (label_propagation.h:2139 move_cluster_weight)

All functions are jit-safe with static shapes; "invalid" is encoded as -1.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Weight/accumulator dtypes and the 64-bit build switch live in the leaf
# module kaminpar_tpu.dtypes (KAMINPAR_TPU_64BIT=1); re-exported here for
# every kernel module.
from ..dtypes import ACC_DTYPE, INT32_MIN, X64_WEIGHTS  # noqa: F401

# A single fused device launch that runs for many minutes reproducibly
# kills the TPU worker (observed at 33M edges with a fully fused Jet
# round and at 128M with 4-iteration chunks); refiners split their
# multi-round launches above this many edge slots.
MAX_FUSED_EDGE_SLOTS = 1 << 26


def pad_k_bucket(k, max_block_weights, min_block_weights=None):
    """Round k up to a power of two with zero-capacity phantom blocks.

    k is shape-defining for every refinement kernel ((n, k) tables,
    k-segment reductions), so each distinct k would compile its own
    executable per shape bucket — with deep k-doubling that is log2(k)
    recompiles of the largest programs.  Phantom blocks get zero max
    (and min) weight: no node can move into them, results are
    identical, and one compiled program serves every k in the bucket.

    Returns (k_pad, max_block_weights, min_block_weights).
    """
    k_pad = max(2, 1 << (int(k) - 1).bit_length())
    from ..caching import record_padding

    record_padding(k=int(k), k_pad=k_pad)
    if k_pad != k:
        pad = jnp.zeros(k_pad - int(k), dtype=ACC_DTYPE)
        max_block_weights = jnp.concatenate(
            [jnp.asarray(max_block_weights, dtype=ACC_DTYPE), pad]
        )
        if min_block_weights is not None:
            min_block_weights = jnp.concatenate(
                [jnp.asarray(min_block_weights, dtype=ACC_DTYPE), pad]
            )
    return k_pad, max_block_weights, min_block_weights


def hash_u32(x: jax.Array, salt) -> jax.Array:
    """murmur3-style finalizer; returns non-negative int32."""
    x = x.astype(jnp.uint32) * jnp.uint32(0x9E3779B1) + jnp.uint32(salt)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x >> jnp.uint32(1)).astype(jnp.int32)


def hash_tie16(x: jax.Array, salt) -> jax.Array:
    """Top 16 bits of hash_u32 as non-negative int16 — the narrow
    TIE-BREAK dtype for edge-wide sort operands (round-9 dtype packing:
    a tie key only needs enough entropy to order ties deterministically,
    and halving the operand width cuts the sort's streamed bytes; an
    equal-16-bit tie falls through to the sort's stable order, which is
    itself deterministic).  NEVER for weights/gains — those keep
    ACC_DTYPE per the dtypes.py policy (tpulint R3)."""
    return (hash_u32(x, salt) >> jnp.int32(16)).astype(jnp.int16)


def sort_by_two_keys(
    primary: jax.Array, secondary: jax.Array, *values: jax.Array
) -> Tuple[jax.Array, ...]:
    """Lexicographic sort by (primary, secondary), carrying values."""
    return lax.sort((primary, secondary) + values, num_keys=2)


def aggregate_by_key(
    seg: jax.Array, key: jax.Array, w: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Group entries by (seg, key) and sum weights per group.

    Returns (seg_g, key_g, w_g), all of length len(seg); group g occupies
    slot g, unused slots have seg_g == -1.  This is the whole-graph analog
    of one RatingMap fill: for LP, seg = edge source node, key = neighbor's
    cluster, w = edge weight, and (seg_g, key_g, w_g) enumerates each node's
    adjacent clusters with their connection weights.
    """
    m = seg.shape[0]
    seg_s, key_s, w_s = sort_by_two_keys(seg, key, w.astype(ACC_DTYPE))
    prev_seg = jnp.concatenate([jnp.array([-1], seg_s.dtype), seg_s[:-1]])
    prev_key = jnp.concatenate([jnp.array([-1], key_s.dtype), key_s[:-1]])
    is_new = (seg_s != prev_seg) | (key_s != prev_key)
    # group sums WITHOUT scatters (TPU scatters cost ~7.5 ns/index; these
    # are streaming passes): inclusive cumsum minus the cummax'd group
    # base (cum - w at group starts is monotone because weights >= 0);
    # the group's total sits at its last element
    cum = jnp.cumsum(w_s)
    base = lax.cummax(jnp.where(is_new, cum - w_s, 0))
    total = cum - base
    is_last = jnp.concatenate([is_new[1:], jnp.array([True])])
    # compact group-last entries to the front, preserving (seg, key)
    # order: one position scatter + three cheap gathers.  This replaced
    # a second 5-operand 2-key sort — bitwise-identical output (the
    # group prefix keeps its (seg, key) order, the suffix is the same
    # masked fill), at one indexed pass instead of a multi-operand
    # comparator sort (the round-9 CPU profile put that sort at ~45% of
    # aggregate_by_key's wall; on TPU a 1-index-per-slot scatter and
    # the sort price within noise of each other).
    pos = jnp.arange(m, dtype=jnp.int32)
    # group g's output slot; non-lasts routed to the dropped slot m
    out_slot = jnp.cumsum(is_last.astype(jnp.int32)) - 1
    dest = jnp.where(is_last, out_slot, m)
    src_pos = (
        jnp.full(m, m, dtype=jnp.int32).at[dest].set(pos, mode="drop")
    )
    in_groups = src_pos < m
    sp = jnp.clip(src_pos, 0, m - 1)
    seg_g = jnp.where(in_groups, seg_s[sp], -1)
    key_g = jnp.where(in_groups, key_s[sp], -1)
    w_g = jnp.where(in_groups, total[sp], 0)
    return seg_g, key_g, w_g


def argmax_per_segment(
    seg: jax.Array,
    key: jax.Array,
    score: jax.Array,
    num_segments: int,
    tie_salt,
    feasible: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """For each segment, the key with max score among feasible entries,
    ties broken by a hashed pseudo-random priority (the TPU analog of the
    uniform random tie-breaking in label_propagation.h:461-541).

    Entries with seg < 0 are ignored.  Returns (best_key, best_score) of
    length num_segments; best_key = -1 / best_score = INT32_MIN where a
    segment has no feasible entry.
    """
    ok = seg >= 0
    if feasible is not None:
        ok = ok & feasible
    seg_c = jnp.where(ok, seg, num_segments)  # routed to an overflow slot
    masked = jnp.where(ok, score, INT32_MIN)
    best = jax.ops.segment_max(masked, seg_c, num_segments=num_segments + 1)[
        :num_segments
    ]
    has = best > INT32_MIN
    is_best = ok & (score == best[jnp.clip(seg_c, 0, num_segments - 1)]) & (
        seg_c < num_segments
    )
    tb = hash_u32(key, tie_salt)
    tb_m = jnp.where(is_best, tb, -1)
    # hashes and keys are int32 regardless of the weight build — their
    # sentinel must stay in the int32 domain
    i32_min = jnp.iinfo(jnp.int32).min
    best_tb = jax.ops.segment_max(
        jnp.where(is_best, tb_m, i32_min), seg_c, num_segments=num_segments + 1
    )[:num_segments]
    winner = is_best & (tb == best_tb[jnp.clip(seg_c, 0, num_segments - 1)])
    best_key = jax.ops.segment_max(
        jnp.where(winner, key, i32_min), seg_c, num_segments=num_segments + 1
    )[:num_segments]
    best_key = jnp.where(has, best_key, -1)
    best_score = jnp.where(has, best, INT32_MIN)
    return best_key, best_score


def accept_prefix_by_capacity(
    target: jax.Array,
    priority: jax.Array,
    weight: jax.Array,
    capacity: jax.Array,
    reach: bool = False,
) -> jax.Array:
    """Capacity-respecting parallel commit.

    Each entry i wants to add `weight[i]` to bucket `target[i]` (-1 = not
    moving).  Entries are ordered by (target, priority) and the maximal
    prefix per target whose cumulative weight fits `capacity[target]` is
    accepted.  Returns a bool mask over entries.

    With `reach=True` the prefix instead *reaches* the capacity: the last
    accepted entry may cross it (used by the balancer when shedding an
    overloaded block — the reference moves nodes until the block becomes
    feasible, overload_balancer.h:25).  The default strict mode never
    exceeds the capacity.

    This replaces the reference's relaxed CAS loop on cluster weights
    (label_propagation.h:818 try_node_move / :2139 move_cluster_weight):
    instead of racing threads, one deterministic sorted pass guarantees the
    cap is never exceeded.
    """
    nbuckets = capacity.shape[0]
    idx = jnp.arange(target.shape[0], dtype=jnp.int32)
    t = jnp.where(target >= 0, target, nbuckets).astype(jnp.int32)
    t_s, p_s, w_s, idx_s = lax.sort((t, priority, weight, idx), num_keys=2)
    c = jnp.cumsum(w_s.astype(ACC_DTYPE))
    prev_t = jnp.concatenate([jnp.array([-1], t_s.dtype), t_s[:-1]])
    is_first = t_s != prev_t
    gid = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    seg_base = jax.ops.segment_min(
        jnp.where(is_first, c - w_s.astype(ACC_DTYPE), jnp.iinfo(ACC_DTYPE).max),
        gid,
        num_segments=target.shape[0],
    )
    cum_in_seg = c - seg_base[gid]
    cap_here = jnp.where(
        t_s < nbuckets, capacity[jnp.clip(t_s, 0, nbuckets - 1)], 0
    ).astype(ACC_DTYPE)
    if reach:
        accepted_sorted = (t_s < nbuckets) & (
            cum_in_seg - w_s.astype(ACC_DTYPE) < cap_here
        )
    else:
        accepted_sorted = (t_s < nbuckets) & (cum_in_seg <= cap_here)
    accept = jnp.zeros(target.shape[0], dtype=bool).at[idx_s].set(accepted_sorted)
    return accept


def move_weight_delta(
    labels: jax.Array,
    target: jax.Array,
    accept: jax.Array,
    node_w: jax.Array,
    num_clusters: int,
) -> jax.Array:
    """Per-cluster weight delta of a bulk move (movers leave `labels`,
    join `target`).  The distributed round psums this across devices
    before applying (the control_cluster_weights analog)."""
    moved_w = jnp.where(accept, node_w, 0).astype(ACC_DTYPE)
    out_w = jax.ops.segment_sum(
        moved_w, jnp.clip(labels, 0, num_clusters - 1), num_segments=num_clusters
    )
    in_w = jax.ops.segment_sum(
        moved_w, jnp.clip(target, 0, num_clusters - 1), num_segments=num_clusters
    )
    return in_w - out_w


def apply_move_weight_delta(
    cluster_weights: jax.Array,
    labels: jax.Array,
    target: jax.Array,
    accept: jax.Array,
    node_w: jax.Array,
) -> jax.Array:
    """Update per-cluster weights after a bulk move: subtract movers from
    their old cluster, add them to the new one.  Shared by LP rounds,
    isolated-node clustering, and two-hop clustering."""
    C = cluster_weights.shape[0]
    delta = move_weight_delta(labels, target, accept, node_w, C)
    return (cluster_weights + delta).astype(cluster_weights.dtype)


def connection_to_label(
    seg_g: jax.Array,
    key_g: jax.Array,
    w_g: jax.Array,
    labels: jax.Array,
    n_pad: int,
) -> jax.Array:
    """Per-node connection weight to its own current label (0 if none).
    Shared by LP, the balancers, and Jet."""
    cur_of_group = labels[jnp.clip(seg_g, 0, n_pad - 1)]
    match = (seg_g >= 0) & (key_g == cur_of_group)
    seg_c = jnp.where(match, seg_g, n_pad)
    w_cur = jax.ops.segment_max(
        jnp.where(match, w_g, 0), seg_c, num_segments=n_pad + 1
    )[:n_pad]
    # segment_max identity is INT32_MIN; empty segments mean no connection
    return jnp.maximum(w_cur, 0)


def combine_labels(l1: jax.Array, l2: jax.Array) -> jax.Array:
    """Intersect two clusterings: nodes end up together iff they share a
    cluster in BOTH inputs (the overlay/PASCO combination used by
    OverlayClusterCoarsener, kaminpar-shm/coarsening/overlay_cluster_
    coarsener.cc).  Returns labels whose values are node ids (the minimum
    node id of each (l1, l2) group), same convention as lp_cluster."""
    n = l1.shape[0]
    node = jnp.arange(n, dtype=jnp.int32)
    a, b, idx = lax.sort((l1, l2, node), num_keys=2)
    prev_a = jnp.concatenate([jnp.array([-1], a.dtype), a[:-1]])
    prev_b = jnp.concatenate([jnp.array([-1], b.dtype), b[:-1]])
    is_new = (a != prev_a) | (b != prev_b)
    gid = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    leader = jax.ops.segment_min(idx, gid, num_segments=n)
    out = jnp.zeros(n, dtype=jnp.int32).at[idx].set(leader[gid])
    return out


def compact_unique(labels: jax.Array, n_pad: int) -> Tuple[jax.Array, jax.Array]:
    """Remap arbitrary label values in [0, n_pad) to dense ids [0, c).

    Returns (dense_label_per_slot, num_unique).  The analog of the
    reference's fill_leader_mapping + prefix sum
    (cluster_contraction_preprocessing.cc:17,69): mark used labels, prefix-
    sum the marks, gather.
    """
    used = jnp.zeros(n_pad, dtype=jnp.int32).at[labels].max(1, mode="drop")
    rank = jnp.cumsum(used) - used  # dense id of each used label
    dense = rank[labels].astype(jnp.int32)
    num = jnp.sum(used)
    return dense, num


# ---------------------------------------------------------------------------
# Sort-free rating engines
# ---------------------------------------------------------------------------
#
# aggregate_by_key is exact but costs a full 2-key sort of the edge list per
# LP round — the dominant cost of the whole framework on TPU (XLA sorts are
# many HBM passes; scatter-adds are one).  These engines produce the same
# per-node (best cluster, weight) decisions with segment_sum/segment_max
# only:
#
#   * hashed_rating_table — clustering (unbounded label space): per node, a
#     fixed row of `num_slots` hash slots; each slot's *winner* label gets
#     an EXACT connection-weight sum (every edge with that label lands in
#     the same slot).  Colliding (non-winning) labels are simply not rated
#     this round — the analog of the reference's two-phase rating-map
#     overflow handling (label_propagation.h:62 kRatingMapThreshold), and
#     the per-round salt rotates which label wins a contested slot.
#
#   * dense_block_ratings — refinement (labels are the k blocks): the full
#     exact (n_pad, k) connection table in one segment_sum, no slots, no
#     collisions.


def hashed_rating_table(
    src: jax.Array,
    neighbor_label: jax.Array,
    edge_w: jax.Array,
    n_pad: int,
    num_slots: int,
    salt,
) -> Tuple[jax.Array, jax.Array]:
    """Per-node hashed rating rows.

    Returns (slot_label, slot_w), both [n_pad, num_slots]: slot_label is
    the slot's winning label (-1 for empty slots) and slot_w its exact
    total connection weight from the row's node.
    """
    if n_pad * num_slots >= 2**31:
        raise ValueError("n_pad * num_slots must fit in int32")
    slot = hash_u32(neighbor_label, salt) % jnp.int32(num_slots)
    flat = src.astype(jnp.int32) * num_slots + slot
    total = n_pad * num_slots
    # winner of a contested slot: max hashed key, ties broken by max label
    key = hash_u32(neighbor_label, salt ^ 0x3779B97F)  # fits int32
    kmax = jax.ops.segment_max(key, flat, num_segments=total)
    is_kwin = key == kmax[flat]
    lwin = jax.ops.segment_max(
        jnp.where(is_kwin, neighbor_label, -1), flat, num_segments=total
    )
    is_win = is_kwin & (neighbor_label == lwin[flat])
    w = jax.ops.segment_sum(
        jnp.where(is_win, edge_w, 0).astype(ACC_DTYPE),
        flat,
        num_segments=total,
    )
    slot_label = jnp.where(kmax >= 0, lwin, -1)
    return (
        slot_label.reshape(n_pad, num_slots),
        w.reshape(n_pad, num_slots),
    )


def best_from_rating_table(
    slot_label: jax.Array,
    slot_w: jax.Array,
    labels: jax.Array,
    cluster_weights: jax.Array,
    node_w: jax.Array,
    cap: jax.Array,
    salt,
    communities: jax.Array | None = None,
    require_fit: bool = True,
    label_range: Tuple[jax.Array, jax.Array] | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-node best move target from a hashed rating table: the
    highest-weight slot whose label is not the node's own, fits under the
    weight cap (unless require_fit=False), and shares the node's community
    (when given).  `label_range=(lo, hi)` restricts targets to labels in
    [lo, hi) — the LocalLPClusterer device-owned restriction.  Hashed
    tie-breaking, same contract as argmax_per_segment: (best_label,
    best_w) with -1/INT32_MIN when none.
    """
    n_pad, H = slot_label.shape
    C = cluster_weights.shape[0]
    lab_c = jnp.clip(slot_label, 0, C - 1)
    feas = (slot_label >= 0) & (slot_label != labels[:, None])
    if label_range is not None:
        lo, hi = label_range
        feas = feas & (slot_label >= lo) & (slot_label < hi)
    if require_fit:
        cap_b = jnp.broadcast_to(cap, (C,))
        feas = feas & (
            cluster_weights[lab_c].astype(ACC_DTYPE)
            + node_w[:, None].astype(ACC_DTYPE)
            <= cap_b[lab_c]
        )
    if communities is not None:
        feas = feas & (communities[lab_c] == communities[:, None])
    score = jnp.where(feas, slot_w, INT32_MIN)
    best_w = jnp.max(score, axis=1)
    has = best_w > INT32_MIN
    is_best = feas & (score == best_w[:, None])
    tb = hash_u32(slot_label, salt)
    best_tb = jnp.max(jnp.where(is_best, tb, -1), axis=1)
    winner = is_best & (tb == best_tb[:, None])
    best = jnp.max(jnp.where(winner, slot_label, -1), axis=1)
    return (
        jnp.where(has, best, -1),
        jnp.where(has, best_w, INT32_MIN),
    )


def connection_to_own_label(
    src: jax.Array,
    neighbor_label: jax.Array,
    edge_w: jax.Array,
    labels: jax.Array,
    n_pad: int,
) -> jax.Array:
    """Exact per-node connection weight to the node's own label — one
    masked segment_sum (sort-free replacement for connection_to_label)."""
    match = neighbor_label == labels[jnp.clip(src, 0, n_pad - 1)]
    return jax.ops.segment_sum(
        jnp.where(match, edge_w, 0).astype(ACC_DTYPE),
        src,
        num_segments=n_pad,
    )


def dense_block_ratings(
    src: jax.Array,
    dst: jax.Array,
    edge_w: jax.Array,
    labels: jax.Array,
    n_pad: int,
    num_blocks: int,
) -> jax.Array:
    """Exact (n_pad, k) connection table in one flat segment_sum — the
    rating engine for refinement, where labels are the k blocks (no sort,
    no hash collisions; identical to gains.build_dense_gain_cache but on
    raw arrays)."""
    lab_c = jnp.clip(labels, 0, num_blocks - 1)
    flat = src.astype(jnp.int32) * num_blocks + lab_c[dst]
    conn = jax.ops.segment_sum(
        edge_w.astype(ACC_DTYPE), flat, num_segments=n_pad * num_blocks
    )
    return conn.reshape(n_pad, num_blocks)


# ---------------------------------------------------------------------------
# CSR-order streaming — work keyed by the OWNER of a CSR slot
# ---------------------------------------------------------------------------
# XLA's gathers and scatters are charged per index on TPU whatever the
# table size (v5e, measured: 9 ns an index gathered, 7 ns scattered),
# a streaming pass well under 1 ns an element.  On a DeviceGraph the
# owner of a slot is sorted and constant along each row, so owner-side
# work is a cumsum plus an n-wide access at the row boundaries (the
# _row_sums / neighbor_any_true idiom), never an m-wide irregular pass.

# Words of one streaming step's (columns, m_pad) cumsum in
# csr_block_ratings: at 32 MiB the compiler keeps it out of HBM
# (temporaries 0.3-5 MB, 134 MB one doubling up); 8 columns fill the
# int32 sublanes.
CONN_STREAM_STEP_WORDS = 1 << 23
# csr_block_ratings streams where its boundary gathers are at least
# this many times fewer indices than the segment_sum's m_pad ...
CONN_STREAM_MIN_INDEX_RATIO = 4
# ... and the table takes at most this many steps: a step whose cumsum
# does live in HBM (m_pad >= 2^21) costs a ninth of the scatter.
# Both measured on v5e (PERF.md, PR 25).
CONN_STREAM_MAX_STEPS = 8


# _cumsum_minor splits a long scan into this many rows (PERF.md, PR 25)
CUMSUM_ROWS = 1024


def _cumsum_minor(x: jax.Array) -> jax.Array:
    """jnp.cumsum along the last axis, bitwise in integers, as a
    two-level scan: CUMSUM_ROWS rows scanned side by side, then each row
    lifted by the total of the rows before it.  The flat scan of 2^20
    or 2^21 words is the most expensive thing in this file to COMPILE
    for the TPU (12-34 s and 1.3-2.4 MB of code an instance on v5e;
    this form under a second and 0.5-0.9 MB, no slower to run), and
    every loaded executable's code sits in HBM."""
    m = x.shape[-1]
    if m % CUMSUM_ROWS or m < 128 * CUMSUM_ROWS:
        return jnp.cumsum(x, axis=-1, dtype=x.dtype)
    rows = x.reshape(x.shape[:-1] + (CUMSUM_ROWS, m // CUMSUM_ROWS))
    within = jnp.cumsum(rows, axis=-1, dtype=x.dtype)
    totals = within[..., -1]
    before = jnp.cumsum(totals, axis=-1, dtype=x.dtype) - totals
    return (within + before[..., None]).reshape(x.shape)


def expand_rows(values: jax.Array, row_ptr: jax.Array, m_pad: int) -> jax.Array:
    """Per-slot value of the slot's owner: bitwise `values[graph.src]`
    on a DeviceGraph (pad slots carry the pad node n_pad - 1, pad rows
    are empty at m), at n_pad scatter indices + one streaming pass.

    The first differences values[i] - values[i-1] are scatter-added at
    the row starts and one cumsum telescopes them back: slot e reads
    the sum over rows starting at or before e, which is the value of
    the last such row.  Empty rows collide on one slot and telescope
    there; a row start at m_pad owns no slot and is dropped; integer
    wrap-around keeps it exact for any int32 word (the afterburner's
    packed meta included)."""
    prev = jnp.concatenate([jnp.zeros(1, values.dtype), values[:-1]])
    starts = jnp.zeros(m_pad, values.dtype).at[row_ptr[:-1]].add(
        values - prev, mode="drop", indices_are_sorted=True
    )
    return _cumsum_minor(starts)


def conn_stream_columns(m_pad: int) -> int:
    """Block columns one streaming step of csr_block_ratings rates."""
    return max(1, min(8, CONN_STREAM_STEP_WORDS // m_pad))


def conn_table_streams(k: int, n_pad: int, m_pad: int) -> bool:
    """The engine csr_block_ratings takes, from shapes alone.  A
    streaming step rates conn_stream_columns block columns with one
    masked cumsum and one boundary gather of n_pad + 1 indices; the
    flat segment_sum scatters m_pad indices."""
    steps = -(-k // conn_stream_columns(m_pad))
    return (
        steps <= CONN_STREAM_MAX_STEPS
        and CONN_STREAM_MIN_INDEX_RATIO * steps * n_pad <= m_pad
    )


def count_conn_engine(graph, k: int) -> None:
    """Host-side record of the engine csr_block_ratings takes for one
    refiner call on `graph` (utils/statistics; free when disabled)."""
    from ..utils import statistics

    streams = conn_table_streams(k, graph.n_pad, graph.m_pad)
    statistics.count("conn_streamed" if streams else "conn_scattered")


def csr_block_ratings(graph, labels: jax.Array, num_blocks: int) -> jax.Array:
    """dense_block_ratings over a DeviceGraph's own CSR rows, bitwise:
    the labels[dst] gather stays (irregular by nature); the src-keyed
    segment_sum becomes, where conn_table_streams says it pays, masked
    cumsums per block column read at the row boundaries.  Columns run
    conn_stream_columns at a time as a (columns, m_pad) cumsum, m minor
    (one boundary gather serves the whole step), steps in a rolled
    loop: HLO size is constant in k, temporaries are bounded by
    CONN_STREAM_STEP_WORDS, and no (m, k) table ever exists (lane
    padding)."""
    n_pad, m_pad = graph.n_pad, graph.m_pad
    if not conn_table_streams(num_blocks, n_pad, m_pad):
        return dense_block_ratings(
            graph.src, graph.dst, graph.edge_w, labels, n_pad, num_blocks
        )
    return _stream_block_ratings(
        graph, labels, num_blocks,
        min(num_blocks, conn_stream_columns(m_pad)),
    )


def _stream_block_ratings(graph, labels, num_blocks: int, columns: int):
    """csr_block_ratings' streaming engine, `columns` block columns a
    step."""
    n_pad, m_pad = graph.n_pad, graph.m_pad
    block_v = jnp.clip(labels, 0, num_blocks - 1)[graph.dst]
    w = graph.edge_w.astype(ACC_DTYPE)
    # the inclusive cumsum read one slot before each row boundary (0
    # before the first slot) is the exclusive one at the boundary
    rp = jnp.clip(graph.row_ptr, 0, m_pad)
    before = jnp.maximum(rp - 1, 0)

    def step(first):
        blocks = first + jnp.arange(columns, dtype=block_v.dtype)
        csum = _cumsum_minor(
            jnp.where(block_v[None, :] == blocks[:, None], w[None, :], 0)
        )
        at_rp = jnp.where(rp[None, :] > 0, csum[:, before], 0)
        return at_rp[:, 1:] - at_rp[:, :-1]

    # a last partial step rates blocks >= num_blocks, which no label
    # carries after the clip: zero columns, cut off below
    firsts = jnp.arange(0, num_blocks, columns, dtype=block_v.dtype)
    conn_t = lax.map(step, firsts).reshape(-1, n_pad)
    return conn_t[:num_blocks].T


def best_from_dense(
    conn: jax.Array,
    labels: jax.Array,
    cluster_weights: jax.Array,
    node_w: jax.Array,
    cap: jax.Array,
    salt,
    communities: jax.Array | None = None,
    require_fit: bool = True,
    allowed: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-node (best_block, best_w, w_own) from a dense rating table,
    excluding the node's own block, with hashed tie-breaking.

    `communities` (clustering only — there column j is node id j) masks
    columns whose community differs from the row node's; `allowed`
    (bool[k]) masks whole columns (balancer target restrictions)."""
    n_pad, k = conn.shape
    lab_col = jnp.clip(labels, 0, k - 1)
    w_own = jnp.take_along_axis(conn, lab_col[:, None], axis=1)[:, 0]
    cols = jnp.arange(k, dtype=jnp.int32)
    feas = cols[None, :] != lab_col[:, None]
    if allowed is not None:
        feas = feas & allowed[None, :]
    if require_fit:
        cap_b = jnp.broadcast_to(cap, (k,)).astype(ACC_DTYPE)
        feas = feas & (
            cluster_weights[None, :].astype(ACC_DTYPE)
            + node_w[:, None].astype(ACC_DTYPE)
            <= cap_b[None, :]
        )
    if communities is not None:
        feas = feas & (communities[:k][None, :] == communities[:, None])
    score = jnp.where(feas, conn, INT32_MIN)
    best_w = jnp.max(score, axis=1)
    has = best_w > INT32_MIN
    is_best = feas & (score == best_w[:, None])
    tb = hash_u32(jnp.broadcast_to(cols[None, :], conn.shape), salt)
    best_tb = jnp.max(jnp.where(is_best, tb, -1), axis=1)
    winner = is_best & (tb == best_tb[:, None])
    best = jnp.max(jnp.where(winner, cols[None, :], -1), axis=1)
    return (
        jnp.where(has, best, -1),
        jnp.where(has, best_w, INT32_MIN),
        w_own,
    )


def rating_top3_by_sort(
    graph,
    neighbor_label: jax.Array,
    salt,
    k_best: int = 3,
) -> Tuple[jax.Array, ...]:
    """Top-k_best rated clusters per node with NO scatters and NO
    node->edge label expansion — the fast clustering rating engine
    ("sort2").

    TPU cost model (measured on v5e): irregular gathers/scatters cost
    ~7.5 ns *per index* (a 33M-edge expansion is ~250 ms) while sorts are
    ~3 ns/element and streaming ops are free.  This engine therefore uses
    exactly ONE edge-wide gather (labels[dst], done by the caller) and two
    edge-wide sorts; every reduction is a cumsum/cummax trick on sorted
    data, and per-node results are read back with n-sized gathers at CSR
    row boundaries.

      sort1   order edges by (src, label): groups = (node, cluster) pairs
      stream  group sums via cumsum minus a cummax'd group base
              (cum - w at group starts is monotone because weights >= 0)
      sort2   order by (src, group_total, tie_hash): each node's top
              clusters land at the end of its CSR row span
      read    the k_best best (label, weight) pairs per node at row end-j

    Returns (lab1, w1, ..., lab_k, w_k) for the `k_best` top clusters,
    each [n_pad]; absent entries are (-1, INT32_MIN).  Own-cluster
    exclusion, feasibility, and the connection-to-own estimate are applied
    by the caller at node level (see ops/lp.py), trading the reference's
    exact rating-time feasibility (find_best_cluster:461-541) for a
    33M-gather-free round.  The extra top-j reads are n-sized gathers —
    nearly free — so a larger k_best costs almost nothing and improves the
    caller's own-connection estimate on dense (coarse) graphs.
    """
    n_pad = graph.n_pad
    src = graph.src
    w = graph.edge_w.astype(ACC_DTYPE)

    src_s, nb_s, w_s = lax.sort((src, neighbor_label, w), num_keys=2)
    prev_src = jnp.concatenate([jnp.array([-1], src_s.dtype), src_s[:-1]])
    prev_nb = jnp.concatenate([jnp.array([-1], nb_s.dtype), nb_s[:-1]])
    new_grp = (src_s != prev_src) | (nb_s != prev_nb)

    cum = jnp.cumsum(w_s)
    base = lax.cummax(jnp.where(new_grp, cum - w_s, 0))
    total = cum - base
    is_last = jnp.concatenate([new_grp[1:], jnp.array([True])])

    # 16-bit tie operand (hash_tie16): half the third sort key's bytes
    tb = hash_tie16(nb_s, salt)
    prio = jnp.where(is_last, total, -1)
    _, prio2, _, lab2 = lax.sort((src_s, prio, tb, nb_s), num_keys=3)

    # per-node top-j reads at CSR row ends (row spans survive any
    # src-ordered sort: each node's edges occupy the same index range)
    deg = graph.row_ptr[1:] - graph.row_ptr[:-1]
    end = graph.row_ptr[1:]
    out = []
    for j in range(k_best):
        pos = jnp.clip(end - 1 - j, 0, prio2.shape[0] - 1)
        valid = (deg > j) & (prio2[pos] >= 0)
        out.append(jnp.where(valid, lab2[pos], -1))
        out.append(jnp.where(valid, prio2[pos], INT32_MIN))
    return tuple(out)


def expand_active_rows(
    row_ptr: jax.Array,
    degrees: jax.Array,
    active: jax.Array,
    num_slots: int,
):
    """Compact the CSR rows of active nodes into a `num_slots` buffer.

    The delta-round primitive: after the first LP/Jet round only a small
    fraction of nodes (movers + their neighbors) need re-rating, yet every
    edge-wide op costs ~10-15 ns per SLOT regardless of how many slots
    matter.  This lays the active nodes' rows head-to-tail into a fixed
    small buffer so every downstream pass scales with the active-edge
    count, not m.

    Cost: O(n) streaming + one n-wide scatter + ONE buffer-wide gather —
    the edge id falls out of a single gather of the PRE-SUBTRACTED
    (row_ptr - start) array (edge_id = diff[owner] + slot), instead of
    separate row_ptr[owner] and start[owner] gathers.  Do NOT be tempted
    to widen this into (n, r) row tables: TPU pads the minor dimension
    to 128 lanes, so materialized small-r tables cost 128/r x the memory
    and bandwidth (measured OOM at the 33.5M-edge shape), and XLA
    un-fuses stacked-table gathers back into scalar gathers anyway.

    Returns (owner_c, owner_key, edge_id, valid, start, end):
      owner_c  i32[num_slots]  owning node of each slot (clipped)
      owner_key i32[num_slots] owner for valid slots, n_pad for pad slots
      edge_id  i32[num_slots]  index into the edge arrays (clip before use)
      valid    bool[num_slots]
      start/end i32[n_pad]     each ACTIVE node's row span in the buffer
    """
    n_pad = degrees.shape[0]
    act = active & (degrees > 0)
    act_deg = jnp.where(act, degrees, 0).astype(jnp.int32)
    end = jnp.cumsum(act_deg)
    start = end - act_deg
    node_ids = jnp.arange(n_pad, dtype=jnp.int32)
    do = act & (start < num_slots)
    pos = jnp.where(do, start, num_slots)
    owner0 = (
        jnp.full(num_slots, -1, dtype=jnp.int32)
        .at[pos]
        .max(jnp.where(do, node_ids, -1), mode="drop")
    )
    owner = lax.cummax(owner0)
    slot = jnp.arange(num_slots, dtype=jnp.int32)
    owner_c = jnp.clip(owner, 0, n_pad - 1)
    diff = row_ptr[:-1].astype(jnp.int32) - start
    edge_id = diff[owner_c] + slot
    valid = (owner >= 0) & (slot < end[n_pad - 1])
    owner_key = jnp.where(valid, owner_c, n_pad)
    return owner_c, owner_key, edge_id, valid, start, end


def prune_candidates_to_budget(
    candidate: jax.Array,
    gain: jax.Array,
    degrees: jax.Array,
    salt,
    budget: int,
) -> jax.Array:
    """Restrict `candidate` to the best-(gain, hashed tie) subset whose
    total degree fits `budget` edge slots.

    The two-stage candidate pruning of the Jet refiner past its edge-slot
    gate, for the iterations whose candidate rows overflow the narrow
    row buffer: the gain temperature admits most border nodes on fine
    RMAT levels, and without a prune such a pass would fall back to
    full edge width.  Keeping the top-gain candidates that fit the wide
    buffer keeps every such iteration on the row-compacted path;
    pruned candidates stay unlocked and compete again next
    iteration, so over a Jet round's 8-16 iterations the move order
    approaches the reference's gain-ordered afterburner sequence
    (jet_refiner.cc:133-170) rather than changing what can move.

    When the candidate set already fits, the result equals `candidate`
    exactly.  One n-wide 2-key sort + streaming passes + one n-wide
    scatter; no edge-wide work.
    """
    n_pad = candidate.shape[0]
    node_ids = jnp.arange(n_pad, dtype=jnp.int32)
    # sentinel INT32_MIN+1 for non-candidates keys them strictly below
    # every candidate and keeps the negation below overflow-free
    key = jnp.where(
        candidate, jnp.maximum(gain, INT32_MIN + 2), INT32_MIN + 1
    )
    tb = hash_u32(node_ids, salt)
    neg_key = -key
    neg_tb = -tb
    deg = jnp.where(candidate, degrees, 0).astype(jnp.int32)
    _, _, deg_s, id_s = lax.sort(
        (neg_key, neg_tb, deg, node_ids), num_keys=2
    )
    cum = jnp.cumsum(deg_s)
    keep_s = cum <= budget
    keep = (
        jnp.zeros(n_pad, dtype=jnp.bool_).at[id_s].set(keep_s, mode="drop")
    )
    return candidate & keep


def rating_topk_rows(
    owner_key: jax.Array,
    nb: jax.Array,
    w: jax.Array,
    end: jax.Array,
    deg: jax.Array,
    salt,
    k_best: int,
) -> Tuple[jax.Array, ...]:
    """Top-k_best rated clusters per row, from row-grouped
    (owner, neighbor-label, weight) triples.

    The row-buffer twin of rating_top3_by_sort: slots must already be
    grouped by owner (ascending, pad slots keyed n_pad); two buffer-wide
    sorts + streaming passes, no scatters.  Returns the flat tuple
    (lab1, w1, ..., lab_k, w_k), each [n_pad], read at row ends
    (end[i]-1-j); absent entries are (-1, INT32_MIN).

    Pad-slot invariant: callers may key pad slots with n_pad (the
    delta-round path) OR with n_pad-1 (the full-round path, which passes
    owner_key=graph.src where pad edges carry owner n_pad-1).  The
    latter is sound ONLY because node n_pad-1 is always a pad node with
    degree 0 and an empty row span, so (a) pad slots still sort after
    every real row's slots and (b) no real read position end[i]-1-j ever
    lands inside them (deg[n_pad-1] == 0 gates validj).  A graph layout
    change that gives node n_pad-1 real edges would silently corrupt the
    top-K reads — keep the last pad row empty (see
    DeviceGraph.from_host's padding contract).
    """
    o_s, nb_s, w_s = sort_by_two_keys(owner_key, nb, w.astype(ACC_DTYPE))
    prev_o = jnp.concatenate([jnp.array([-1], o_s.dtype), o_s[:-1]])
    prev_nb = jnp.concatenate([jnp.array([-1], nb_s.dtype), nb_s[:-1]])
    new_grp = (o_s != prev_o) | (nb_s != prev_nb)
    cum = jnp.cumsum(w_s)
    base = lax.cummax(jnp.where(new_grp, cum - w_s, 0))
    total = cum - base
    is_last = jnp.concatenate([new_grp[1:], jnp.array([True])])
    # 16-bit tie operand (hash_tie16): half the third sort key's bytes
    tb = hash_tie16(nb_s, salt)
    prio = jnp.where(is_last, total, -1)
    _, prio2, _, lab2 = lax.sort((o_s, prio, tb, nb_s), num_keys=3)
    D = prio2.shape[0]
    out = []
    for j in range(k_best):
        posj = jnp.clip(end - 1 - j, 0, D - 1)
        validj = (deg > j) & (prio2[posj] >= 0)
        out.append(jnp.where(validj, lab2[posj], -1))
        out.append(jnp.where(validj, prio2[posj], INT32_MIN))
    return tuple(out)


def connection_to_own_rows(
    nb: jax.Array,
    w: jax.Array,
    own_of_slot: jax.Array,
    start: jax.Array,
    end: jax.Array,
) -> jax.Array:
    """Exact per-row connection weight to the row node's own label, via a
    streaming masked cumsum over row spans — no scatter, no sort.  `nb`
    and `w` are in buffer order, `own_of_slot` is the owner's label per
    slot, `start`/`end` the row spans."""
    D = nb.shape[0]
    match = nb == own_of_slot
    csum = jnp.cumsum(jnp.where(match, w, 0).astype(ACC_DTYPE))
    csum0 = jnp.concatenate([jnp.zeros(1, dtype=csum.dtype), csum])
    s = jnp.clip(start, 0, D)
    e = jnp.clip(end, 0, D)
    return csum0[e] - csum0[s]


def packed_afterburner_gain(
    src: jax.Array,
    dst: jax.Array,
    edge_w: jax.Array,
    row_ptr: jax.Array,
    part: jax.Array,
    next_part: jax.Array,
    gain: jax.Array,
    candidate: jax.Array,
    k: int,
) -> jax.Array:
    """Afterburner-adjusted gain per node, at TWO edge-wide gathers.

    The afterburner (jet_refiner.cc:133-170) re-evaluates each move
    candidate's gain assuming every neighbor ordering strictly before it —
    by (gain, smaller id) — already sits at its target block.  A naive
    implementation gathers gain/part/next_part for both endpoints of every
    edge (six edge-wide gathers — irregular gathers are charged per index
    on TPU and dominate the round).  Here the three per-node values are
    BIT-PACKED into ONE int32 per node, so each endpoint costs a single
    gather.  (n, r) row tables are NOT an alternative: TPU pads the minor
    dimension to 128 lanes — a materialized (m, 2) table is a 64x
    memory/bandwidth blowup (measured OOM at 33.5M edges) and XLA
    un-fuses in-loop stacked-table gathers back into scalar gathers.
    The per-node contribution sum is a streaming cumsum + CSR
    row-boundary diff (src must be CSR-sorted), not a scatter.

    The gain field is clipped to `31 - 2*ceil(log2 k)` bits; a runtime
    guard detects when any candidate |gain| exceeds the range (heavy
    edge weights) and dispatches the exact per-endpoint-gather fallback,
    so move SELECTION never silently diverges from the exact ordering.

    Returns adj_gain[n_pad]; entries for non-candidates are the plain
    neighborhood sum with no candidate mask applied to themselves (mask
    with `candidate` when accepting).  Shared by the Jet refiner and the
    bulk-synchronous LP refinement round.  A CSR edge list is a row
    buffer with owner=src and spans [row_ptr[i], row_ptr[i+1]), whose
    owner columns need no gather at all: src is sorted and constant
    along a row, so they stream (expand_rows) and only the dst side
    stays irregular.
    """
    m_pad = src.shape[0]
    adj, _, _ = _afterburner_gain(
        lambda values: expand_rows(values, row_ptr, m_pad),
        src, dst, edge_w, row_ptr[:-1], row_ptr[1:],
        part, next_part, gain, candidate, k,
    )
    return adj


def packed_afterburner_gain_rows(
    owner: jax.Array,
    dst: jax.Array,
    edge_w: jax.Array,
    start: jax.Array,
    end: jax.Array,
    part: jax.Array,
    next_part: jax.Array,
    gain: jax.Array,
    candidate: jax.Array,
    k: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """packed_afterburner_gain over a row buffer: slots grouped by owner
    with spans [start, end) per node (see expand_active_rows).

    Returns (adj_gain[n_pad], from_u[slots], to_u[slots]): the owner's
    current and tentative blocks PER SLOT fall out of the endpoint
    gathers either branch takes, so the Jet conn-table delta reuses them
    without further irregular ops."""
    return _afterburner_gain(
        lambda values: values[owner],
        owner, dst, edge_w, start, end,
        part, next_part, gain, candidate, k,
    )


def _afterburner_gain(
    of_owner,
    owner: jax.Array,
    dst: jax.Array,
    edge_w: jax.Array,
    start: jax.Array,
    end: jax.Array,
    part: jax.Array,
    next_part: jax.Array,
    gain: jax.Array,
    candidate: jax.Array,
    k: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The afterburner both entry points share.  `of_owner(values)` is
    the caller's way to a per-node array's per-slot owner column: a
    gather over a row buffer, a streaming pass over CSR rows."""
    label_bits = max((k - 1).bit_length(), 1)
    gain_bits = 31 - 2 * label_bits

    def _row_sums(to_u, from_u, block_v, u_is_cand):
        contrib = jnp.where(
            to_u == block_v,
            edge_w,
            jnp.where(from_u == block_v, -edge_w, 0),
        )
        csum = jnp.cumsum(
            jnp.where(u_is_cand, contrib, 0).astype(ACC_DTYPE)
        )
        csum0 = jnp.concatenate([jnp.zeros(1, dtype=csum.dtype), csum])
        D = contrib.shape[0]
        return csum0[jnp.clip(end, 0, D)] - csum0[jnp.clip(start, 0, D)]

    def _packed(_):
        half = jnp.int32(1 << (gain_bits - 1))
        gain_clip = jnp.clip(gain, 1 - half, half - 1) + half
        # the clipped field fits its bit budget by construction; force
        # int32 so 64-bit weight builds produce the same meta dtype as
        # the exact branch's label columns (lax.cond requires it)
        gain_field = jnp.where(candidate, gain_clip, 0).astype(jnp.int32)
        meta = (
            (gain_field << (2 * label_bits))
            | (next_part << label_bits)
            | part
        )
        mu = of_owner(meta)
        mv = meta[dst]
        lab_mask = jnp.int32((1 << label_bits) - 1)
        gain_u = mu >> (2 * label_bits)
        gain_v = mv >> (2 * label_bits)
        v_is_cand = gain_v > 0
        v_before_u = v_is_cand & (
            (gain_v > gain_u) | ((gain_v == gain_u) & (dst < owner))
        )
        block_v = jnp.where(
            v_before_u, (mv >> label_bits) & lab_mask, mv & lab_mask
        )
        to_u = (mu >> label_bits) & lab_mask
        from_u = mu & lab_mask
        return _row_sums(to_u, from_u, block_v, gain_u > 0), from_u, to_u

    def _exact(_):
        gain_full = jnp.where(candidate, gain, INT32_MIN)
        gain_u = of_owner(gain_full)
        gain_v = gain_full[dst]
        v_is_cand = gain_v > INT32_MIN
        v_before_u = v_is_cand & (
            (gain_v > gain_u) | ((gain_v == gain_u) & (dst < owner))
        )
        block_v = jnp.where(v_before_u, next_part[dst], part[dst])
        from_u = of_owner(part)
        to_u = of_owner(next_part)
        return (
            _row_sums(to_u, from_u, block_v, gain_u > INT32_MIN),
            from_u,
            to_u,
        )

    if gain_bits < 15:
        # huge k: the packed layout has no room at all
        return _exact(None)
    # clip guard: the packed gain field only orders moves correctly while
    # every candidate's |gain| fits its `gain_bits - 1` bits.  Heavy edge
    # weights (or degrees >~16k at k=256) push gains past the clip range
    # and silently change move SELECTION vs the exact ordering — so the
    # regime is detected at runtime (an n-wide reduce on values already
    # in hand) and the exact path takes over.  Both branches compile
    # once; only one executes per call.
    half = jnp.int32(1 << (gain_bits - 1))
    max_abs_gain = jnp.max(
        jnp.where(candidate, jnp.abs(jnp.clip(gain, -2**30, 2**30)), 0)
    )
    return lax.cond(max_abs_gain < half, _packed, _exact, None)


def neighbor_any_true(
    flag: jax.Array,
    dst: jax.Array,
    row_ptr: jax.Array,
) -> jax.Array:
    """Per-node "any neighbor has `flag`", at one edge-wide gather plus
    streaming passes (cumsum + CSR row-boundary diff) — the scatter-free
    replacement for segment_max(flag[dst], src).  Requires the edge list
    in CSR order (contiguous row spans), which DeviceGraph guarantees."""
    f = flag[dst].astype(ACC_DTYPE)
    csum = jnp.cumsum(f)
    csum0 = jnp.concatenate([jnp.zeros(1, dtype=csum.dtype), csum])
    rp = jnp.clip(row_ptr, 0, f.shape[0])
    return (csum0[rp[1:]] - csum0[rp[:-1]]) > 0


def afterburner_filter(
    src: jax.Array,
    dst: jax.Array,
    edge_w: jax.Array,
    labels_of_src: jax.Array,
    labels_of_dst: jax.Array,
    gain_by_node: jax.Array,
    target_by_node: jax.Array,
    seg: jax.Array,
    num_segments: int,
    src_order: jax.Array | None = None,
    dst_order: jax.Array | None = None,
) -> jax.Array:
    """Jet's afterburner (jet_refiner.cc:133-170) as a reusable filter:
    re-evaluate each move candidate's gain assuming every neighbor that
    orders strictly before it — by (gain, smaller id) — is already at its
    target, and return the adjusted gain per segment (node).  Bulk-
    synchronous LP refinement needs this because simultaneous moves of
    adjacent nodes can jointly increase the cut even when each individual
    gain is positive.

    `gain_by_node` must be INT32_MIN for non-candidates; `labels_of_*`
    and `target_by_node` are indexed by the same space as `src`/`dst`;
    `seg` maps each edge to its output segment (local node id on sharded
    layouts).  `src_order`/`dst_order` override the ids used for the
    who-moves-first tie ordering — on ghost-halo layouts `src`/`dst` are
    LOCAL indices (not globally consistent), so callers pass the GLOBAL
    ids there to keep the order a total order across devices.
    """
    if src_order is None:
        src_order = src
    if dst_order is None:
        dst_order = dst
    gain_u = gain_by_node[src]
    gain_v = gain_by_node[dst]
    v_before_u = (gain_v > INT32_MIN) & (
        (gain_v > gain_u) | ((gain_v == gain_u) & (dst_order < src_order))
    )
    block_v = jnp.where(v_before_u, target_by_node[dst], labels_of_dst)
    to_u = target_by_node[src]
    from_u = labels_of_src
    contrib = jnp.where(
        to_u == block_v,
        edge_w,
        jnp.where(from_u == block_v, -edge_w, 0),
    )
    return jax.ops.segment_sum(
        jnp.where(gain_u > INT32_MIN, contrib, 0),
        jnp.clip(seg, 0, num_segments - 1),
        num_segments=num_segments,
    )
