"""Rating engines — the shared gather→rate→argmax core of LP and Jet.

The reference rates a node's adjacent clusters in per-thread adaptive
hash maps (kaminpar-common/datastructures/rating_map.h) that grow from a
small fixed map to a full-size table on overflow
(label_propagation.h:62 kRatingMapThreshold).  "Partitioning Complex
Networks via Size-constrained Clustering" (arXiv 1402.3281) is explicit
that the map STRATEGY must adapt to the graph: dense rows want dense
tables, sparse rows want small hashed maps.  This module is the TPU
analog of that adaptivity: one home for every whole-graph rating
strategy plus the density-adaptive selector that picks between them per
level.

Engines (see docs/performance.md "Rating engines"):

  * ``scatter``  — NEW: a hashed slot table filled with segment-sum
    scatter-adds.  Two elimination passes make every *uncontested*
    label's connection weight EXACT, and a per-node ``fully_rated``
    flag marks rows whose every adjacent cluster got rated; rows that
    stay contested are barred from moving this round (the per-round
    salt re-rolls the slots) and a round-level guard falls back to the
    exact sort engine when too many rows are barred — collision-safe
    by construction.  No edge-list sort anywhere: the round touches
    the edge list with two gathers plus segment ops, and the table
    (wider than the edge list) with nothing irregular, which is why
    this is the coarsening hot-path engine (XLA sorts are many HBM
    passes; scatter-adds are one — BENCH_r04 utilization data).
  * ``sort2``    — top-K rated clusters per row via two buffer-wide
    sorts (ops/segments.rating_topk_rows); exact own-connection.
  * ``sort``     — exact enumeration of every adjacent cluster via the
    full 2-key COO sort (ops/segments.aggregate_by_key).  The fallback
    target of ``scatter`` and the reference semantics baseline.
  * ``hash``     — the legacy single-pass winner table
    (ops/segments.hashed_rating_table): contested labels are simply
    unrated for the round.  Kept as a forced option.
  * ``dense``    — the exact (n, k) table for refinement-sized label
    spaces (ops/segments.dense_block_ratings).

All engines share the SAME tie-break hash (hash_u32 of the candidate
label under the round salt), so two engines that rate the same
candidate set pick the SAME cluster — the engine-equivalence contract
tests/test_rating.py pins.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .segments import (
    ACC_DTYPE,
    INT32_MIN,
    _cumsum_minor,
    best_from_dense,
    dense_block_ratings,
    hash_u32,
)

ENGINES = ("auto", "scatter", "sort2", "sort", "hash", "dense")

#: Hashed slots per node row (per elimination pass).  32 puts the slot
#: table at n_pad * 64 entries across both passes, and the coarsener
#: doubles it on dense levels: at (n_pad, m_pad) = (2^16, 2^21), S = 64,
#: the table is 2^23 entries, FOUR TIMES the edge list (select_engine
#: admits up to six).  Nothing irregular may run at table width: a
#: gather is charged per index, 49-72 ms at 2^23 on v5e against 18.7
#: at 2^21 (PERF.md, PR 27).  Two passes push the fully-rated
#: fraction past ~95% at average degrees up to ~20 (measured on the
#: RMAT bench graphs).
DEFAULT_NUM_SLOTS = 32

#: Fall back to the exact sort engine when more than this fraction of
#: the round's active real nodes are barred (still-contested rows).
#: LPConfig.scatter_fallback defaults from this (0.5 measured best on
#: the 600k bench: barred rows concentrate in the active set over
#: rounds, and a lower threshold flips late rounds into paying BOTH
#: the table build and the sort).
SCATTER_FALLBACK_FRAC = 0.5


# ---------------------------------------------------------------------------
# density-adaptive engine selection
# ---------------------------------------------------------------------------


def select_engine(
    rating: str,
    num_clusters: int,
    n: int,
    m_slots: int,
    num_slots: int = DEFAULT_NUM_SLOTS,
    avg_degree: Optional[float] = None,
    degree_skew: Optional[float] = None,
    row_spans: bool = True,
) -> Tuple[str, str]:
    """Pick a rating engine for one level; returns (engine, reason).

    Trace-time static: every input is a host int/float (shapes, measured
    level stats), never a traced array.  ``avg_degree``/``degree_skew``
    are the measured per-level density stats (the coarsener reads them
    off the level before clustering; callers without measurements pass
    None and get the padded-shape approximation).  ``row_spans=False``
    (the sharded COO layout) removes the row-span engines (sort2).

    The rule, in order (the 1402.3281 adaptivity argument):
      * forced engine -> respected verbatim;
      * label space <= 256 (refinement-sized) -> dense exact table;
      * avg degree within the slot budget and skew moderate -> scatter
        (collisions stay rare enough that the two-pass elimination
        rates nearly every row; the fallback guard catches the rest);
      * otherwise -> sort2 (dense rows want the top-K sort, and its
        cost does not degrade with contention) — or sort when the
        layout has no row spans.
    """
    if rating != "auto":
        return rating, "forced"
    if num_clusters <= 256:
        return "dense", f"labels={num_clusters}<=256"
    if avg_degree is None:
        avg_degree = m_slots / max(n, 1)
    if degree_skew is None:
        degree_skew = 1.0
    # scatter preconditions, checked in order so the REASON names the
    # first one that failed (the rating-engine event/report row is an
    # audit surface — it must never claim a condition that held):
    #   * density within the slot budget;
    #   * skew window — BELOW it (uniform/geometric graphs, e.g. rgg2d
    #     at skew ~2.5) clustering rides zero-gain tie chains and even
    #     a few percent of barred rows measurably derail the
    #     trajectory (2x cut at 3% barred); ABOVE it, hub rows can
    #     never be fully rated and the fallback churns.  High-skew
    #     RMAT (the class that motivated the engine) tolerates barred
    #     rows: cut matched sort2 within 0.2%;
    #   * int32 packed-winner domain (scatter_slot_ratings' guard,
    #     with headroom for the pad bucket above n AND the coarsener's
    #     density-stepped slot doubling);
    #   * table (2 passes x num_slots per row) within ~6x the edge
    #     width: segment ops pay for their OUTPUT too, and on small
    #     shape-bucketed subgraphs (deep's bipartition coarseners) a
    #     table 30x the edge list costs more than the sorts it
    #     replaces (measured: +50% on extend-partition).
    scatter_reject = None
    if avg_degree > num_slots:
        scatter_reject = f"avg_degree={avg_degree:.1f}>slots={num_slots}"
    elif not (8 <= degree_skew <= 4096):
        scatter_reject = (
            f"degree_skew={degree_skew:.1f} outside [8, 4096]"
        )
    elif n * num_slots > (1 << 27):
        scatter_reject = f"n*slots={n * num_slots} past the int32 budget"
    elif 2 * n * num_slots > 12 * m_slots:
        scatter_reject = "slot table past 6x the edge width"
    if scatter_reject is None:
        return (
            "scatter",
            f"avg_degree={avg_degree:.1f}<=slots={num_slots}",
        )
    if row_spans:
        return "sort2", scatter_reject
    return "sort", f"{scatter_reject}; no row spans (sharded COO)"


# ---------------------------------------------------------------------------
# the scatter-add slot table (two-pass collision elimination)
# ---------------------------------------------------------------------------


def scatter_slot_ratings(
    owner: jax.Array,
    neighbor_label: jax.Array,
    edge_w: jax.Array,
    n_pad: int,
    num_slots: int,
    salt,
    valid: jax.Array | None = None,
    spans: Tuple[jax.Array, jax.Array] | None = None,
    label_space: int | None = None,
    joinable: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact-where-rated hashed rating rows via scatter-adds only.

    Every edge of one (node, label) pair hashes to the SAME slot, so a
    slot whose entries all carry one label holds that label's EXACT
    total connection weight after one segment-sum.  Contested slots
    (>= 2 distinct labels) are resolved by a hashed winner; the losing
    labels' edges are re-hashed under a second salt into a second
    table, where the game repeats.  Labels still contested after both
    passes stay unrated and flag their row.

    Returns (slot_label, slot_w, fully_rated):
      slot_label i32[n_pad, 2*num_slots]  rated label per slot (-1 empty)
      slot_w     ACC[n_pad, 2*num_slots]  exact connection weight
                                          (< 0: not joinable, below)
      fully_rated bool[n_pad]             every adjacent label was rated

    ``joinable`` (bool per edge) is where the caller says which rated
    clusters a row may join: False on the edges of an (owner, label)
    pair that is over its weight cap or outside the owner's community.
    All edges of one pair carry one value, and the owner's OWN label is
    always joinable (its weight is the row's w_own).  Such a pair
    competes for its slot, wins it and sends its losers on exactly as
    any other; only the weight of a slot it won reads minus the number
    of its edges instead of their sum.  Edge weights are >= 0 (0 on pad
    and sparsified-away edges), so slot_w < 0 says "rated, not
    joinable" and a joinable slot of total weight 0 stays apart from
    it.  The feasibility test thus rides the segment_sum that builds
    the table, decided per edge from what the caller fetches at edge
    width; best_from_slots never gathers at table width.

    ``valid`` masks buffer slots (delta rounds); pad/invalid slots are
    routed to an overflow segment so they can never pollute a row.
    ``spans=(start, end)`` are the owner rows' contiguous slot spans
    (CSR row_ptr on full rounds, the compacted buffer spans on delta
    rounds): when given, the fully_rated flag falls out of a streaming
    cumsum + span diff instead of an n-wide scatter.  Per-edge
    intermediates stay narrow: slot ids and the packed winner keys are
    single int32 lanes (label low bits, hashed key high bits), weights
    keep ACC_DTYPE throughout (dtypes.py policy).

    ``label_space`` is the exclusive upper bound of the LABEL domain
    when it differs from the ROW domain — the owner-sharded dist layout
    has n_loc rows rating GLOBAL cluster ids (n_pad-wide); clipping
    labels to the row count there would silently merge every remote
    label into one.  Default: the row domain (the shm layout).
    """
    if label_space is None:
        label_space = n_pad
    if n_pad * num_slots >= 2**30:
        raise ValueError("n_pad * num_slots must stay well inside int32")
    total = n_pad * num_slots
    label_bits = max(int(label_space - 1).bit_length(), 1)
    key_bits = 31 - label_bits
    if key_bits < 4:
        raise ValueError(
            f"label_space={label_space} leaves {key_bits} winner-key "
            "bits; use the sort engine at this scale"
        )
    lab_mask = jnp.int32((1 << label_bits) - 1)
    nb_c = jnp.clip(neighbor_label, 0, label_space - 1)
    ok = neighbor_label >= 0
    if valid is not None:
        ok = ok & valid
    rated_w = edge_w if joinable is None else jnp.where(joinable, edge_w, -1)

    def one_pass(pass_salt, active_edge):
        """One elimination pass over the masked edges.  Returns
        (slot_label, slot_w, edge_lost): the pass's (n, num_slots)
        table and the mask of edges whose label lost its slot."""
        slot = hash_u32(nb_c, pass_salt) % jnp.int32(num_slots)
        flat = jnp.where(
            active_edge, owner.astype(jnp.int32) * num_slots + slot, total
        )
        # winner of a contested slot in ONE segment-max: hashed key in
        # the high bits, the label itself in the low bits (tie-break by
        # larger label, deterministic)
        key = (
            (hash_u32(nb_c, pass_salt ^ 0x3779B97F) & ((1 << key_bits) - 1))
            << label_bits
        ) | nb_c
        win = jax.ops.segment_max(
            jnp.where(active_edge, key, -1), flat, num_segments=total + 1
        )[:total]
        win_label = jnp.where(win >= 0, win & lab_mask, -1)
        flat_c = jnp.clip(flat, 0, total - 1)
        is_win = active_edge & (win_label[flat_c] == nb_c)
        w = jax.ops.segment_sum(
            jnp.where(is_win, rated_w, 0).astype(ACC_DTYPE),
            flat,
            num_segments=total + 1,
        )[:total]
        edge_lost = active_edge & ~is_win
        return (
            win_label.reshape(n_pad, num_slots),
            w.reshape(n_pad, num_slots),
            edge_lost,
        )

    lab1, w1, lost1 = one_pass(salt, ok)
    lab2, w2, lost2 = one_pass(
        jnp.asarray(salt, jnp.int32) ^ jnp.int32(0x5851F42D), lost1
    )
    # a row is fully rated iff no edge's label remained contested after
    # the second pass (all of a label's edges lose together, so one
    # surviving loser edge == one unrated adjacent cluster)
    if spans is not None:
        # streaming: cumsum of the loser mask + row-span diff (no
        # scatter; the same trick as segments.neighbor_any_true)
        start, end = spans
        csum = _cumsum_minor(lost2.astype(ACC_DTYPE))
        csum0 = jnp.concatenate([jnp.zeros(1, dtype=csum.dtype), csum])
        D = lost2.shape[0]
        fully_rated = (
            csum0[jnp.clip(end, 0, D)] - csum0[jnp.clip(start, 0, D)]
        ) == 0
    else:
        # non-lost edges route to slot n_pad-1 with VALUE 0 (a max
        # no-op), so every row's flag — including n_pad-1's own — is
        # exact from this single scatter
        owner_c = jnp.clip(owner, 0, n_pad - 1)
        unrated = (
            jnp.zeros(n_pad, dtype=jnp.int32)
            .at[jnp.where(lost2, owner_c, n_pad - 1)]
            .max(lost2.astype(jnp.int32), mode="drop")
        )
        fully_rated = unrated == 0
    return (
        jnp.concatenate([lab1, lab2], axis=1),
        jnp.concatenate([w1, w2], axis=1),
        fully_rated,
    )


def best_from_slots(
    slot_label: jax.Array,
    slot_w: jax.Array,
    labels: jax.Array,
    tie_salt,
    label_range: Tuple[jax.Array, jax.Array] | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-node (best_label, best_w, w_own) from scatter slot tables.

    Element-wise over the table plus row reductions, nothing irregular:
    whether a rated cluster may be joined (weight cap, community) was
    decided per edge and is read off the sign of ``slot_w``
    (scatter_slot_ratings' ``joinable``; a table built without it has
    no negative weight and rates unconstrained, as two-hop wants).

    The feasibility chain and the tie-break are IDENTICAL to the sort
    engine's argmax_per_segment (max weight, then max hash_u32(label,
    tie_salt), then max label), so a fully-rated row picks the same
    cluster the sort engine would — the engine-equivalence contract.
    ``w_own`` is the row's exact connection to its own label (0 when
    the own label is absent; rows whose own label stayed contested are
    never fully rated, so callers bar them anyway).
    """
    own = labels[:, None]
    w_own = jnp.max(
        jnp.where(slot_label == own, slot_w, 0), axis=1
    )
    feas = (slot_label >= 0) & (slot_label != own) & (slot_w >= 0)
    if label_range is not None:
        lo, hi = label_range
        feas = feas & (slot_label >= lo) & (slot_label < hi)
    score = jnp.where(feas, slot_w, INT32_MIN)
    best_w = jnp.max(score, axis=1)
    has = best_w > INT32_MIN
    is_best = feas & (score == best_w[:, None])
    tb = hash_u32(slot_label, tie_salt)
    best_tb = jnp.max(jnp.where(is_best, tb, -1), axis=1)
    winner = is_best & (tb == best_tb[:, None])
    best = jnp.max(jnp.where(winner, slot_label, -1), axis=1)
    return (
        jnp.where(has, best, -1),
        jnp.where(has, best_w, INT32_MIN),
        w_own,
    )


# Re-exports: the dense refinement core lives in segments.py for
# historical import-cycle reasons; rating.py is its public home so LP,
# Jet and the dist kernels share one rating surface.
__all__ = [
    "ENGINES",
    "DEFAULT_NUM_SLOTS",
    "SCATTER_FALLBACK_FRAC",
    "select_engine",
    "scatter_slot_ratings",
    "best_from_slots",
    "dense_block_ratings",
    "best_from_dense",
]
