"""Jet refinement on device.

Analog of kaminpar-shm/refinement/jet/jet_refiner.cc, itself an
implementation of "Jet: Multilevel Graph Partitioning on GPUs" (Gilbert et
al.) — the reference's most TPU-amenable refiner, and here it runs as a
fully fused device loop.  Per iteration (jet_refiner.cc:100-214):

  1. find:     every unlocked border node picks its best external block;
               it becomes a candidate if best_gain > -floor(temp * conn_own)
               (the gain temperature admits slightly-negative moves);
  2. filter    ("afterburner"): each candidate's gain is re-evaluated
               assuming every neighbor with strictly better (gain, id) order
               is already at its tentative destination; only candidates with
               positive adjusted gain are locked in;
  3. execute:  apply locked moves in bulk;
  4. rebalance with the overload balancer;
  5. keep the best-cut partition seen; stop after `num_fruitless_iterations`
     without sufficient improvement (fruitless_threshold) and roll back.

The candidate/filter/execute steps are already bulk-synchronous in the
reference (it is a GPU algorithm run on CPU threads); the TPU version
expresses them as whole-graph segment reductions, and the iteration loop is
a lax.while_loop so an entire Jet pass is one XLA program.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..context import JetRefinementContext
from ..graphs.csr import DeviceGraph
from ..telemetry import progress as progress_mod
from .balancer import overload_balance_round
from .metrics import edge_cut
# the dense rate+argmax core is shared with LP through ops/rating.py —
# one public home for every rating engine (see its module docstring)
from .rating import best_from_dense
from .segments import (
    ACC_DTYPE,
    INT32_MIN,
    MAX_FUSED_EDGE_SLOTS,
    count_conn_engine,
    csr_block_ratings,
    expand_active_rows,
    packed_afterburner_gain,
    packed_afterburner_gain_rows,
    prune_candidates_to_budget,
)

# The gate and both buffers read a level's power-of-two bucket
# (DeviceGraph.m_bucket: m_pad itself on every level but the input
# graph's, which uploads at an eighth of its bucket).
# From this many edge slots on, Jet never leaves its row afterburner
# (_rows_filter) and takes the smaller coarse iteration budget (mirrors
# ops/lp.DELTA_MIN_EDGE_SLOTS): an iteration whose candidates' rows fit
# CONN_DELTA_DIVISOR's buffer runs through it, one whose rows overflow
# it PRUNES its candidates to a buffer of m_bucket // 4 slots
# (prune_candidates_to_budget: a different move set, a different cut,
# where the prune drops any) and runs through that.  Under the gate
# nothing is pruned: an iteration runs the same row afterburner through
# CONN_DELTA_DIVISOR's buffer when its candidates' rows fit it, and the
# edge-wide one (_edges_filter) when they do not; the partition is the
# same either way.  A reconcile through _conn_step does not ask the
# gate: that takes CONN_DELTA_DIVISOR's buffer at every size.
DELTA_MIN_EDGE_SLOTS = 1 << 22

# Under the gate an iteration runs its afterburner over the candidates'
# CSR rows while their degrees sum to at most m_bucket // CONN_DELTA_DIVISOR
# slots; _conn_step updates the conn table from the movers' rows through
# a buffer of the same width, and rebuilds it otherwise, on either side
# of the gate.  Either costs per slot of the buffer, full or not: 8
# costs nearly a k = 2 rebuild, 4 twice one, 32 misses half of R-MAT's
# coarse iterations at k = 16 (PERF.md, PR 29, 31 and 35).
CONN_DELTA_DIVISOR = 16

# Largest dense (n_pad, k) conn table Jet will materialize (int32
# entries; 2^28 = 1 GiB).  Above it jet_refine degrades to LP
# refinement rounds (see entry point).
JET_DENSE_MAX_ENTRIES = 1 << 28


def _delta_slots(graph: DeviceGraph) -> int | None:
    m_slots = graph.m_bucket
    if m_slots < DELTA_MIN_EDGE_SLOTS:
        return None
    return m_slots // 4


def iteration_path(graph: DeviceGraph, k: int) -> str:
    """Which iteration `jet_refine` runs on `graph` at `k`, from the shapes
    alone: `jet-rows` (the afterburner always over the candidates' rows:
    through `_conn_slots` in the iterations where they fit it, pruned to
    and through `_delta_slots` in the others: the `wide` column of the
    progress series says which), `jet-edges` (nothing pruned; the
    afterburner over the candidates' rows in the iterations where they
    fit `_conn_slots`, edge-wide in the others: the `rows` column says
    which; the name follows the shapes) or `jet-lp`
    (no dense table: LP refinement rounds).  The refiner names a timer
    scope after it, so a trace of a run with telemetry off still says
    which Jet a level ran."""
    if graph.n_bucket * k > JET_DENSE_MAX_ENTRIES:
        return "jet-lp"
    return "jet-edges" if _delta_slots(graph) is None else "jet-rows"


def _conn_slots(graph: DeviceGraph) -> int:
    """Row-buffer width of a _conn_step reconcile, whatever the path, and
    of the row afterburner wherever the candidates' rows fit it."""
    return graph.m_bucket // CONN_DELTA_DIVISOR


def _full_ratings(graph: DeviceGraph, part: jax.Array, k: int) -> jax.Array:
    """Full dense (n_pad, k) rating table of `part`."""
    return csr_block_ratings(graph, part, k)


def _conn_cut(
    graph: DeviceGraph, conn: jax.Array, part: jax.Array, wdeg: jax.Array,
    k: int,
) -> jax.Array:
    """Exact cut of `part` from its conn table:
    sum over real nodes of (weighted degree - connection to own block),
    halved (each cut edge counts at both endpoints)."""
    is_real = jnp.arange(graph.n_pad, dtype=jnp.int32) < graph.n
    conn_own = jnp.take_along_axis(
        conn, jnp.clip(part, 0, k - 1)[:, None], axis=1
    )[:, 0]
    return jnp.sum(
        jnp.where(is_real, wdeg - conn_own, 0).astype(ACC_DTYPE)
    ) // 2


def _scatter_conn_delta_cols(
    conn: jax.Array,
    old_b: jax.Array,
    new_b: jax.Array,
    dst_b: jax.Array,
    w_b: jax.Array,
    k: int,
    n_pad: int,
) -> jax.Array:
    """Apply a bulk-move delta to the dense (n, k) connection table from
    prepared per-slot columns: for each edge (u, v) with u moved a->b,
    conn[v, a] -= w and conn[v, b] += w.  Exact integer arithmetic — the
    table stays bitwise equal to a full rebuild.  Callers zero w_b on
    edges whose owner did not move; `old_b`/`new_b` are the owner's
    before/after blocks PER SLOT (already gathered by the caller)."""
    flat_old = dst_b * k + jnp.clip(old_b, 0, k - 1)
    flat_new = dst_b * k + jnp.clip(new_b, 0, k - 1)
    flat_conn = conn.reshape(-1)
    flat_conn = flat_conn.at[flat_old].add(-w_b, mode="drop")
    flat_conn = flat_conn.at[flat_new].add(w_b, mode="drop")
    return flat_conn.reshape(n_pad, k)


def _conn_update_rows(
    graph: DeviceGraph,
    conn: jax.Array,
    part_before: jax.Array,
    part_after: jax.Array,
    k: int,
    dslots: int,
) -> jax.Array:
    """Expand the changed nodes' CSR rows and apply the conn-table delta
    (see _scatter_conn_delta_cols).  The owner's before/after blocks ride
    ONE gather, bit-packed as before * k + after (both < k, so the
    product stays far inside int32)."""
    n_pad = graph.n_pad
    changed = part_before != part_after
    owner_c, _, edge_id, valid, start, end = expand_active_rows(
        graph.row_ptr, graph.degrees, changed, dslots
    )
    eid = jnp.clip(edge_id, 0, graph.src.shape[0] - 1)
    dst_b = jnp.where(valid, graph.dst[eid], n_pad - 1)
    w_b = jnp.where(valid, graph.edge_w[eid], 0).astype(ACC_DTYPE)
    pb_c = jnp.clip(part_before, 0, k - 1)
    pa_c = jnp.clip(part_after, 0, k - 1)
    pba = (pb_c * k + pa_c)[owner_c]
    return _scatter_conn_delta_cols(
        conn, pba // k, pba % k, dst_b, w_b, k, n_pad
    )


def _conn_step(
    graph: DeviceGraph,
    conn: jax.Array,
    part_before: jax.Array,
    part_after: jax.Array,
    k: int,
    conn_slots: int,
) -> Tuple[jax.Array, jax.Array]:
    """The conn table of `part_after` from the table of `part_before`:
    the movers' rows re-scattered when their degrees sum to at most
    `conn_slots`, a full rebuild otherwise (bitwise the same table
    either way).  Returns (conn, 1 if the delta was taken else 0)."""
    if conn_slots == 0:
        return _full_ratings(graph, part_after, k), jnp.int32(0)
    # degree total <= m_pad < 2^31 (device layout)
    # tpulint: disable=R3
    changed_edges = jnp.sum(
        jnp.where(part_before != part_after, graph.degrees, 0),
        dtype=jnp.int32,
    )
    fits = changed_edges <= conn_slots
    new_conn = lax.cond(
        fits,
        lambda args: _conn_update_rows(graph, *args, k, conn_slots),
        lambda args: _full_ratings(graph, args[2], k),
        (conn, part_before, part_after),
    )
    return new_conn, fits.astype(jnp.int32)


def _find_moves(
    graph: DeviceGraph,
    conn: jax.Array,
    part: jax.Array,
    lock: jax.Array,
    k: int,
    gain_temp: jax.Array,
    salt: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Jet's find step (jet_refiner.cc:104-131) from the dense (n, k)
    rating table of `part`: one segment_sum, no edge-list sort (the
    gain-cache strategy Jet's paper assumes; caps checked by the
    balancer, so require_fit=False like the reference's candidate step).
    Returns (best external block, gain of moving there, connection to
    the own block, candidate): every unlocked real border node whose
    gain is above -floor(gain_temp * conn_own) is a candidate."""
    best, best_conn, conn_own = best_from_dense(
        conn, part, jnp.zeros((k,), ACC_DTYPE), graph.node_w,
        jnp.zeros((k,), ACC_DTYPE), salt, require_fit=False,
    )
    gain = best_conn - conn_own
    threshold = -jnp.floor(gain_temp * conn_own.astype(jnp.float32)).astype(
        jnp.int32
    )
    is_real = jnp.arange(graph.n_pad, dtype=jnp.int32) < graph.n
    candidate = is_real & (best >= 0) & (lock == 0) & (gain > threshold)
    return best, gain, conn_own, candidate


def _rows_filter(
    graph: DeviceGraph,
    conn: jax.Array,
    part: jax.Array,
    next_part: jax.Array,
    gain: jax.Array,
    candidate: jax.Array,
    k: int,
    slots: int,
) -> Tuple[jax.Array, jax.Array]:
    """The afterburner over the candidates' CSR rows, laid into a buffer
    of `slots` slots that the caller knows they fit, and the conn table
    of the accepted moves from the same buffer.  Returns (accept, conn).

    Accepted movers are a subset of the candidates, whose rows the
    afterburner has expanded and gathered: the conn update reuses
    (owner_c, dst_b, w_b) and the (from, to) block columns the
    afterburner returns (bit-packed endpoint metadata, one gather per
    endpoint), so its only new irregular op is the accept gather.  Edges
    of rejected candidates contribute weight 0."""
    n_pad = graph.n_pad
    owner_c, _, edge_id, valid, start, end = expand_active_rows(
        graph.row_ptr, graph.degrees, candidate, slots
    )
    eid = jnp.clip(edge_id, 0, graph.src.shape[0] - 1)
    dst_b = jnp.where(valid, graph.dst[eid], n_pad - 1)
    w_b = jnp.where(valid, graph.edge_w[eid], 0)
    adj_gain, from_u, to_u = packed_afterburner_gain_rows(
        owner_c, dst_b, w_b, start, end,
        part, next_part, gain, candidate, k,
    )
    accept = candidate & (adj_gain > 0)
    acc_o = accept[owner_c]
    w_m = jnp.where(acc_o, w_b, 0).astype(ACC_DTYPE)
    new_b = jnp.where(acc_o, to_u, from_u)
    return accept, _scatter_conn_delta_cols(
        conn, from_u, new_b, dst_b, w_m, k, n_pad
    )


def _edges_filter(
    graph: DeviceGraph,
    part: jax.Array,
    next_part: jax.Array,
    gain: jax.Array,
    candidate: jax.Array,
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """The afterburner over the whole edge array (two edge-wide passes,
    one of them the `meta[dst]` gather), for candidates whose rows
    overflow _conn_slots, and the conn table rebuilt.  Returns (accept,
    conn).  No _conn_step here: where the candidates overflow the buffer
    the movers do too (0 of 110 overflowing iterations of `rmat-s16.k16`
    at --seed 1-3 had movers that fit; PERF.md, PR 35), and a third row
    expansion in every _jet_chunk (beside the row filter's and the
    balancer's) costs code on the device and seconds of lowering."""
    adj_gain = packed_afterburner_gain(
        graph.src, graph.dst, graph.edge_w, graph.row_ptr,
        part, next_part, gain, candidate, k,
    )
    accept = candidate & (adj_gain > 0)
    return accept, _full_ratings(
        graph, jnp.where(accept, next_part, part), k
    )


def _candidate_slots(graph: DeviceGraph, candidate: jax.Array) -> jax.Array:
    """Edge slots the candidates' CSR rows take (an n-wide reduce)."""
    # degree total <= m_pad < 2^31 (device layout)
    # tpulint: disable=R3
    return jnp.sum(jnp.where(candidate, graph.degrees, 0), dtype=jnp.int32)


def _gated_rows_filter(
    graph: DeviceGraph,
    conn: jax.Array,
    part: jax.Array,
    best: jax.Array,
    gain: jax.Array,
    candidate: jax.Array,
    k: int,
    salt: jax.Array,
    dslots: int,
    conn_slots: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The row afterburner past the gate: through `conn_slots` when the
    candidates' degrees sum to at most that, else through `dslots` after
    pruning the candidates to the best-gain subset that fits it.  The
    prune keeps a set that fits as it is, and its salt is derived, not
    drawn: both branches give the same bits where both hold the rows.
    Returns (accept, conn, pruned, wide) with wide 1 where the
    candidates were pruned to and filtered through `dslots`."""

    def narrow(conn, candidate):
        next_part = jnp.where(candidate, best, part)
        accept, jet_conn = _rows_filter(
            graph, conn, part, next_part, gain, candidate, k, conn_slots
        )
        return accept, jet_conn, jnp.zeros((), ACC_DTYPE)

    def pruned_wide(conn, candidate):
        kept = prune_candidates_to_budget(
            candidate, gain, graph.degrees, salt ^ 0x5BD1E995, dslots
        )
        next_part = jnp.where(kept, best, part)
        accept, jet_conn = _rows_filter(
            graph, conn, part, next_part, gain, kept, k, dslots
        )
        return accept, jet_conn, jnp.sum(candidate & ~kept, dtype=ACC_DTYPE)

    if conn_slots == 0:
        return (*pruned_wide(conn, candidate), jnp.int32(1))
    wide = _candidate_slots(graph, candidate) > conn_slots
    return (*lax.cond(wide, pruned_wide, narrow, conn, candidate),
            wide.astype(jnp.int32))


def _jet_iteration(
    graph: DeviceGraph,
    part: jax.Array,
    lock: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    gain_temp: jax.Array,
    salt: jax.Array,
    balancer_rounds: int,
    wdeg: jax.Array | None = None,
    conn: jax.Array | None = None,
) -> Tuple[jax.Array, ...]:
    """One Jet move round.  Returns (new_part, new_lock, ext_sum,
    new_conn, conn_delta, pruned, rows, wide) where ext_sum = sum over real
    nodes of (weighted degree - connection to own block) in the INPUT
    partition — the rating table
    gives the input partition's edge cut for free as ext_sum / 2, saving
    the driver a separate edge-wide cut pass per iteration.  ext_sum =
    2*cut stays in int32 exactly when edge_cut itself would (unlike a
    total-edge-weight sum, which overflows first on heavy graphs).
    `wdeg` is the static per-node weighted degree; when None, ext_sum is
    returned as 0 (the caller does not use it).

    `conn` is the incrementally-maintained dense (n, k) connection table
    for the INPUT partition (the gain cache Jet's paper assumes).  When
    None it is built from scratch; the returned new_conn matches the
    OUTPUT partition bitwise either way.  After the Jet moves it comes
    from the candidates' rows where the afterburner ran over them and
    from a full rebuild where it ran edge-wide; after the balancer's from
    the movers' rows or a rebuild (lax.cond picks, see _conn_step).
    conn_delta counts the iteration's two reconciles that re-scattered
    rows: 0, 1 or 2; pruned the candidates prune_candidates_to_budget
    dropped (0 under the gate, which has no budget, and where the
    candidates' rows fit _conn_slots); rows is 1 where the afterburner
    ran over the candidates' rows (always past the gate; under it where
    they fit _conn_slots) and 0 where it ran edge-wide; wide is 1 where
    the candidates were pruned to and filtered through _delta_slots (past
    the gate, where their rows overflow _conn_slots) and 0 elsewhere."""
    dslots = _delta_slots(graph)
    conn_slots = _conn_slots(graph)

    if conn is None:
        conn = _full_ratings(graph, part, k)
    best, gain, conn_own, candidate = _find_moves(
        graph, conn, part, lock, k, gain_temp, salt
    )
    if wdeg is not None:
        is_real = jnp.arange(graph.n_pad, dtype=jnp.int32) < graph.n
        ext_sum = jnp.sum(
            jnp.where(is_real, wdeg - conn_own, 0).astype(ACC_DTYPE)
        )
    else:
        ext_sum = jnp.int32(0)

    # ---- filter: afterburner (jet_refiner.cc:133-170), execute
    # (:172-183), and the rating table kept across the jet moves ----
    # Only edges of CANDIDATE rows contribute to the filter, so it runs
    # over those rows wherever a buffer holds them (_rows_filter: every
    # pass at buffer width, the table updated from the same buffer).
    # lax.cond takes the rows through _conn_step's buffer when the
    # candidates' degrees sum to at most its width (an n-wide reduce).
    # Where they do not: past the gate the candidates are PRUNED to the
    # best-gain subset whose rows fit m_pad // 4 (two-stage candidate
    # pruning; pruned candidates compete again next iteration) and
    # filtered through that buffer; under it nothing is pruned and the
    # filter runs edge-wide with a rebuild.  The prune keeps a set that
    # fits as it is, _rows_filter gives the same integers at any width
    # that holds the rows, and adj_gain of a non-candidate differs
    # between the row and the edge-wide filter and is masked by
    # `candidate` in both (a candidate's is the same integers summed
    # over the same row, and the packed / exact guard reads candidates'
    # gains only): the partition and the table are bitwise the same
    # whichever ran.
    pruned = jnp.int32(0)
    wide = jnp.int32(0)
    if dslots is not None:
        rows = jnp.bool_(True)
        accept, jet_conn, pruned, wide = _gated_rows_filter(
            graph, conn, part, best, gain, candidate, k, salt, dslots,
            conn_slots,
        )
        # every accepted move is a kept candidate's, bound for `best`
        next_part = best
    else:
        next_part = jnp.where(candidate, best, part)
        filter_args = (part, next_part, gain, candidate)
        if conn_slots == 0:
            rows = jnp.bool_(False)
            accept, jet_conn = _edges_filter(graph, *filter_args, k)
        else:
            rows = _candidate_slots(graph, candidate) <= conn_slots
            accept, jet_conn = lax.cond(
                rows,
                lambda conn, *args: _rows_filter(
                    graph, conn, *args, k, conn_slots
                ),
                lambda conn, *args: _edges_filter(graph, *args, k),
                conn, *filter_args,
            )
    # the row filter serves the Jet moves' reconcile from its buffer
    rows = rows.astype(jnp.int32)
    new_part = jnp.where(accept, next_part, part)
    new_lock = accept.astype(jnp.int32)  # moved nodes rest next iteration

    # ---- rebalance (jet_refiner.cc:185-187) ----
    # while_loop, not fori: Jet iterations usually keep the partition
    # feasible, and a false condition skips the balancer body entirely.
    # Balancer rounds rate from the post-jet conn table — STALE within
    # the loop (the reference's balancer PQs also run on cached gains);
    # block-weight caps are recomputed fresh per round, so feasibility is
    # exact, and the table itself is reconciled ONCE after the loop from
    # the partition diff.  No edge-wide work anywhere in the loop.
    def _overload(p):
        bw = jax.ops.segment_sum(
            graph.node_w.astype(ACC_DTYPE), p, num_segments=k
        )
        return jnp.sum(
            jnp.maximum(bw - max_block_weights.astype(ACC_DTYPE), 0)
        )

    def bal_cond(state):
        i, p, moved, over = state
        return (i < balancer_rounds) & (over > 0) & (moved != 0)

    def bal_body(state):
        i, p, _, _ = state
        s = (salt + i * 7919) & 0x7FFFFFFF
        p2, moved = overload_balance_round(
            graph, p, k, max_block_weights, s, conn=jet_conn
        )
        return (i + 1, p2, moved, _overload(p2))

    _, bal_part, _, _ = lax.while_loop(
        bal_cond,
        bal_body,
        (jnp.int32(0), new_part, jnp.int32(1), _overload(new_part)),
    )
    # reconcile the table only when the balancer actually moved something
    # (the common case is a feasible partition and zero balancer rounds)
    new_conn, bal_delta = lax.cond(
        jnp.any(bal_part != new_part),
        lambda args: _conn_step(graph, *args, k, conn_slots),
        lambda args: (args[0], jnp.int32(0)),
        (jet_conn, new_part, bal_part),
    )
    return (bal_part, new_lock, ext_sum, new_conn, rows + bal_delta, pruned,
            rows, wide)


@partial(
    jax.jit,
    static_argnames=("k", "max_fruitless", "balancer_rounds"),
)
def _jet_chunk(
    graph: DeviceGraph,
    part: jax.Array,
    lock: jax.Array,
    best: jax.Array,
    best_cut: jax.Array,
    fruitless: jax.Array,
    conn: jax.Array,
    i0: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    gain_temp: jax.Array,
    fruitless_threshold: jax.Array,
    seed: jax.Array,
    rnd: jax.Array,
    limit: jax.Array,
    wdeg: jax.Array,
    max_fruitless: int,
    balancer_rounds: int,
    stats=None,
):
    """A bounded chunk of Jet iterations in one device program.

    Jet used to run all (up to 64) iterations inside a single fused
    while_loop; at ~33M-edge shapes the multi-minute single launch
    reproducibly killed the TPU worker.  The host now drives the
    iteration loop in chunks, reading back the fruitless counter between
    chunks (one scalar sync per `chunk` iterations).

    `stats` is an optional progress buffer (telemetry/progress.py),
    row-indexed by the GLOBAL iteration `i0 + j` so it threads across
    chunks unchanged; None leaves the jaxpr identical to the
    uninstrumented loop."""

    def is_feasible(p):
        bw = jax.ops.segment_sum(
            graph.node_w.astype(ACC_DTYPE), p, num_segments=k
        )
        return jnp.all(bw <= max_block_weights.astype(ACC_DTYPE))

    def iter_cond(state):
        j, fruitless, part, lock, best, best_cut, conn, stats = state
        # `limit` is traced, so a short remainder chunk reuses the same
        # compiled program instead of triggering a second trace
        return (j < limit) & (fruitless < max_fruitless)

    def iter_body(state):
        j, fruitless, part, lock, best, best_cut, conn, stats = state
        i = i0 + j
        salt = (
            seed.astype(jnp.int32) * 31321 + rnd * 2221 + i * 1566083941
        ) & 0x7FFFFFFF
        (new_part, lock, ext_sum, conn, conn_delta, pruned, rows,
         wide) = _jet_iteration(
            graph,
            part,
            lock,
            k,
            max_block_weights,
            gain_temp,
            salt,
            balancer_rounds,
            wdeg=wdeg,
            conn=conn,
        )
        # snapshot the state ENTERING this iteration (its cut falls out
        # of the rating); the state leaving the round's final iteration
        # is closed out by _jet_round_close in the driver
        cut = ext_sum // 2
        # while best_cut is still the no-feasible-partition sentinel,
        # "improvement" means finding the first feasible partition —
        # comparing against the sentinel would defeat the fruitless
        # early-exit entirely
        has_best = best_cut < jnp.iinfo(ACC_DTYPE).max
        improved_enough = jnp.where(
            has_best,
            (best_cut - cut).astype(jnp.float32)
            > (1.0 - fruitless_threshold)
            * jnp.abs(best_cut).astype(jnp.float32),
            is_feasible(part),
        )
        fruitless = jnp.where(improved_enough, 0, fruitless + 1)
        is_best = (cut <= best_cut) & is_feasible(part)
        best = jnp.where(is_best, part, best)
        best_cut = jnp.where(is_best, cut, best_cut)
        if stats is not None:  # trace-time guard (None adds no carry)
            # cut of the state entering iteration i; moved = locked
            # (accepted) movers of this iteration; fruitless after the
            # improvement test — the convergence picture Jet's paper
            # plots (and the reference's statistics registry prints);
            # conn_delta = conn-table reconciles served by the movers'
            # rows instead of a rebuild (0..2); pruned = candidates the
            # row budget dropped (they compete again next iteration);
            # rows = 1 where the afterburner ran over the candidates'
            # rows, 0 where it ran edge-wide; wide = 1 where they were
            # pruned to and filtered through _delta_slots
            stats = progress_mod.record(
                stats, i, cut, jnp.sum(lock), fruitless, conn_delta, pruned,
                rows, wide,
            )
        return (j + 1, fruitless, new_part, lock, best, best_cut, conn,
                stats)

    _, fruitless, part, lock, best, best_cut, conn, stats = lax.while_loop(
        iter_cond,
        iter_body,
        (jnp.int32(0), fruitless, part, lock, best, best_cut, conn, stats),
    )
    return part, lock, best, best_cut, fruitless, conn, stats


@partial(jax.jit, static_argnames=("k",))
def _jet_round_close(
    graph: DeviceGraph,
    part: jax.Array,
    best: jax.Array,
    best_cut: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    conn: jax.Array | None = None,
    wdeg: jax.Array | None = None,
):
    """Evaluate the round's final (post-move) state once: the in-loop
    snapshots cover every state except the last one.  When the caller
    passes the maintained conn table (which matches `part` exactly —
    every in-loop update is bitwise-equal to a rebuild), the cut falls
    out as sum(wdeg - conn[i, part[i]]) / 2 instead of an edge-wide
    pass (0.68 s -> ~0.1 s at 33.5M slots)."""
    from .metrics import is_feasible as feasibility

    if conn is not None:
        cut = _conn_cut(graph, conn, part, wdeg, k)
    else:
        cut = edge_cut(graph, part)
    is_best = (cut <= best_cut) & feasibility(graph, part, max_block_weights)
    return (
        jnp.where(is_best, part, best),
        jnp.where(is_best, cut, best_cut),
    )


@partial(jax.jit, static_argnames=("k",))
def _jet_build_conn(graph: DeviceGraph, part: jax.Array, k: int):
    """Fresh dense rating table — run once per Jet round (the in-round
    table is maintained incrementally; the round-end rollback to `best`
    invalidates it)."""
    return _full_ratings(graph, part, k)


@partial(jax.jit, static_argnames=("k",))
def _jet_init(graph: DeviceGraph, partition: jax.Array, k: int,
              max_block_weights: jax.Array, wdeg: jax.Array):
    """Clip the input partition, build the round-0 conn table, and derive
    the starting cut FROM the table (one segment_sum instead of a
    separate edge-wide cut pass — the table is needed anyway)."""
    part0 = jnp.clip(partition, 0, k - 1).astype(jnp.int32)
    bw = jax.ops.segment_sum(
        graph.node_w.astype(ACC_DTYPE), part0, num_segments=k
    )
    feasible = jnp.all(bw <= max_block_weights.astype(ACC_DTYPE))
    conn = _jet_build_conn(graph, part0, k)  # nested jit inlines
    cut = _conn_cut(graph, conn, part0, wdeg, k)
    # snapshots track the best FEASIBLE cut; an infeasible input (e.g.
    # everything in one block, cut 0) must not pin the snapshot
    best_cut0 = jnp.where(feasible, cut, jnp.iinfo(ACC_DTYPE).max)
    return part0, best_cut0, conn


def _jet_refine_impl(
    graph: DeviceGraph,
    partition: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    seed: jax.Array,
    initial_gain_temp,
    final_gain_temp,
    fruitless_threshold,
    num_rounds: int,
    max_iterations: int,
    max_fruitless: int,
    balancer_rounds: int,
    chunk: int = 4,
) -> jax.Array:
    # static per-node weighted degree (one streaming pass per refine
    # call, via the CSR row spans): each iteration's rating table then
    # yields the visited partition's exact cut as sum(wdeg - conn_own)/2
    # — no per-iteration cut pass
    csum = jnp.cumsum(graph.edge_w.astype(ACC_DTYPE))
    csum0 = jnp.concatenate([jnp.zeros(1, dtype=csum.dtype), csum])
    row_ptr = jnp.clip(graph.row_ptr, 0, graph.edge_w.shape[0])
    wdeg = csum0[row_ptr[1:]] - csum0[row_ptr[:-1]]
    part, best_cut, conn = _jet_init(
        graph, partition, k, max_block_weights, wdeg
    )
    best = part
    # scale the iteration chunk down with edge count so each launch
    # stays short (see segments.MAX_FUSED_EDGE_SLOTS)
    m_bucket = graph.m_bucket
    if m_bucket > MAX_FUSED_EDGE_SLOTS:
        chunk = 1
    elif m_bucket > MAX_FUSED_EDGE_SLOTS // 2:
        chunk = min(chunk, 2)
    rec = progress_mod.capture()
    for rnd in range(num_rounds):
        if num_rounds > 1:
            gain_temp = initial_gain_temp + (
                final_gain_temp - initial_gain_temp
            ) * rnd / max(num_rounds - 1, 1)
        else:
            gain_temp = initial_gain_temp
        lock = jnp.zeros(graph.n_pad, dtype=jnp.int32)
        fruitless = jnp.int32(0)
        if conn is None:
            # only needed on round 0 and after a rollback — the in-round
            # table is maintained incrementally and stays valid across
            # rounds whenever the round ended on its best partition
            conn = _jet_build_conn(graph, part, k)
        # per-round progress buffer, row-indexed by the global iteration
        # so it rides across host-driven chunks without a host pull
        stats = progress_mod.new_buffer(max_iterations, 7) if rec else None
        t0 = progress_mod.now()
        i = 0
        closed = False
        while i < max_iterations:
            part, lock, best, best_cut, fruitless, conn, stats = _jet_chunk(
                graph, part, lock, best, best_cut, fruitless, conn,
                jnp.int32(i), k, max_block_weights,
                jnp.float32(gain_temp), jnp.float32(fruitless_threshold),
                seed, jnp.int32(rnd),
                jnp.int32(min(chunk, max_iterations - i)), wdeg,
                max_fruitless, balancer_rounds, stats,
            )
            i += chunk
            # the readback is a blocking device sync; skip it when the
            # fruitless early-exit is disabled so chunks enqueue
            # back-to-back
            if max_fruitless < max_iterations and int(fruitless) >= max_fruitless:
                # the in-loop snapshots lag one iteration; before giving
                # up, evaluate the (uncounted) final state — if it just
                # improved the best cut, the plateau was illusory and
                # the round keeps going (when iterations remain)
                prev_best = int(best_cut)
                best, best_cut = _jet_round_close(
                    graph, part, best, best_cut, k, max_block_weights,
                    conn=conn, wdeg=wdeg,
                )
                closed = True
                if int(best_cut) < prev_best and i < max_iterations:
                    fruitless = jnp.int32(0)
                    closed = False
                    continue
                break
        if not closed:
            # close out the round's final (post-move, unrated) state
            best, best_cut = _jet_round_close(
                graph, part, best, best_cut, k, max_block_weights,
                conn=conn, wdeg=wdeg,
            )
        if rec:
            # ONE host pull per round, after the loop exited (the chunk
            # driver's fruitless readback already synced the stream)
            progress_mod.emit(
                "jet",
                ("cut", "moved", "fruitless", "conn_delta", "pruned", "rows",
                 "wide"),
                stats, t0, round=rnd, best_cut=int(best_cut),
            )
        # rollback to best (jet_refiner.cc:221-227): the round continues
        # from the best partition seen
        if bool(jnp.any(part != best)):
            conn = None  # table matches `part`, not the rolled-back best
        part = best
    return best


def jet_refine(
    graph: DeviceGraph,
    partition: jax.Array,
    k: int,
    max_block_weights: jax.Array,
    seed: jax.Array,
    ctx: JetRefinementContext,
    level: int = 0,
    num_levels: int = 1,
    balancer_rounds: int = 4,
) -> jax.Array:
    """Jet refinement entry point; picks coarse/fine temperatures by level
    (jet_refiner.cc:40-49: every level except the finest counts as coarse)."""
    if iteration_path(graph, k) == "jet-lp":
        # huge k: the dense (n, k) conn table Jet's incremental machinery
        # rides would not fit HBM (16 GB at n=1M, k=4096).  Degrade to
        # bulk-synchronous LP refinement rounds — the sort2 rating engine
        # is k-independent and the afterburner keeps gains exact — so the
        # strong preset completes at any k instead of OOMing (the
        # reference's large-k configs likewise swap refiner strategy,
        # gains/compact_hashing_gain_cache.h:34 lineage).
        from .lp import LPConfig, lp_refine

        cfg = LPConfig(
            num_iterations=8,
            participation=1.0,
            allow_tie_moves=False,
            use_active_set=True,
            refinement=True,
        )
        return lp_refine(graph, partition, k, max_block_weights, seed, cfg)
    is_coarse = level > 0
    if is_coarse:
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
    # auto iteration budget: an iteration costs ~105 ns per edge SLOT on
    # v5e regardless of level (profiled at 0.26M..33M slots), and coarse
    # RMAT levels keep millions of edges — a 64-iteration coarse budget
    # was the single largest cost of the whole pipeline (~75 s per coarse
    # level at 4M slots).  Most of the cut gain arrives early: on the
    # medium RMAT bench 8 fine iters matches 16 within ±0.1% cut at half
    # the cost (and 32 was measurably worse than 16); coarse levels get
    # 12 — half as much again (they set up the solution structure).
    # 12 is the fruitless limit: of a budget of 16 the last four ran
    # only where one of the first four had improved the cut by 0.1 %,
    # so a coarse call did 12 to 16 iterations by the luck of the seed
    # (88-96 a request on a 131k-node mesh, 9 % of Jet's seconds) and
    # read the counter back after every chunk to find out.  At 12 every
    # coarse call does the same work and no chunk waits for the host;
    # the mesh's cut over 40 seeds is the same (mean 4,251 against
    # 4,255) and R-MAT's cut after every refiner call (three seeds at
    # k = 16; CPU, the arithmetic is integer; PERF.md, PR 26).
    # Above the large-graph boundary (the delta-round threshold) the
    # coarse budget is 8: measured on the 10M bench, coarse 8 costs
    # +0.2% cut for -18% total wall (140 s -> 115 s warm).
    if ctx.num_iterations > 0:
        max_iterations = ctx.num_iterations
    elif is_coarse:
        max_iterations = (
            8 if graph.m_bucket >= DELTA_MIN_EDGE_SLOTS else 12
        )
    else:
        max_iterations = 8
    max_fruitless = (
        ctx.num_fruitless_iterations
        if ctx.num_fruitless_iterations > 0
        else 2**30
    )
    count_conn_engine(graph, k)
    return _jet_refine_impl(
        graph,
        partition,
        k,
        max_block_weights,
        seed,
        jnp.float32(t0),
        jnp.float32(t1),
        jnp.float32(ctx.fruitless_threshold),
        int(rounds),
        int(max_iterations),
        int(max_fruitless),
        int(balancer_rounds),
    )
