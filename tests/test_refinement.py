"""Balancer / Jet / FM refinement tests (analog of the reference's
refinement unit coverage, e.g. gain_cache_test.cc validating gains against
recomputation)."""

import hashlib
import os
import re
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from mesh_reference import delaunay_mesh, recursive_coordinate_bisection
from kaminpar_tpu.context import FMRefinementContext, JetRefinementContext
from kaminpar_tpu.graphs import device_graph_from_host, factories
from kaminpar_tpu.graphs.host import from_edge_list
from kaminpar_tpu.ops import metrics
from kaminpar_tpu.ops.balancer import overload_balance, underload_balance
from kaminpar_tpu.ops.jet import jet_refine
from kaminpar_tpu.refinement.fm import fm_refine_host


def _pad_part(dg, values):
    p = np.zeros(dg.n_pad, dtype=np.int32)
    p[: len(values)] = values
    return jnp.asarray(p)


def test_overload_balancer_restores_feasibility():
    g = factories.make_grid_graph(8, 8)
    dg = device_graph_from_host(g)
    # all 64 nodes in block 0 of 4
    part = _pad_part(dg, np.zeros(64, dtype=np.int32))
    caps = jnp.array([17, 17, 17, 17], dtype=jnp.int32)
    balanced = overload_balance(dg, part, 4, caps, jnp.int32(1))
    bw = np.asarray(metrics.block_weights(dg, balanced, 4))
    assert (bw <= 17).all(), bw


def test_overload_balancer_never_overloads_feasible_block():
    # regression: k=3, block 0 heavily overloaded, block 1 has small
    # headroom — incoming movers must not push block 1 over its cap
    g = factories.make_path(12)
    g.node_weights = np.full(12, 10, dtype=np.int64)
    dg = device_graph_from_host(g)
    part = _pad_part(dg, np.array([0] * 8 + [1, 1, 2, 2], dtype=np.int32))
    caps = jnp.array([55, 25, 1000], dtype=jnp.int32)
    from kaminpar_tpu.ops.balancer import overload_balance_round

    out, _ = overload_balance_round(dg, part, 3, caps, jnp.int32(7))
    bw = np.asarray(metrics.block_weights(dg, out, 3))
    assert bw[1] <= 25, bw  # previously-feasible block must stay feasible


def test_overload_balancer_noop_when_feasible():
    g = factories.make_grid_graph(4, 4)
    dg = device_graph_from_host(g)
    part = _pad_part(dg, np.arange(16) // 4)
    caps = jnp.array([5, 5, 5, 5], dtype=jnp.int32)
    out = overload_balance(dg, part, 4, caps, jnp.int32(1))
    assert np.array_equal(np.asarray(out)[:16], np.asarray(part)[:16])


def test_underload_balancer_fills_min_weights():
    g = factories.make_grid_graph(8, 8)
    dg = device_graph_from_host(g)
    part = _pad_part(dg, np.zeros(64, dtype=np.int32))  # block 1 empty
    caps = jnp.array([64, 64], dtype=jnp.int32)
    mins = jnp.array([10, 10], dtype=jnp.int32)
    out = underload_balance(dg, part, 2, caps, mins, jnp.int32(1))
    bw = np.asarray(metrics.block_weights(dg, out, 2))
    assert (bw >= 10).all(), bw


def test_jet_improves_random_partition():
    g = factories.make_grid_graph(10, 10)
    dg = device_graph_from_host(g)
    rng = np.random.default_rng(1)
    part = _pad_part(dg, rng.integers(0, 4, 100))
    caps = jnp.array([30, 30, 30, 30], dtype=jnp.int32)
    before = int(metrics.edge_cut(dg, part))
    out = jet_refine(
        dg, part, 4, caps, jnp.int32(1), JetRefinementContext(), level=0
    )
    after = int(metrics.edge_cut(dg, out))
    assert after < before
    bw = np.asarray(metrics.block_weights(dg, out, 4))
    assert (bw <= 30).all()


def test_fm_host_improves_partition():
    g = factories.make_grid_graph(8, 8)
    dg = device_graph_from_host(g)
    rng = np.random.default_rng(2)
    part = _pad_part(dg, rng.integers(0, 2, 64))
    caps = np.array([40, 40])
    before = int(metrics.edge_cut(dg, part))
    out = fm_refine_host(dg, part, 2, caps, FMRefinementContext(), seed=1)
    after = int(metrics.edge_cut(dg, out))
    assert after < before
    bw = np.asarray(metrics.block_weights(dg, out, 2))
    assert (bw <= 40).all()


def test_k_bucketing_never_uses_phantom_blocks():
    """RefinerPipeline pads k to a power of two with zero-capacity
    phantom blocks (ops/segments.pad_k_bucket); labels must stay < k."""
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.utils.logger import OutputLevel
    from kaminpar_tpu.ops.segments import pad_k_bucket

    k_pad, max_bw, min_bw = pad_k_bucket(5, np.array([10, 10, 10, 10, 10]))
    assert k_pad == 8
    assert max_bw.shape == (8,) and int(max_bw[5:].sum()) == 0
    assert min_bw is None

    g = factories.make_rmat(1 << 10, 6_000, seed=4)
    for k in (3, 5, 11):
        p = KaMinPar("default")
        p.set_output_level(OutputLevel.QUIET)
        part = p.set_graph(g).compute_partition(k=k, epsilon=0.05, seed=2)
        assert part.min() >= 0 and part.max() < k
        assert len(np.unique(part)) == k  # all real blocks populated


def test_chunked_launch_paths_match_fused(monkeypatch):
    """Above MAX_FUSED_EDGE_SLOTS, Jet shrinks its iteration chunk and LP
    refinement runs one round per launch (TPU-worker watchdog guard).
    Force the thresholds down and check both paths still produce valid,
    cap-respecting refinements equivalent to the fused path's quality."""
    import kaminpar_tpu.ops.jet as jet_mod
    import kaminpar_tpu.ops.segments as seg_mod
    from kaminpar_tpu.ops.jet import jet_refine
    from kaminpar_tpu.ops.lp import lp_refine
    from kaminpar_tpu.context import JetRefinementContext

    g = device_graph_from_host(factories.make_rmat(1 << 10, 8_000, seed=9))
    k = 4
    nw = np.asarray(g.node_w)[: int(g.n)]
    cap = jnp.full(k, int(1.05 * np.ceil(nw.sum() / k)), dtype=jnp.int32)
    p0 = jnp.asarray((np.arange(g.n_pad) % k).astype(np.int32))
    cut0 = int(metrics.edge_cut(g, p0))

    fused_jet = jet_refine(g, p0, k, cap, jnp.int32(3), JetRefinementContext(), 0, 2)
    fused_lp = lp_refine(g, p0, k, cap, jnp.int32(3))

    monkeypatch.setattr(jet_mod, "MAX_FUSED_EDGE_SLOTS", 1024)
    monkeypatch.setattr(seg_mod, "MAX_FUSED_EDGE_SLOTS", 1024)
    chunked_jet = jet_refine(g, p0, k, cap, jnp.int32(3), JetRefinementContext(), 0, 2)
    chunked_lp = lp_refine(g, p0, k, cap, jnp.int32(3))

    for part in (chunked_jet, chunked_lp):
        labels = np.asarray(part)[: int(g.n)]
        assert labels.min() >= 0 and labels.max() < k
        bw = np.bincount(labels, weights=nw, minlength=k)
        assert bw.max() <= int(cap[0])
    # same quality class as the fused paths (jet chunk=1 visits the same
    # states, so it is exact; chunked LP may converge slightly differently)
    assert int(metrics.edge_cut(g, chunked_jet)) == int(
        metrics.edge_cut(g, fused_jet)
    )
    assert int(metrics.edge_cut(g, chunked_lp)) < cut0
    assert int(metrics.edge_cut(g, fused_lp)) < cut0


def test_jet_incremental_table_matches_full_rebuild(monkeypatch):
    """The incrementally-maintained (n, k) rating table and the
    candidate-row afterburner must be bitwise-equivalent to full
    rebuilds: integer re-scatter of changed rows is exact, and candidate
    rows contain every edge the filter sums.  Force the delta threshold
    down and compare whole refinements."""
    import kaminpar_tpu.ops.jet as jet_mod
    from kaminpar_tpu.ops.jet import jet_refine
    from kaminpar_tpu.context import JetRefinementContext

    g = device_graph_from_host(factories.make_rmat(1 << 11, 24_000, seed=21))
    k = 8
    nw = np.asarray(g.node_w)[: int(g.n)]
    cap = jnp.full(k, int(1.1 * np.ceil(nw.sum() / k)), dtype=jnp.int32)
    rng = np.random.default_rng(5)
    p0 = np.zeros(g.n_pad, np.int32)
    p0[: int(g.n)] = rng.integers(0, k, int(g.n))
    p0 = jnp.asarray(p0)

    full = np.asarray(
        jet_refine(g, p0, k, cap, jnp.int32(4), JetRefinementContext(), 0, 2)
    )
    # full-width delta budget: candidate pruning keeps everything, so the
    # row-compacted path must reproduce the full path bitwise
    monkeypatch.setattr(jet_mod, "DELTA_MIN_EDGE_SLOTS", 1)
    monkeypatch.setattr(
        jet_mod, "_delta_slots", lambda graph: graph.src.shape[0]
    )
    jet_mod._jet_chunk.clear_cache()
    try:
        delta = np.asarray(
            jet_refine(g, p0, k, cap, jnp.int32(4), JetRefinementContext(), 0, 2)
        )
    finally:
        jet_mod._jet_chunk.clear_cache()
    np.testing.assert_array_equal(delta, full)


def test_jet_candidate_pruning_quality_class(monkeypatch):
    """With a TIGHT delta budget the two-stage candidate pruning admits
    only the best-gain rows per iteration; the refinement must stay
    feasible and land in the same cut class as the unpruned run (pruned
    candidates compete again next iteration)."""
    import kaminpar_tpu.ops.jet as jet_mod
    from kaminpar_tpu.context import JetRefinementContext
    from kaminpar_tpu.ops.jet import jet_refine
    from kaminpar_tpu.ops.metrics import edge_cut

    g = device_graph_from_host(factories.make_rmat(1 << 11, 24_000, seed=21))
    k = 8
    nw = np.asarray(g.node_w)[: int(g.n)]
    cap = jnp.full(k, int(1.1 * np.ceil(nw.sum() / k)), dtype=jnp.int32)
    rng = np.random.default_rng(5)
    p0 = np.zeros(g.n_pad, np.int32)
    p0[: int(g.n)] = rng.integers(0, k, int(g.n))
    p0 = jnp.asarray(p0)

    cut_full = int(
        edge_cut(g, jnp.asarray(jet_refine(
            g, p0, k, cap, jnp.int32(4), JetRefinementContext(), 0, 2)))
    )
    monkeypatch.setattr(jet_mod, "DELTA_MIN_EDGE_SLOTS", 1)
    jet_mod._jet_chunk.clear_cache()
    try:
        pruned_part = jet_refine(
            g, p0, k, cap, jnp.int32(4), JetRefinementContext(), 0, 2
        )
        cut_pruned = int(edge_cut(g, jnp.asarray(pruned_part)))
        bw = np.zeros(k, np.int64)
        np.add.at(bw, np.asarray(pruned_part)[: int(g.n)], nw)
        assert (bw <= int(cap[0])).all()
    finally:
        jet_mod._jet_chunk.clear_cache()
    # same class: pruning costs at most a few percent on this workload
    assert cut_pruned <= 1.1 * cut_full


def _jet_case(kind: str, k: int, heavy: bool):
    """A graph, caps and a random start for the conn-buffer tests."""
    if kind == "rmat":
        # padded as the chip pads (half the slots and more are padding):
        # R-MAT's move sets at k = 16 straddle a sixteenth of that
        host, m_pad = factories.make_rmat(1 << 10, 12_000, seed=13), 1 << 16
    else:
        host, m_pad = factories.make_grid_graph(32, 32), None
    if heavy:
        # one weight per undirected edge, large enough that gains at
        # R-MAT's hubs leave the packed afterburner's clip range at k = 16
        ew = np.asarray(
            np.random.default_rng(3).integers(1, 100_000, len(host.adjncy))
        )
        src = np.repeat(np.arange(host.n), np.diff(host.xadj))
        lo, hi = np.minimum(src, host.adjncy), np.maximum(src, host.adjncy)
        host.edge_weights = ew[np.unique(
            lo.astype(np.int64) * host.n + hi, return_inverse=True)[1]]
    g = device_graph_from_host(host, m_pad=m_pad)
    nw = np.asarray(g.node_w)[: int(g.n)]
    cap = jnp.full(k, int(1.05 * np.ceil(nw.sum() / k)), dtype=jnp.int32)
    p0 = np.zeros(g.n_pad, np.int32)
    p0[: int(g.n)] = np.random.default_rng(k).integers(0, k, int(g.n))
    return g, cap, jnp.asarray(p0)


@pytest.mark.parametrize(
    "k, kind, heavy, rows",
    [pytest.param(k, kind, heavy, False,
                  id=f"{k}-{kind}-{'heavy' if heavy else 'unit'}")
     for heavy in (False, True) for kind in ("rmat", "grid")
     for k in (2, 8, 16)]
    # past the gate, the cases whose balancer moves straddle the buffer
    + [pytest.param(16, "rmat", False, True, id="16-rmat-unit-rows"),
       pytest.param(16, "rmat", True, True, id="16-rmat-heavy-rows"),
       pytest.param(16, "grid", False, True, id="16-grid-unit-rows")],
)
def test_jet_conn_buffer_matches_full_rebuilds(monkeypatch, k, kind, heavy,
                                               rows):
    """Jet with the conn table kept by its movers' rows (buffer of
    m_pad // CONN_DELTA_DIVISOR slots, the shipped size) returns bitwise
    the partition of Jet that rebuilds the table at every reconcile
    (buffer forced to 0), on runs that have reconciles on both sides of
    the threshold.  With `rows` the gate is lowered to the graph's m_pad:
    the Jet moves' reconcile then rides the afterburner's rows (always
    counted) and the buffer serves the balancer's alone."""
    import kaminpar_tpu.ops.jet as jet_mod
    from kaminpar_tpu import telemetry

    g, cap, p0 = _jet_case(kind, k, heavy)
    if rows:
        monkeypatch.setattr(jet_mod, "DELTA_MIN_EDGE_SLOTS", g.src.shape[0])
    assert jet_mod.iteration_path(g, k) == (
        "jet-rows" if rows else "jet-edges")
    assert jet_mod._conn_slots(g) == g.src.shape[0] // 16 > 0

    def run():
        telemetry.reset()
        jet_mod._jet_chunk.clear_cache()
        try:
            out = np.asarray(jet_refine(
                g, p0, k, cap, jnp.int32(4), JetRefinementContext(), 1, 2))
        finally:
            jet_mod._jet_chunk.clear_cache()
        delta = [c for s in telemetry.progress_series("jet")
                 for c in s.series["conn_delta"]]
        return out, delta

    def run_with(slots):
        monkeypatch.setattr(jet_mod, "_conn_slots", lambda graph: slots)
        return run()

    telemetry.enable()
    shipped, delta = run()
    rebuilt, none = run_with(0)
    # a buffer as wide as the edge array takes every reconcile: its
    # counter is the number of reconciles an iteration had
    _, reconciles = run_with(g.src.shape[0])
    always = int(rows)  # the afterburner's rows serve the Jet moves' own
    assert set(none) == {always} and set(reconciles) <= {1, 2}
    assert any(d < r for d, r in zip(delta, reconciles)), delta
    assert any(d > always for d in delta), delta
    np.testing.assert_array_equal(shipped, rebuilt)


@pytest.mark.parametrize("rows", [False, True], ids=["jet-edges", "jet-rows"])
def test_conn_reconciles_take_their_own_buffer_at_every_size(
        monkeypatch, rows):
    """Every reconcile _conn_step takes (the balancer's, on both sides
    of the gate) goes through m_pad // CONN_DELTA_DIVISOR slots, whatever
    the afterburner's buffer is; the afterburner's is that same sixteenth
    where the candidates' rows fit it, and past the gate m_pad // 4 (after
    the prune) where they do not: one lax.cond traces both."""
    import kaminpar_tpu.ops.jet as jet_mod

    g, cap, p0 = _jet_case("grid", 4, False)
    m_pad = g.src.shape[0]
    if rows:
        monkeypatch.setattr(jet_mod, "DELTA_MIN_EDGE_SLOTS", m_pad)
    assert jet_mod._delta_slots(g) == (m_pad // 4 if rows else None)
    want = m_pad // jet_mod.CONN_DELTA_DIVISOR
    assert jet_mod._conn_slots(g) == want
    widths, filters = [], []
    real, real_filter = jet_mod._conn_update_rows, jet_mod._rows_filter

    def recording(graph, conn, before, after, k, dslots):
        widths.append(dslots)
        return real(graph, conn, before, after, k, dslots)

    def recording_filter(*args):
        filters.append(args[-1])
        return real_filter(*args)

    monkeypatch.setattr(jet_mod, "_conn_update_rows", recording)
    monkeypatch.setattr(jet_mod, "_rows_filter", recording_filter)
    jet_mod._jet_iteration(
        g, p0, jnp.zeros_like(p0), 4, cap, jnp.float32(0.75), jnp.int32(5), 4)
    assert widths == [want]
    assert sorted(filters) == ([want, m_pad // 4] if rows else [want])


@pytest.mark.parametrize("over", [0, 1], ids=["fits", "one-over"])
def test_conn_step_threshold(over):
    """_conn_step takes the movers' rows while their degrees sum to at
    most the buffer and rebuilds from one more slot on; the table is the
    rebuilt one either way."""
    import kaminpar_tpu.ops.jet as jet_mod

    g = device_graph_from_host(factories.make_grid_graph(16, 16))
    k = 4
    rng = np.random.default_rng(0)
    before = _pad_part(g, rng.integers(0, k, int(g.n)))
    movers = np.array([17, 40, 100, 200])  # interior nodes, degree 4
    after = before.at[movers].set((before[movers] + 1) % k)
    changed_edges = int(np.asarray(g.degrees)[movers].sum())
    conn = jet_mod._full_ratings(g, before, k)
    got, took = jet_mod._conn_step(
        g, conn, before, after, k, changed_edges - over)
    assert int(took) == 1 - over
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jet_mod._full_ratings(g, after, k)))


def test_jet_conn_delta_counter_rides_only_the_stats_buffer():
    """The conn_delta, pruned, rows and wide counters are the fourth to
    seventh column of the `jet` progress series; with telemetry off
    _jet_chunk's loop has the carries it had before the counters (j,
    fruitless, part, lock, best, best_cut, conn) and with it on exactly
    one more, the stats buffer, one column wider for `wide`."""
    import jax

    import kaminpar_tpu.ops.jet as jet_mod
    from kaminpar_tpu.telemetry import progress as progress_mod

    g, cap, p0 = _jet_case("grid", 4, False)
    k = 4
    conn = jet_mod._full_ratings(g, p0, k)
    wdeg = jnp.zeros(g.n_pad, jnp.int32)

    def chunk(stats):
        return jax.make_jaxpr(lambda part, stats: jet_mod._jet_chunk(
            g, part, jnp.zeros_like(part), part, jnp.int32(2**31 - 1),
            jnp.int32(0), conn, jnp.int32(0), k, cap, jnp.float32(0.25),
            jnp.float32(0.999), jnp.int32(1), jnp.int32(0), jnp.int32(4),
            wdeg, 2**30, 4, stats))(p0, stats)

    def loop(jaxpr):
        """_jet_chunk's iteration loop: the `while` with most carries."""
        (pjit,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "jit"]
        return max((e for e in pjit.params["jaxpr"].jaxpr.eqns
                    if e.primitive.name == "while"),
                   key=lambda e: len(e.outvars))

    off = loop(chunk(None))
    assert len(off.outvars) == 7
    assert len(loop(chunk(progress_mod.new_buffer(4, 7))).outvars) == 8
    # the buffer is as wide as the series has names: one of six columns
    # (the series before `wide`) does not take the record
    with pytest.raises(ValueError):
        chunk(progress_mod.new_buffer(4, 6))
    # telemetry off, every carry is a scalar, a node-wide vector or the
    # table: nothing of the stats rides the loop
    assert {v.aval.shape for v in off.outvars} == {
        (), (g.n_pad,), (g.n_pad, k)}


def test_prune_candidates_to_budget_semantics():
    from kaminpar_tpu.ops.segments import prune_candidates_to_budget

    degrees = jnp.asarray(np.array([3, 5, 2, 4, 1, 7, 0, 0], np.int32))
    gain = jnp.asarray(np.array([10, -2, 7, 7, 1, 3, 0, 0], np.int32))
    cand = jnp.asarray(np.array([1, 1, 1, 1, 1, 1, 0, 0], bool))
    # budget fits everything -> identity
    keep = prune_candidates_to_budget(cand, gain, degrees, 3, 1000)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(cand))
    # tight budget -> a prefix of the gain order, total degree <= budget
    keep = np.asarray(prune_candidates_to_budget(cand, gain, degrees, 3, 9))
    kept_deg = int(np.asarray(degrees)[keep].sum())
    assert kept_deg <= 9
    assert keep[0]  # gain 10 is always kept first (deg 3 fits)
    assert not keep[1]  # the worst gain goes first when pruning
    # budget monotonicity: a bigger budget keeps a superset
    keep_big = np.asarray(prune_candidates_to_budget(cand, gain, degrees, 3, 12))
    assert (keep <= keep_big).all()


def _afterburner_pair(weight_scale: int, k: int, seed: int):
    """Run the packed (guard-dispatched) afterburner and the exact
    reference filter on the same inputs; return (packed, exact, cand)."""
    from kaminpar_tpu.ops.segments import (
        INT32_MIN,
        afterburner_filter,
        packed_afterburner_gain,
    )

    g = device_graph_from_host(factories.make_rmat(512, 4_000, seed=9))
    n_pad = g.n_pad
    rng = np.random.default_rng(seed)
    ew = np.asarray(g.edge_w).copy()
    real = ew > 0
    ew[real] = rng.integers(1, weight_scale + 1, real.sum())
    edge_w = jnp.asarray(ew)
    part = jnp.asarray(rng.integers(0, k, n_pad).astype(np.int32))
    cand = jnp.asarray(
        (rng.random(n_pad) < 0.4) & (np.arange(n_pad) < int(g.n))
    )
    tgt = jnp.asarray(rng.integers(0, k, n_pad).astype(np.int32))
    next_part = jnp.where(cand, tgt, part)
    # gains scale with the edge weights, so heavy graphs push them past
    # the packed clip range (gain_bits = 31 - 2*ceil(log2 k))
    gain = jnp.asarray(
        rng.integers(-3 * weight_scale, 3 * weight_scale + 1, n_pad)
        .astype(np.int32)
    )
    packed = packed_afterburner_gain(
        g.src, g.dst, edge_w, g.row_ptr, part, next_part, gain, cand, k
    )
    exact = afterburner_filter(
        g.src,
        g.dst,
        edge_w,
        part[g.src],
        part[g.dst],
        jnp.where(cand, gain, INT32_MIN),
        next_part,
        g.src,
        n_pad,
    )
    return np.asarray(packed), np.asarray(exact), np.asarray(cand)


def test_afterburner_clip_guard_heavy_weights():
    """Heavy edge weights push candidate gains past the packed layout's
    clip range (gain_bits=15 at k=256); the runtime guard must dispatch
    the exact path, making the packed entry point agree with the exact
    filter bit-for-bit."""
    packed, exact, cand = _afterburner_pair(
        weight_scale=50_000, k=256, seed=3
    )
    np.testing.assert_array_equal(packed[cand], exact[cand])


def test_afterburner_packed_path_matches_exact_in_range():
    """Below the clip range the packed path itself must equal the exact
    filter (the guard keeps the cheap branch)."""
    packed, exact, cand = _afterburner_pair(weight_scale=50, k=256, seed=4)
    np.testing.assert_array_equal(packed[cand], exact[cand])


def test_fm_threaded_pool_feasible_and_improves():
    """The threaded native FM (NodeTracker claims + atomic gain table)
    must keep the caps and improve the cut; threads=1 must reproduce the
    sequential result bitwise (same rng discipline)."""
    import os

    if os.environ.get("KAMINPAR_TPU_NO_NATIVE_FM", "") == "1":
        import pytest

        pytest.skip("native FM disabled")
    from kaminpar_tpu import native

    if not native.available():
        import pytest

        pytest.skip("no native lib")

    g = factories.make_rmat(1 << 11, 24_000, seed=8)
    dg = device_graph_from_host(g)
    k = 8
    nw = np.asarray(dg.node_w)[: int(dg.n)]
    cap = jnp.full(k, int(1.1 * np.ceil(nw.sum() / k)), dtype=jnp.int32)
    rng = np.random.default_rng(2)
    p0 = np.zeros(dg.n_pad, np.int32)
    p0[: int(dg.n)] = rng.integers(0, k, int(dg.n))
    p0 = jnp.asarray(p0)
    from kaminpar_tpu.ops.metrics import edge_cut

    cut0 = int(edge_cut(dg, p0))
    ctx = FMRefinementContext()

    seq1 = np.asarray(fm_refine_host(dg, p0, k, cap, ctx, seed=5, threads=1))
    seq2 = np.asarray(fm_refine_host(dg, p0, k, cap, ctx, seed=5, threads=1))
    np.testing.assert_array_equal(seq1, seq2)  # deterministic

    for threads in (1, 2, 4):
        out = fm_refine_host(dg, p0, k, cap, ctx, seed=5, threads=threads)
        labels = np.asarray(out)[: int(dg.n)]
        bw = np.bincount(labels, weights=nw, minlength=k)
        assert bw.max() <= int(cap[0]), (threads, bw.max())
        cut = int(edge_cut(dg, out))
        assert cut < cut0, (threads, cut, cut0)


def test_fm_sparse_compact_hashing_cache():
    """The sparse compact-hashing FM path (large-k gain cache,
    compact_hashing_gain_cache.h:34 analog): improves the cut, respects
    caps, and the conn bookkeeping stays exact through rebuilds."""
    from kaminpar_tpu import native

    if not native.available():
        import pytest

        pytest.skip("native library unavailable")
    g = factories.make_rmat(1 << 9, 4000, seed=6)
    dg = device_graph_from_host(g)
    k = 8
    rng = np.random.default_rng(4)
    part_h = rng.integers(0, k, g.n).astype(np.int32)
    nw = g.node_weight_array()
    cap = np.full(k, int(1.1 * nw.sum() / k) + 2, dtype=np.int64)
    part_dev = _pad_part(dg, part_h)
    before = int(metrics.edge_cut(dg, part_dev))

    part_sp = np.array(part_h, copy=True)
    imp = native.fm_refine(
        g, part_sp, k, cap, FMRefinementContext(), seed=9, force_sparse=True
    )
    assert imp is not None and imp > 0
    after = int(metrics.edge_cut(dg, _pad_part(dg, part_sp)))
    assert after < before
    # the returned improvement is the exact cut delta
    assert before - after == imp
    bw = np.zeros(k, dtype=np.int64)
    np.add.at(bw, part_sp, nw)
    assert (bw <= cap).all()

    # dense path on the same instance for comparison: both must land in
    # the same quality ballpark (identical algorithms, different
    # candidate enumeration order)
    part_dn = np.array(part_h, copy=True)
    imp_dn = native.fm_refine(
        g, part_dn, k, cap, FMRefinementContext(), seed=9
    )
    assert imp_dn is not None and imp_dn > 0
    after_dn = int(metrics.edge_cut(dg, _pad_part(dg, part_dn)))
    assert after <= int(1.15 * after_dn) + 5


def _fm_replay_case(name):
    """`(graph, start)` of a golden-digest case: `start(k)` is a
    deterministic partition that knows nothing of FM (coordinate blocks
    of the mesh's points, index blocks elsewhere)."""
    if name == "delaunay-2000":
        points, g = delaunay_mesh(2000, seed=3)
        return g, lambda k: recursive_coordinate_bisection(points, k)
    if name == "rmat-1024":
        # make_rmat merges its parallel edges into weights (up to 24 here)
        g = factories.make_rmat(1 << 10, 8000, seed=5)
    else:
        # 40 x 40 grid, edge weights up to 2^20 from the endpoints alone
        grid = factories.make_grid_graph(40, 40)
        src = grid.edge_sources().astype(np.int64)
        dst = grid.adjncy.astype(np.int64)
        e = np.stack([src, dst], axis=1)[src < dst]
        w = 1 + (e[:, 0] * 2654435761 + e[:, 1] * 40503) % (1 << 20)
        g = from_edge_list(1600, e, w)
    return g, lambda k: (np.arange(g.n) * k // g.n).astype(np.int32)


#: (graph, k, seed) -> (sha1 of the refined int32 labels, returned gain),
#: taken from the engine of PR 32 (`Delta` over an `unordered_map`) before
#: PR 33 touched fm.cpp: whatever the engine's bookkeeping becomes, one
#: thread returns these bytes.  Seed 3 is PR 36's engine, before PR 38's
#: guards for more threads.
FM_GOLDEN = {
    ("delaunay-2000", 2, 1): ("ca8372f102bee2efaafb2c048303137eddb0f0a9", 19),
    ("delaunay-2000", 2, 7): ("a4f947bebfdfe488c51a795d2d0117c7add8d0f4", 19),
    ("delaunay-2000", 4, 1): ("801637ae033db21e76629f1d7b5a4aa3490fa088", 31),
    ("delaunay-2000", 4, 7): ("e132d72f36909591a418d6e860bca947dd53387f", 28),
    ("delaunay-2000", 16, 1): ("3e286fa47b040533e94fc179512814ab4b26efe4", 110),
    ("delaunay-2000", 16, 7): ("76817d1828194cdb6a6dea389c76ecc7e1a14c70", 106),
    ("rmat-1024", 2, 1): ("72c94973342e53c7921f11c9a31dae5c88e45aec", 1910),
    ("rmat-1024", 2, 7): ("d79f411c716fc0d201f7c4f09be59781856e27a9", 1835),
    ("rmat-1024", 4, 1): ("7e28ec3b5d39a7eb331fa4a2dd2081a183f1a967", 2044),
    ("rmat-1024", 4, 7): ("22fc3f4abd7c314b0911dc783b143a75d4843927", 2047),
    ("rmat-1024", 16, 1): ("4e0592a1bd8363d6f325d814e96b31c16e25fcac", 1464),
    ("rmat-1024", 16, 7): ("59a6c3088770ed8e0aba7d6f4a53355c779eeb53", 1412),
    # distinct heavy weights leave no tie to break: at k = 2 both seeds
    # end in the same labels
    ("grid-40x40", 2, 1): ("e7cc219caf6a3499ec2e50c150efa87de3bbb758", 881680),
    ("grid-40x40", 2, 7): ("e7cc219caf6a3499ec2e50c150efa87de3bbb758", 881680),
    ("grid-40x40", 4, 1): ("e2e86400dd59c6e45818cf891fa96076e426c1e1", 13019656),
    ("grid-40x40", 4, 7): ("797ff19506c3755bf1ff7ba5834afd869f737cb8", 14666744),
    ("grid-40x40", 16, 1): ("cd2c9ab05bc879deedfec3c25825b75b72bc899a", 84136595),
    ("grid-40x40", 16, 7): ("06a11524498b2e8cd99a47d881c8626cc098cfa5", 65955219),
    ("delaunay-2000", 2, 3): ("bc38c61cc2415b75523ba322a96729665cf07e16", 19),
    ("delaunay-2000", 4, 3): ("1bbee5305ae73879aad66e36063479ea6ca6137f", 27),
    ("delaunay-2000", 16, 3): ("3608aa9b64a2a40525b681096f58a1271b071be9", 107),
    ("rmat-1024", 2, 3): ("d599f44b30f64080c2881ead70479d78a32296f4", 1917),
    ("rmat-1024", 4, 3): ("e630e02937dfee9a8a3904ce688b84a47f0bb973", 2062),
    ("rmat-1024", 16, 3): ("cb10e2225160f43fcc02d11fbf7799afa77ce5c4", 1447),
    ("grid-40x40", 2, 3): ("e7cc219caf6a3499ec2e50c150efa87de3bbb758", 881680),
    ("grid-40x40", 4, 3): ("6dc90959cfb7a2f80a4ed287802ee11bb0c2fe96", 13259944),
    ("grid-40x40", 16, 3): ("c04aa6fc9cd4c9e7c38ea4f4bc08f0fcf8989caa", 88665830),
}


@pytest.mark.parametrize("name,k,seed", sorted(FM_GOLDEN))
def test_fm_native_replays_the_recorded_partition(name, k, seed):
    from kaminpar_tpu import native

    if not native.available():
        pytest.skip("no native lib")
    g, start = _fm_replay_case(name)
    part = start(k)
    cap = np.full(
        k, int(1.03 * np.ceil(g.node_weight_array().sum() / k)), np.int64
    )
    gain = native.fm_refine(g, part, k, cap, FMRefinementContext(), seed=seed)
    assert (hashlib.sha1(part.tobytes()).hexdigest(), gain) == FM_GOLDEN[
        name, k, seed
    ]


def test_microbench_fm_prints_one_digest_twice():
    """scripts/microbench_fm.py, the replay that sizes an FM change
    without the pipeline, runs at n = 4,096 and is a replay: two
    processes print the same cut and the same sha1 of the labels."""
    from kaminpar_tpu import native

    if not native.available():
        pytest.skip("no native lib")
    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "microbench_fm.py",
    )
    last = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, script, "--n", "4096", "--k", "16", "--seed",
             "1", "--calls", "2"],
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        lines = proc.stdout.strip().splitlines()
        assert len(lines) == 4, proc.stdout  # start, two calls, the total
        last.append(re.fullmatch(
            r"microbench_fm: 2 calls \S+ s (cut \d+ sha1 [0-9a-f]{40})",
            lines[-1],
        ).group(1))
    assert last[0] == last[1]


def test_jet_large_k_degrades_to_lp():
    """jet_refine above JET_DENSE_MAX_ENTRIES must not materialize the
    dense (n, k) table — it degrades to LP refinement rounds and still
    returns a feasible, not-worse partition."""
    import kaminpar_tpu.ops.jet as jet_mod

    g = factories.make_rmat(1 << 9, 4000, seed=3)
    dg = device_graph_from_host(g)
    k = 16
    rng = np.random.default_rng(1)
    part = _pad_part(dg, rng.integers(0, k, g.n))
    nw = g.node_weight_array()
    cap = jnp.asarray(
        np.full(k, int(1.2 * nw.sum() / k) + 2, dtype=np.int32)
    )
    before = int(metrics.edge_cut(dg, part))
    old = jet_mod.JET_DENSE_MAX_ENTRIES
    jet_mod.JET_DENSE_MAX_ENTRIES = 1  # force the large-k fallback
    try:
        out = jet_mod.jet_refine(
            dg, part, k, cap, jnp.int32(7), JetRefinementContext()
        )
    finally:
        jet_mod.JET_DENSE_MAX_ENTRIES = old
    after = int(metrics.edge_cut(dg, out))
    assert after <= before
    bw = np.asarray(metrics.block_weights(dg, out, k))
    assert (bw <= np.asarray(cap)).all()
