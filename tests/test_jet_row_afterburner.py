"""Jet under the `1 << 22` gate: an iteration whose candidates' CSR rows
fit `_conn_slots` runs the afterburner and the conn-table delta over that
row buffer (`_rows_filter`, the code the `jet-rows` path past the gate
runs), one whose candidates overflow it runs the edge-wide afterburner
(`_edges_filter`) and rebuilds the table.  Whichever ran, the partition, the locks and the table
are the same bits; the `rows` column of the `jet` progress series says
which it was.
"""

import functools
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kaminpar_tpu.ops.jet as jet_mod
from kaminpar_tpu import telemetry
from kaminpar_tpu.context import JetRefinementContext
from kaminpar_tpu.dtypes import ACC_DTYPE
from kaminpar_tpu.graphs import device_graph_from_host, factories
from fegrid_reference import rectangles
from mesh_reference import delaunay_mesh, recursive_coordinate_bisection

GAIN_TEMP = 0.75
SALT = 5
KS = (2, 4, 16)
KINDS = ("mesh", "skewed")
WEIGHTS = ("unit", "heavy")
CASES = [pytest.param(kind, k, weights, id=f"{kind}-k{k}-{weights}")
         for kind in KINDS for k in KS for weights in WEIGHTS]


def _packed_half(k: int) -> int:
    """First |gain| the packed afterburner's gain field does not hold at
    `k` (segments._afterburner_gain's clip guard)."""
    label_bits = max((k - 1).bit_length(), 1)
    return 1 << (31 - 2 * label_bits - 1)


@functools.lru_cache(maxsize=None)
def _graph(kind: str, k: int, weights: str):
    """A mesh-like or a skewed graph, both padded as the chip pads (most
    slots are padding).  `heavy` plants three edges whose weight is past
    the packed gain field at this `k`, so a candidate beside one sends
    the afterburner to its exact branch; every sum stays inside int32."""
    if kind == "mesh":
        host = factories.make_fe_grid(24, 24)
    else:
        host = factories.make_rmat(1 << 9, 5_000, seed=13)
    if weights == "heavy":
        rng = np.random.default_rng(11)
        src = np.repeat(np.arange(host.n), np.diff(host.xadj))
        lo, hi = np.minimum(src, host.adjncy), np.maximum(src, host.adjncy)
        _, undirected = np.unique(lo.astype(np.int64) * host.n + hi,
                                  return_inverse=True)
        ew = rng.integers(1, 50, undirected.max() + 1)
        ew[rng.choice(len(ew), 3, replace=False)] = _packed_half(k) + 7
        host.edge_weights = ew[undirected]
    return device_graph_from_host(host, m_pad=1 << 15)


def _start(g, k: int):
    part = np.zeros(g.n_pad, np.int32)
    part[: int(g.n)] = np.random.default_rng(k).integers(0, k, int(g.n))
    nw = np.asarray(g.node_w)[: int(g.n)]
    caps = jnp.full(k, int(1.05 * math.ceil(nw.sum() / k)), dtype=jnp.int32)
    return jnp.asarray(part), caps


def _wdeg(g):
    return jax.ops.segment_sum(
        g.edge_w.astype(ACC_DTYPE), g.src, num_segments=g.n_pad)


def _candidates(g, part, lock, k: int):
    """(candidate, gain) of the iteration `_step` runs from `part`."""
    _, gain, _, candidate = jet_mod._find_moves(
        g, jet_mod._full_ratings(g, part, k), part, lock, k,
        jnp.float32(GAIN_TEMP), jnp.int32(SALT))
    return candidate, gain


def _cand_edges(g, part, lock, k: int) -> int:
    candidate, _ = _candidates(g, part, lock, k)
    return int(jnp.sum(jnp.where(candidate, g.degrees, 0)))


@functools.lru_cache(maxsize=None)
def _step(k: int, slots: int):
    """One jitted `_jet_iteration` traced with `_conn_slots` forced to
    `slots` (the graph is an argument: the cases of one shape share it)."""

    def step(g, part, lock, conn, caps, wdeg):
        patch = pytest.MonkeyPatch()
        patch.setattr(jet_mod, "_conn_slots", lambda graph: slots)
        try:
            return jet_mod._jet_iteration(
                g, part, lock, k, caps, jnp.float32(GAIN_TEMP),
                jnp.int32(SALT), 4, wdeg=wdeg, conn=conn)
        finally:
            patch.undo()

    return jax.jit(step)


def _iterate(g, k: int, slots: int, steps: int = 2):
    """`steps` successive iterations through a buffer of `slots` slots;
    the outputs of each as numpy."""
    part, caps = _start(g, k)
    lock, wdeg = jnp.zeros_like(part), _wdeg(g)
    conn = jet_mod._full_ratings(g, part, k)
    outs = []
    for _ in range(steps):
        part, lock, ext_sum, conn, delta, pruned, rows, _ = _step(
            k, slots)(g, part, lock, conn, caps, wdeg)
        outs.append(SimpleNamespace(
            part=np.asarray(part), lock=np.asarray(lock),
            ext_sum=int(ext_sum), conn=np.asarray(conn),
            conn_delta=int(delta), pruned=int(pruned), rows=int(rows)))
    return outs


@functools.lru_cache(maxsize=None)
def _both(kind: str, k: int, weights: str):
    """Two iterations with the row branch forced (a buffer as wide as
    the edge array holds any candidate set) and with the edge branch
    forced (a buffer of one slot holds none that has an edge)."""
    g = _graph(kind, k, weights)
    return g, _iterate(g, k, g.src.shape[0]), _iterate(g, k, 1)


@pytest.mark.parametrize("kind, k, weights", CASES)
def test_row_and_edge_branch_return_the_same_bits(kind, k, weights):
    g, by_rows, by_edges = _both(kind, k, weights)
    assert [o.rows for o in by_rows] == [1, 1]
    assert [o.rows for o in by_edges] == [0, 0]
    start, _ = _start(g, k)
    assert (by_rows[0].part != np.asarray(start)).any()  # it did move
    assert by_rows[1].lock.any()
    for rows, edges in zip(by_rows, by_edges):
        np.testing.assert_array_equal(rows.part, edges.part)
        np.testing.assert_array_equal(rows.lock, edges.lock)
        assert rows.ext_sum == edges.ext_sum > 0
        np.testing.assert_array_equal(rows.conn, edges.conn)
        assert rows.pruned == edges.pruned == 0
    if weights == "heavy":
        # the first iteration's filter ran the exact branch on both
        part, _ = _start(g, k)
        candidate, gain = _candidates(g, part, jnp.zeros_like(part), k)
        assert int(jnp.max(jnp.where(candidate, jnp.abs(gain), 0))) >= (
            _packed_half(k))


@pytest.mark.parametrize("kind, k, weights", CASES)
def test_the_table_is_the_rebuilt_one_on_both_branches(kind, k, weights):
    g, by_rows, by_edges = _both(kind, k, weights)
    for out in by_rows + by_edges:
        np.testing.assert_array_equal(
            out.conn,
            np.asarray(jet_mod._full_ratings(g, jnp.asarray(out.part), k)))
    # the row branch serves the Jet moves' reconcile from its buffer
    assert all(o.conn_delta >= 1 for o in by_rows)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("over", [0, 1], ids=["fits", "one-over"])
def test_the_branch_follows_the_candidates_summed_degree(kind, over):
    """Rows while the candidates' degrees sum to at most the buffer, the
    edge array from one more slot on."""
    k = 4
    g = _graph(kind, k, "unit")
    part, _ = _start(g, k)
    cand_edges = _cand_edges(g, part, jnp.zeros_like(part), k)
    assert cand_edges > 1
    (out,) = _iterate(g, k, cand_edges - over, steps=1)
    assert out.rows == 1 - over
    (wide,) = _iterate(g, k, g.src.shape[0], steps=1)
    np.testing.assert_array_equal(out.part, wide.part)
    np.testing.assert_array_equal(out.conn, wide.conn)


def _refine(g, k: int, part, caps):
    """One `jet_refine` (coarse budget: 12 iterations) with telemetry on:
    (partition, the `rows` column, the `conn_delta` column)."""
    was_enabled = telemetry.enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        out = np.asarray(jet_mod.jet_refine(
            g, part, k, caps, jnp.int32(3), JetRefinementContext(), 1, 2))
        series = [s.series for s in telemetry.progress_series("jet")]
    finally:
        telemetry.enable() if was_enabled else telemetry.disable()
    assert all(list(s) == ["cut", "moved", "fruitless", "conn_delta",
                           "pruned", "rows", "wide"] for s in series)
    return (out, [r for s in series for r in s["rows"]],
            [d for s in series for d in s["conn_delta"]])


def _noisy(reference, k: int):
    """A plain reference partition with 3 % of its labels redrawn: what a
    refiner is handed (a projected partition, wrong along its borders
    and in a few places inside), unlike `_start`'s random labels."""
    rng = np.random.default_rng(k)
    part = np.array(reference, dtype=np.int32)
    redrawn = rng.random(len(part)) < 0.03
    part[redrawn] = rng.integers(0, k, int(redrawn.sum()))
    return part


#: the cell's meshes run 24k-787k slots in 2^20 (76-98 % padding); here
#: ~24k in 2^17, so the buffer is to the border what it is on the chip
MESH_M_PAD = 1 << 17


def _delaunay(k: int):
    points, host = delaunay_mesh(4096, 1)
    return host, MESH_M_PAD, _noisy(
        recursive_coordinate_bisection(points, k), k)


def _fe_grid(k: int):
    return factories.make_fe_grid(64, 64), MESH_M_PAD, _noisy(
        rectangles(64, 64, k), k)


def _rmat(k: int):
    # padded as the chip pads; a random start, whose first iterations
    # overflow the shipped buffer
    return factories.make_rmat(1 << 10, 12_000, seed=13), 1 << 16, None


#: the meshes tests/test_mesh_deployment.py and
#: tests/test_strong_deployment.py partition, and a skewed graph
REFINE_GRAPHS = {"delaunay-4096": _delaunay, "fe-grid-64x64": _fe_grid,
                 "rmat-1024": _rmat}


@pytest.mark.parametrize("name, k", [
    ("delaunay-4096", 16), ("fe-grid-64x64", 16), ("fe-grid-64x64", 2),
    ("rmat-1024", 4)])
def test_jet_refine_replays_and_matches_the_edge_wide_refiner(
        monkeypatch, name, k):
    """A whole refiner call at the shipped buffer replays bit for bit,
    and returns the partition of the refiner that has no row branch (the
    parent's iteration: edge-wide afterburner, then `_conn_step`)."""
    host, m_pad, labels = REFINE_GRAPHS[name](k)
    g = device_graph_from_host(host, m_pad=m_pad)
    assert jet_mod.iteration_path(g, k) == "jet-edges"
    part, caps = _start(g, k)
    if labels is not None:
        part = part.at[: len(labels)].set(labels)
    jet_mod._jet_chunk.clear_cache()
    shipped, rows, delta = _refine(g, k, part, caps)
    replay, rows_again, _ = _refine(g, k, part, caps)
    np.testing.assert_array_equal(shipped, replay)
    assert rows == rows_again and set(rows) <= {0, 1} and len(rows) == 12
    assert (shipped != np.asarray(part)).any()
    # a row iteration always counts its own reconcile
    assert all(d >= r for d, r in zip(delta, rows))
    if labels is None:
        assert set(rows) == {0, 1}  # both sides of the choice in one call
    else:
        assert set(rows) == {1}  # a mesh's border holds few candidates

    def as_the_parent(graph, conn, part, next_part, gain, candidate, k,
                      slots):
        accept, _ = jet_mod._edges_filter(
            graph, part, next_part, gain, candidate, k)
        moved = jnp.where(accept, next_part, part)
        return accept, jet_mod._conn_step(
            graph, conn, part, moved, k, slots)[0]

    monkeypatch.setattr(jet_mod, "_rows_filter", as_the_parent)
    jet_mod._jet_chunk.clear_cache()
    try:
        parent, none, _ = _refine(g, k, part, caps)
    finally:
        jet_mod._jet_chunk.clear_cache()
    assert set(none) == set(rows)  # the column follows the choice made
    np.testing.assert_array_equal(shipped, parent)
