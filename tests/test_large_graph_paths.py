"""The large-graph side of the `1 << 22` gates at a small size, through
the facade, against the plain host reference: the benchmark cell
`rmat-s17.k2` (Graph500-style R-MAT, n = 2^17, 2.2 M directed slots,
`m_pad` 2^22) is this on the chip.

From `DELTA_MIN_EDGE_SLOTS` slots on a Jet iteration always runs its
afterburner over the candidates' rows (`jet-rows`): through `_conn_slots`
where they fit it, pruned to and through `_delta_slots` where they do
not; the coarse budget is 8, and LP takes delta rounds.  The
tests lower both gates to the graph's own `m_pad`, so every level of the
small graph is "large".
"""

import json
import math
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kaminpar_tpu as ktp
import kaminpar_tpu.ops.jet as jet_mod
import kaminpar_tpu.ops.lp as lp_mod
from kaminpar_tpu import telemetry
from kaminpar_tpu.graphs import device_graph_from_host, factories
from kaminpar_tpu.graphs.host import host_partition_metrics
from kaminpar_tpu.utils import timer
from kaminpar_tpu.utils.logger import OutputLevel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "perfbench", "configs", "rmat-s17-default.json")
EPSILON = 0.03
#: two levels, a coarse Jet call that prunes, ~5 s a partition on the CPU
N = 1 << 13
EDGE_FACTOR = 9.16  # the configuration's requested edges a node


def _rmat(seed: int):
    with open(CONFIG) as f:
        params = json.load(f)["params"]
    return factories.make_rmat(N, int(EDGE_FACTOR * N), params["a"],
                               params["b"], params["c"], seed=seed)


def _gates(monkeypatch, slots: int) -> None:
    """Both modules read their gate while tracing: set it, and drop what
    was traced under another."""
    monkeypatch.setattr(jet_mod, "DELTA_MIN_EDGE_SLOTS", slots)
    monkeypatch.setattr(lp_mod, "DELTA_MIN_EDGE_SLOTS", slots)
    jax.clear_caches()


def _tree(node, path=""):
    """{dotted path: count} of a timer tree."""
    out = {}
    for name, child in node.children.items():
        child_path = f"{path}.{name}" if path else name
        out[child_path] = child.count
        out.update(_tree(child, child_path))
    return out


def _partition(graph, k: int, seed: int, gated=None) -> SimpleNamespace:
    """One request through the facade with telemetry on: the partition,
    what the program reports of it, its timer tree, the `_delta_slots` of
    every graph Jet was called on, the `jet` progress series, and what
    `gated` (a list the fixture's `_gated_rows_filter` appends to) took
    during the request."""
    resolved = []
    real = jet_mod.jet_refine
    start = len(gated) if gated is not None else 0

    def recording(g, *args, **kwargs):
        resolved.append(jet_mod._delta_slots(g))
        return real(g, *args, **kwargs)

    solver = ktp.KaMinPar("default")
    solver.set_output_level(OutputLevel.QUIET)
    was_enabled = telemetry.enabled()
    telemetry.reset()
    telemetry.enable()
    jet_mod.jet_refine = recording  # the refiner imports it at the call
    try:
        part = np.asarray(solver.set_graph(graph).compute_partition(
            k=k, epsilon=EPSILON, seed=seed))
        series = [(s.attrs["level"], dict(s.series))
                  for s in telemetry.progress_series("jet")]
        jax.effects_barrier()
    finally:
        jet_mod.jet_refine = real
        telemetry.enable() if was_enabled else telemetry.disable()
    return SimpleNamespace(
        part=part, reported=solver.result_metrics(graph, part),
        tree=_tree(timer.GLOBAL_TIMER.root), resolved=resolved,
        series=series, gated=list(gated[start:]) if gated else [])


def _recording_gated_filter(gated: list):
    """`_gated_rows_filter` that appends, for every iteration it runs,
    (the candidates' summed degree, `_conn_slots`, wide, pruned)."""
    real = jet_mod._gated_rows_filter

    def record(candidate, degrees, conn_slots, wide, pruned):
        gated.append((int(np.where(candidate, degrees, 0).sum()),
                      int(conn_slots), int(wide), int(pruned)))

    def recording(graph, conn, part, best, gain, candidate, k, salt, dslots,
                  conn_slots):
        out = real(graph, conn, part, best, gain, candidate, k, salt,
                   dslots, conn_slots)
        jax.debug.callback(record, candidate, graph.degrees,
                           jnp.int32(conn_slots), out[3], out[2])
        return out

    return recording


@pytest.fixture(scope="module", params=[(2, 1), (2, 2), (4, 1), (4, 2)],
                ids=lambda p: f"k{p[0]}-seed{p[1]}")
def case(request):
    k, seed = request.param
    graph = _rmat(2 + seed)
    m_pad = device_graph_from_host(graph).src.shape[0]
    patch = pytest.MonkeyPatch()
    gated = []
    real_conn_slots = jet_mod._conn_slots
    try:
        closed = _partition(graph, k, seed)
        _gates(patch, m_pad)
        patch.setattr(jet_mod, "_gated_rows_filter",
                      _recording_gated_filter(gated))
        opened = _partition(graph, k, seed, gated)
        replay = _partition(graph, k, seed, gated)
        # past the gate, no narrow branch: every iteration prunes to and
        # filters through `_delta_slots`, as before the branch existed
        patch.setattr(jet_mod, "_conn_slots", lambda g: (
            0 if jet_mod._delta_slots(g) is not None else real_conn_slots(g)))
        jax.clear_caches()
        wide = _partition(graph, k, seed, gated)
    finally:
        patch.undo()
        jax.clear_caches()
    return SimpleNamespace(k=k, graph=graph, closed=closed, opened=opened,
                           replay=replay, wide=wide)


def test_rows_path_partition_against_the_host_reference(case):
    part, k, graph = case.opened.part, case.k, case.graph
    assert part.shape == (graph.n,)
    assert part.min() >= 0 and part.max() < k
    recount = host_partition_metrics(graph, part, k)
    assert recount["cut"] == case.opened.reported["cut"] > 0
    bound = (1 + EPSILON) * math.ceil(graph.total_node_weight / k)
    assert recount["block_weights"].max() <= bound


def test_rows_path_replay_is_bitwise_equal(case):
    np.testing.assert_array_equal(case.opened.part, case.replay.part)


def test_rows_path_cut_is_in_the_class_of_the_edge_wide_path(case):
    opened = host_partition_metrics(case.graph, case.opened.part, case.k)
    closed = host_partition_metrics(case.graph, case.closed.part, case.k)
    assert opened["cut"] <= 1.1 * closed["cut"]


def test_every_jet_scope_names_the_iteration_it_resolved_to(case):
    """Exactly one of `jet-rows` / `jet-edges` directly under every `jet`,
    `jet-rows` exactly where `_delta_slots` gives a buffer."""
    for run, path in ((case.opened, "jet-rows"), (case.closed, "jet-edges")):
        jets = {p: c for p, c in run.tree.items() if p.endswith(".jet")}
        assert jets and sum(jets.values()) == len(run.resolved)
        for jet, count in jets.items():
            below = {p[len(jet) + 1:]: c for p, c in run.tree.items()
                     if p.startswith(jet + ".")}
            assert set(below) == {path}, below
            assert below[path] == count
        assert all((slots is not None) == (path == "jet-rows")
                   for slots in run.resolved)
    # every level of this graph pads to one bucket, so the open gate
    # takes all of them; the coarse call then has the budget of 8
    assert {len(s["cut"]) for level, s in case.opened.series
            if level > 0} == {8}
    assert {len(s["cut"]) for level, s in case.closed.series
            if level > 0} == {12}


def test_pruned_rides_the_progress_series(case):
    assert all(set(s["pruned"]) == {0} for _, s in case.closed.series)
    assert all(len(s) == 7 and min(s["pruned"]) >= 0
               for _, s in case.opened.series)
    # a prune runs only where the candidates overflow `_conn_slots`
    assert all(w == 1 for _, s in case.opened.series
               for p, w in zip(s["pruned"], s["wide"]) if p > 0)
    if case.k == 4:
        # the coarse call's first iteration finds more rows than
        # m_pad // 4 slots hold (at k = 2 only by the luck of the seed)
        assert sum(sum(s["pruned"]) for level, s in case.opened.series
                   if level > 0) > 0


def test_conn_delta_rides_the_progress_series(case):
    """0, 1 or 2 reconciles an iteration served by rows; past the gate
    the Jet moves' own always is (it reuses the afterburner's rows), and
    the counter replays with the partition."""
    assert all(set(s["conn_delta"]) <= {0, 1, 2}
               for _, s in case.closed.series)
    assert all(set(s["conn_delta"]) <= {1, 2} for _, s in case.opened.series)
    assert ([s["conn_delta"] for _, s in case.opened.series]
            == [s["conn_delta"] for _, s in case.replay.series])


def test_rows_rides_the_progress_series(case):
    """The sixth column: 1 in every iteration of a `jet-rows` call (the
    candidates fit `_conn_slots` or are pruned to `_delta_slots`); under
    the gate 1 where the candidates' rows fit `_conn_slots` and 0 where
    the afterburner ran edge-wide, and a row iteration counts its own
    reconcile.  The seventh, `wide`: 0 under the gate; past it 1 exactly
    where the candidates' rows overflow `_conn_slots`."""
    assert all(list(s)[5:] == ["rows", "wide"] for _, s in case.opened.series)
    assert all(set(s["rows"]) == {1} for _, s in case.opened.series)
    assert all(set(s["wide"]) == {0} for _, s in case.closed.series)
    opened = [w for _, s in case.opened.series for w in s["wide"]]
    assert sorted(opened) == sorted(w for _, _, w, _ in case.opened.gated)
    assert all(w == int(edges > slots)
               for edges, slots, w, _ in case.opened.gated)
    if case.k == 4:
        assert 1 in opened  # the coarse call's pruning iteration
    assert opened == [w for _, s in case.replay.series for w in s["wide"]]
    closed = [s for _, s in case.closed.series]
    assert all(set(s["rows"]) <= {0, 1} for s in closed)
    assert {r for s in closed for r in s["rows"]} == {0, 1}
    assert all(d >= r for s in closed
               for d, r in zip(s["conn_delta"], s["rows"]))
    assert ([s["rows"] for _, s in case.opened.series]
            == [s["rows"] for _, s in case.replay.series])


def test_the_narrow_branch_leaves_the_partition_bitwise(case):
    """Past the gate an iteration whose candidates' rows fit `_conn_slots`
    skips the prune and filters through that buffer: the labels, the cut
    and the prune's count are those of the run that always prunes to and
    filters through `_delta_slots` (`conn_delta`, `rows` and `wide` may
    differ: that run's reconciles rebuild)."""
    np.testing.assert_array_equal(case.opened.part, case.wide.part)
    for column in ("cut", "pruned"):
        assert ([s[column] for _, s in case.opened.series]
                == [s[column] for _, s in case.wide.series])
    assert all(set(s["wide"]) == {1} for _, s in case.wide.series)
    assert all(w == 1 for _, _, w, _ in case.wide.gated)


def _iteration(graph, k):
    part = jnp.asarray(
        (np.arange(graph.n_pad) % k).astype(np.int32))
    caps = jnp.full(k, graph.n_pad, dtype=jnp.int32)
    return jet_mod._jet_iteration(
        graph, part, jnp.zeros_like(part), k, caps, jnp.float32(0.75),
        jnp.int32(5), 4)


@pytest.mark.parametrize("budget",
                         ["edge-wide", "narrow", "full-width", "tight"])
def test_pruned_counts_what_the_budget_drops(monkeypatch, budget):
    """0 on the edge-wide path, and where the candidates' rows fit
    `_conn_slots` (`narrow`: the prune never runs, `wide` is 0); past
    that, where every candidate fits the pruning buffer, and candidates
    before less candidates after with a tight one (`wide` 1)."""
    graph = device_graph_from_host(
        factories.make_rmat(1 << 10, 12_000, seed=13))
    m_pad = graph.src.shape[0]
    seen = []
    real = jet_mod.prune_candidates_to_budget

    def recording(candidate, *args):
        kept = real(candidate, *args)
        # the prune runs inside a lax.cond branch: count what it ran on
        jax.debug.callback(
            lambda c, kp: seen.append((int(c.sum()), int(kp.sum()))),
            candidate, kept)
        return kept

    monkeypatch.setattr(jet_mod, "prune_candidates_to_budget", recording)
    slots = {"edge-wide": None, "narrow": m_pad, "full-width": m_pad,
             "tight": m_pad // 64}
    monkeypatch.setattr(jet_mod, "_delta_slots", lambda g: slots[budget])
    if budget == "narrow":
        monkeypatch.setattr(jet_mod, "_conn_slots", lambda g: m_pad)
    out = _iteration(graph, 4)
    jax.effects_barrier()
    pruned, wide = int(out[5]), int(out[7])
    if budget in ("edge-wide", "narrow"):
        assert not seen and pruned == 0 and wide == 0
        return
    ((before, after),) = seen
    assert pruned == before - after
    assert (pruned > 0) == (budget == "tight")
    assert wide == 1


def test_iteration_path_follows_the_shapes(monkeypatch):
    graph = device_graph_from_host(factories.make_grid_graph(8, 8))
    assert jet_mod._delta_slots(graph) is None
    assert jet_mod.iteration_path(graph, 4) == "jet-edges"
    monkeypatch.setattr(jet_mod, "DELTA_MIN_EDGE_SLOTS", graph.src.shape[0])
    assert jet_mod.iteration_path(graph, 4) == "jet-rows"
    monkeypatch.setattr(jet_mod, "JET_DENSE_MAX_ENTRIES", graph.n_pad)
    assert jet_mod.iteration_path(graph, 4) == "jet-lp"


@pytest.mark.parametrize("graph_seed", [3, 4])
def test_the_configuration_crosses_the_gate(graph_seed):
    """`rmat-s17-default` gives more than 2^21 and at most 2^22 directed
    slots, so level 0 of the cell pads to 2^22 and takes the large-graph
    paths: the cell cannot slide under the gate unnoticed."""
    from perfbench.generators import rmat

    with open(CONFIG) as f:
        config = json.load(f)
    assert config["graph_seed_base"] + 1 == 3
    csr = rmat.generate(config["params"], graph_seed)
    assert len(csr["xadj"]) - 1 == 1 << 17
    assert 1 << 21 < len(csr["adjncy"]) <= jet_mod.DELTA_MIN_EDGE_SLOTS
    assert jet_mod.DELTA_MIN_EDGE_SLOTS == lp_mod.DELTA_MIN_EDGE_SLOTS == 1 << 22
