"""The direct k-way scheme under the `mtkahypar-kway` preset at a small
size, through the facade, against a plain reference: the benchmark cell
`rmat-s16-kway.k64` is this request on an R-MAT of 2^16 nodes at k = 64
on the chip; here an R-MAT of 2^12 nodes at k = 8.  The scheme (`partitioning/kway.py`) coarsens until
n <= 160 k, cuts the coarsest graph into k blocks on the host by
recursive bipartition, and refines at the full k on every level.

One module fixture serves the request twice at one seed, with the
hierarchy's projections, the initial partition and the program's shape
account recorded on the way; every check below is plain numpy.  The
graph is an R-MAT's core, its isolated nodes dropped: where the facade
sets isolated nodes aside, the block caps stay those of the whole graph
(as the reference sets them up), so the core may leave a block empty for
the isolated nodes to fill, and only under the graph's own caps must
the coarsest partition use all k blocks.  As on the chip, every level
pads into one shape bucket (here (2^12, 2^16) instead of the chip's
(2^13, 2^20)), so the hierarchy's five levels compile one set of
programs.
"""

from types import SimpleNamespace

import numpy as np
import pytest

import kaminpar_tpu as ktp
from kaminpar_tpu.graphs import factories
from kaminpar_tpu.graphs import csr as csr_mod
from kaminpar_tpu.graphs.csr import host_graph_from_device
from kaminpar_tpu.graphs.host import remove_isolated_nodes
from kaminpar_tpu.ops import segments
from kaminpar_tpu.partitioning import kway
from kaminpar_tpu.partitioning.coarsener import Coarsener
from kaminpar_tpu.telemetry import compile_account
from kaminpar_tpu.telemetry.request_account import (
    RequestAccount,
    shape_account,
)
from kaminpar_tpu.utils import statistics, timer
from kaminpar_tpu.utils.logger import OutputLevel

PRESET = "mtkahypar-kway"
K, EPSILON, SEED = 8, 0.03, 1
FLOORS = (1 << 12, 1 << 16)


def _cut(graph, part) -> int:
    """Plain-numpy edge cut of a host graph under a partition."""
    part = np.asarray(part)
    src = np.repeat(np.arange(graph.n), np.diff(graph.xadj))
    w = graph.edge_weight_array()
    return int(w[part[src] != part[graph.adjncy]].sum()) // 2


def _paths(node, path=""):
    """Dotted paths of a timer node's descendants."""
    out = set()
    for name, child in node.children.items():
        child_path = f"{path}.{name}" if path else name
        out |= {child_path} | _paths(child, child_path)
    return out


@pytest.fixture(scope="module")
def served():
    graph, _, _ = remove_isolated_nodes(
        factories.make_rmat(1 << 12, 30_000, seed=3))
    record = {"levels": [], "initial": [], "requests": []}
    uncoarsen, bipartition = Coarsener.uncoarsen, kway.recursive_bipartition

    def recorded_uncoarsen(self, partition):
        level = self.levels[-1]
        coarse = host_graph_from_device(self.current)
        cmap = np.asarray(level.coarse.cmap)[: level.fine_n]
        coarse_part = np.asarray(partition)[: coarse.n]  # donated below
        fine, projected = uncoarsen(self, partition)
        record["levels"].append({
            "coarse": coarse,
            "coarse_part": coarse_part,
            "fine": host_graph_from_device(fine),
            "projected": np.asarray(projected)[: level.fine_n],
            "cmap": cmap,
        })
        return fine, projected

    def recorded_bipartition(coarsest, k, ctx, rng=None, **kwargs):
        part = bipartition(coarsest, k, ctx, rng, **kwargs)
        record["initial"].append((coarsest.n, k, np.array(part)))
        return part

    was_enabled = statistics.enabled()
    statistics.enable()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(csr_mod, "shape_floors", lambda: FLOORS)
        mp.setattr(Coarsener, "uncoarsen", recorded_uncoarsen)
        mp.setattr(kway, "recursive_bipartition", recorded_bipartition)
        try:
            for _ in range(2):
                levels_before = len(record["levels"])
                solver = ktp.KaMinPar(PRESET)
                solver.set_output_level(OutputLevel.QUIET)
                part = solver.set_graph(graph).compute_partition(
                    k=K, epsilon=EPSILON, seed=SEED)
                record["requests"].append({
                    "part": part,
                    "reported_cut": solver.result_metrics(graph, part)["cut"],
                    "ordinal": compile_account.requests_begun(),
                    "levels": len(record["levels"]) - levels_before,
                    # the facade resets the counters at a request's start
                    "stats": statistics.counters_snapshot(),
                    "scopes": _paths(timer.GLOBAL_TIMER.root),
                })
        finally:
            if not was_enabled:
                statistics.disable()
    record["graph"] = graph
    record["account"] = shape_account.summary()
    return record


def test_mtkahypar_kway_partition_is_feasible_with_the_recounted_cut(served):
    graph = served["graph"]
    first = served["requests"][0]
    part = np.asarray(first["part"])
    assert part.shape == (graph.n,)
    assert part.min() >= 0 and part.max() < K
    weights = np.bincount(part, weights=graph.node_weight_array(),
                          minlength=K)
    cap = (1 + EPSILON) * -(-graph.total_node_weight // K)
    assert weights.max() <= cap
    assert first["reported_cut"] == _cut(graph, part)
    assert graph.degrees().min() > 0
    assert first["levels"] >= 2  # the scheme really coarsened


def test_mtkahypar_kway_replays_bitwise(served):
    first, second = served["requests"]
    np.testing.assert_array_equal(first["part"], second["part"])


def test_projection_keeps_the_cut_level_by_level(served):
    levels = served["levels"][: served["requests"][0]["levels"]]
    assert len(levels) >= 2
    for level in levels:
        # the coarsener's projection is the plain gather through cmap
        np.testing.assert_array_equal(
            level["projected"], level["coarse_part"][level["cmap"]])
        assert _cut(level["coarse"], level["coarse_part"]) == _cut(
            level["fine"], level["projected"])


def test_initial_partitioning_returns_k_nonempty_blocks(served):
    coarsest = served["levels"][0]["coarse"]  # uncoarsened first
    assert len(served["initial"]) == 2  # one per request
    n, k, part = served["initial"][0]
    assert (n, k) == (coarsest.n, K)
    assert part.shape == (n,)
    np.testing.assert_array_equal(np.unique(part), np.arange(K))


def test_shape_account_counts_levels_and_scatters(served):
    by_request = served["account"]["by_request"]
    for request in served["requests"]:
        counts = by_request[request["ordinal"]]
        assert counts["coarsening_levels"] == request["levels"]
        assert counts["conn_scatter_calls"] == request["stats"].get(
            "conn_scattered", 0)
        assert request["stats"]["conn_streamed"] > 0  # counted at all


def test_kway_readbacks_have_their_scopes(served):
    """Each host readback of the scheme sits in a leaf scope of its own,
    as deep's do, so the device's idle there is named."""
    scopes = served["requests"][0]["scopes"]
    for path in ("partitioning.initial-partitioning.graph-download",
                 "partitioning.balance-check",
                 "partitioning.partition-download"):
        assert path in scopes


@pytest.mark.parametrize("k,n_pad,m_pad,scatters", [
    # rmat-s16-kway.k64 on the chip: level 0 scatters, the coarse levels
    # stream in 8 steps
    (64, 1 << 16, 1 << 21, 1), (64, 1 << 15, 1 << 20, 0),
    (64, 1 << 14, 1 << 20, 0),
])
def test_conn_scatter_calls_counts_the_scatter_side(k, n_pad, m_pad,
                                                    scatters):
    def scatter_calls():
        entry = shape_account.summary()["by_request"].get(
            compile_account.open_request(), {})
        return entry.get("conn_scatter_calls", 0)

    before = scatter_calls()
    segments.count_conn_engine(SimpleNamespace(n_pad=n_pad, m_pad=m_pad), k)
    assert scatter_calls() - before == scatters


def test_shape_account_reads_zero_where_no_request_ran():
    account = RequestAccount(shape_account.counters)
    summary = account.summary()
    assert summary["by_request"] == {}
    for counter in ("coarsening_levels", "conn_scatter_calls"):
        assert summary["by_request"].get(summary["requests"], {}).get(
            counter, 0) == 0
    account.add({"coarsening_levels": 2})
    assert account.summary()["by_request"][compile_account.open_request()] \
        == {"coarsening_levels": 2, "conn_scatter_calls": 0}
