"""The mesh deployment at a small size, through the facade, against its
plain reference (tests/mesh_reference.py): the benchmark cell
`delaunay-n17.k16` (DIMACS-10 Delaunay family, k = 16, eps = 0.03) is
this at n = 2^17 on the chip.

Every case runs the facade twice (a request and its replay) with
telemetry on, the replay inside a profiler session: the `rating-engine`
events say which engine each level took, the trace says under which
span name the benchmark finds it.
"""

import glob
import math
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kaminpar_tpu as ktp
from kaminpar_tpu import telemetry
from kaminpar_tpu.context import JetRefinementContext
from kaminpar_tpu.graphs import device_graph_from_host
from kaminpar_tpu.graphs.host import host_partition_metrics
from kaminpar_tpu.ops import balancer, jet, segments
from kaminpar_tpu.utils import timer
from kaminpar_tpu.utils.logger import OutputLevel
from mesh_reference import delaunay_mesh, recursive_coordinate_bisection

EPSILON = 0.03
POINT_SEED = 1
#: name -> (points, k).  4,096 = 2^12 exactly is the cell's pad boundary
#: in small: the n + 1 row pointers pad to 2n, as 2^17 does to 2^18
CASES = {"n8192-k16": (8192, 16), "n4096-k16": (4096, 16),
         "n8192-k2": (8192, 2)}
ENGINE_SCOPE = "partitioning.coarsening.lp-clustering.rating-sort2"


def _tree_paths(node, path=""):
    out = set()
    for name, child in node.children.items():
        child_path = f"{path}.{name}" if path else name
        out.add(child_path)
        out |= _tree_paths(child, child_path)
    return out


@pytest.fixture(scope="module", params=list(CASES))
def case(request, tmp_path_factory):
    from jax.profiler import ProfileData

    n, k = CASES[request.param]
    points, graph = delaunay_mesh(n, POINT_SEED)
    solver = ktp.KaMinPar("default")
    solver.set_output_level(OutputLevel.QUIET)
    was_enabled = telemetry.enabled()
    telemetry.enable()
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    try:
        part = solver.set_graph(graph).compute_partition(
            k=k, epsilon=EPSILON, seed=1)
        engines = [(e.attrs["level"], e.attrs["engine"])
                   for e in telemetry.events("rating-engine")]
        reported = solver.result_metrics(graph, part)
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            replay = solver.set_graph(graph).compute_partition(
                k=k, epsilon=EPSILON, seed=1)
        finally:
            jax.profiler.stop_trace()
    finally:
        telemetry.enable() if was_enabled else telemetry.disable()
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    span_names = {ev.name for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith(timer.SPAN_PREFIX)}
    return SimpleNamespace(
        n=n, k=k, points=points, graph=graph, part=np.asarray(part),
        replay=np.asarray(replay), engines=engines, reported=reported,
        tree=_tree_paths(timer.GLOBAL_TIMER.root), span_names=span_names)


def test_recounted_cut_is_the_reported_cut(case):
    assert case.part.shape == (case.n,)
    assert case.part.min() >= 0 and case.part.max() < case.k
    recount = host_partition_metrics(case.graph, case.part, case.k)
    assert recount["cut"] == case.reported["cut"] > 0
    assert case.reported["feasible"]


def test_every_block_is_within_the_strict_bound(case):
    weights = np.bincount(case.part, minlength=case.k)
    assert weights.sum() == case.n
    assert weights.max() <= (1 + EPSILON) * math.ceil(case.n / case.k)


def test_a_replay_is_bitwise_equal(case):
    np.testing.assert_array_equal(case.part, case.replay)


def test_every_level_rates_with_sort2(case):
    # degree 6 and no skew: select_engine's skew window [8, 4096] bars
    # the scatter engine on every level of a mesh
    assert case.engines and {e for _, e in case.engines} == {"sort2"}
    assert [level for level, _ in case.engines] == list(
        range(len(case.engines)))
    # the engine is a scope of its own under lp-clustering, and a span
    # of that name in a profiler trace (telemetry is off in a measured
    # run: the span is all the benchmark sees)
    assert ENGINE_SCOPE in case.tree
    assert timer.SPAN_PREFIX + ENGINE_SCOPE in case.span_names
    clusterings = {p for p in case.tree if p.endswith(".lp-clustering")}
    assert clusterings and all(
        [c for c in case.tree if c.startswith(p + ".")]
        == [p + ".rating-sort2"] for p in clusterings)


def test_cut_is_no_worse_than_coordinate_bisection(case):
    """Recursive coordinate bisection sees the points and not the edges,
    and is exactly balanced; the multilevel partitioner may use the 3 %.
    Measured (point seed 1): 1,011 against 1,224 at n = 8,192, k = 16
    (0.83x), 4,318 against 4,822 at n = 2^17 (0.90x); with one native
    bipartition attempt a call, before PR 26's eight, 1,093 and 4,487.
    At k = 2 one straight line through uniform points is near optimal
    (187 against 192), so the bound is parity with 5 % of room for a
    small cut's noise between seeds; a partitioner that lost its
    refinement reads 1.3x and more."""
    reference = recursive_coordinate_bisection(case.points, case.k)
    assert np.bincount(reference, minlength=case.k).max() == math.ceil(
        case.n / case.k)
    reference_cut = host_partition_metrics(
        case.graph, reference, case.k)["cut"]
    assert case.reported["cut"] <= 1.05 * reference_cut


def test_pad_boundary_n_plus_one_pads_to_2n():
    """n = 2^12 exactly: `device_graph_from_host` pads the n + 1 row
    pointers, so `n_pad` is 2n (the cell's level 0 is (2^18, 2^20)); the
    last real node keeps its row, its weight and its label."""
    n = 4096
    _, graph = delaunay_mesh(n, POINT_SEED)
    device = device_graph_from_host(graph)
    assert device.n_pad == 2 * n
    row_ptr = np.asarray(device.row_ptr)
    assert row_ptr[n] == graph.m and (row_ptr[n:] == graph.m).all()
    assert row_ptr[n] - row_ptr[n - 1] == graph.xadj[n] - graph.xadj[n - 1]
    node_w = np.asarray(device.node_w)
    assert node_w[:n].sum() == n and node_w[n:].sum() == 0


def test_last_real_node_is_labelled_like_its_neighbourhood(case):
    """The last node's label is a block one of its neighbours is in (an
    off-by-one at the pad boundary would leave it in a stale block)."""
    last = case.n - 1
    neighbours = case.graph.adjncy[
        case.graph.xadj[last]:case.graph.xadj[last + 1]]
    assert case.part[last] in set(case.part[neighbours].tolist())


@pytest.mark.parametrize("streams", [True, False])
def test_conn_engine_forced_each_way_gives_the_same_partition(
        streams, monkeypatch):
    """One small mesh level at k = 16 through Jet and the overload
    balancer with `conn_table_streams` forced to each answer, against
    the rule's own choice: bit for bit (the cell runs both sides: six
    refiner calls stream, level 0 at k = 16 keeps the `segment_sum`)."""
    k = 16
    _, graph = delaunay_mesh(2048, POINT_SEED)
    g = device_graph_from_host(graph)
    rng = np.random.default_rng(2)
    part = np.zeros(g.n_pad, np.int32)
    part[: graph.n] = rng.integers(0, k, graph.n)
    part = jnp.asarray(part)
    caps = jnp.full(k, int((1 + EPSILON) * math.ceil(graph.n / k)),
                    dtype=jnp.int32)

    def refine():
        moved = jet.jet_refine(g, part, k, caps, jnp.int32(7),
                               JetRefinementContext())
        return np.asarray(balancer.overload_balance(
            g, moved, k, caps, jnp.int32(1)))

    chosen = refine()
    monkeypatch.setattr(segments, "conn_table_streams",
                        lambda k, n_pad, m_pad: streams)
    jax.clear_caches()  # the jitted refiners must trace the forced rule
    try:
        forced = refine()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert (chosen != np.asarray(part)).any()  # the refiners did move
    np.testing.assert_array_equal(chosen, forced)
