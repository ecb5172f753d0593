"""The mesh deployment under the `strong` preset at a small size, through
the facade, against a plain reference: the benchmark cell
`delaunay-n17-strong.k16` (DIMACS-10 `delaunay_n17`, k = 16, eps = 0.03)
is the Delaunay case at n = 2^17 on the chip.  `strong` is another
pipeline than `default`, not other numbers: Jet and the host k-way FM
alternated, no LP refinement.  The meshes: a Delaunay triangulation
against the recursive coordinate bisection of its points
(tests/mesh_reference.py), and the triangulated FE grid
(`factories.make_fe_grid`) against its rectangles
(tests/fegrid_reference.py).

Every case runs `strong` at seeds 1-3, each as a request and its replay,
and `default` at the same seeds; seed 1's replay has every refiner
pipeline's level, `light` flag and FM calls recorded on the way.  Below,
the host FM engines alone against a recount.  (The spans under `kway-fm`
are tests/test_timer_annotations.py's.)
"""

import math
from statistics import median
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import kaminpar_tpu as ktp
from fegrid_reference import cut_of, rectangles
from mesh_reference import delaunay_mesh, recursive_coordinate_bisection
from kaminpar_tpu import native
from kaminpar_tpu.context import FMRefinementContext
from kaminpar_tpu.graphs import device_graph_from_host, factories
from kaminpar_tpu.graphs.host import HostGraph, host_partition_metrics
from kaminpar_tpu.partitioning import refiner as refiner_mod
from kaminpar_tpu.refinement.fm import fm_refine_host
from kaminpar_tpu.utils import timer
from kaminpar_tpu.utils.logger import OutputLevel

EPSILON = 0.03
SEEDS = (1, 2, 3)
#: name -> (family, size, k): a Delaunay mesh of `size` points, or the
#: FE grid of `size` x `size` vertices
CASES = {"delaunay-4096-k16": ("delaunay", 4096, 16),
         "grid-64x64-k16": ("grid", 64, 16),
         "grid-96x96-k16": ("grid", 96, 16),
         "grid-64x64-k2": ("grid", 64, 2)}
FM_SCOPE = "kway-fm"
UNDER_FM = {"graph-download", "fm-native", "partition-upload"}


def _tree(node, path=""):
    """{dotted path: count} of a TimerNode's descendants."""
    out = {}
    for name, child in node.children.items():
        child_path = f"{path}.{name}" if path else name
        out[child_path] = child.count
        out.update(_tree(child, child_path))
    return out


def _fm_calls() -> int:
    return sum(count for path, count in _tree(timer.GLOBAL_TIMER.root).items()
               if path.rsplit(".", 1)[-1] == FM_SCOPE)


def _partition(preset, graph, k, seed):
    solver = ktp.KaMinPar(preset)
    solver.set_output_level(OutputLevel.QUIET)
    part = solver.set_graph(graph).compute_partition(
        k=k, epsilon=EPSILON, seed=seed)
    return np.asarray(part), solver.result_metrics(graph, part)


def _mesh(family, size, k):
    """`(graph, reference, count)`: the mesh, its plain reference
    partition, and a count of any partition's cut that does not read the
    graph (None where the family has none)."""
    if family == "delaunay":
        points, graph = delaunay_mesh(size, seed=1)
        return graph, recursive_coordinate_bisection(points, k), None
    return (factories.make_fe_grid(size, size), rectangles(size, size, k),
            lambda part: cut_of(size, size, part))


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    family, size, k = CASES[request.param]
    graph, reference, count = _mesh(family, size, k)
    runs = {seed: _partition("strong", graph, k, seed) for seed in SEEDS}
    replays = {seed: _partition("strong", graph, k, seed)[0]
               for seed in SEEDS[1:]}

    # seed 1's replay: every pipeline watched
    pipelines = []  # (level, light, k, FM calls inside)
    refine = refiner_mod.RefinerPipeline.refine

    def watched(self, *args, **kwargs):
        before = _fm_calls()
        out = refine(self, *args, **kwargs)
        pipelines.append((kwargs.get("level", 0), self.light, self.k,
                          _fm_calls() - before))
        return out

    refiner_mod.RefinerPipeline.refine = watched
    try:
        replays[1] = _partition("strong", graph, k, 1)[0]
        tree = _tree(timer.GLOBAL_TIMER.root)
    finally:
        refiner_mod.RefinerPipeline.refine = refine
    default = {seed: _partition("default", graph, k, seed) for seed in SEEDS}
    return SimpleNamespace(
        name=request.param, k=k, n=graph.n, graph=graph, reference=reference,
        count=count, runs=runs, replays=replays, default=default,
        pipelines=pipelines, tree=tree,
        default_tree=_tree(timer.GLOBAL_TIMER.root))


def test_recounted_cut_is_the_reported_cut(case):
    for seed, (part, reported) in case.runs.items():
        assert part.shape == (case.n,)
        assert part.min() >= 0 and part.max() < case.k
        recount = host_partition_metrics(case.graph, part, case.k)
        assert recount["cut"] == reported["cut"] > 0, seed
        if case.count is not None:
            assert recount["cut"] == case.count(part)
        assert reported["feasible"]


def test_every_block_is_within_the_strict_bound(case):
    for part, _ in case.runs.values():
        weights = np.bincount(part, minlength=case.k)
        assert weights.sum() == case.n
        assert weights.max() <= (1 + EPSILON) * math.ceil(case.n / case.k)


def test_a_replay_is_bitwise_equal(case):
    for seed, (part, _) in case.runs.items():
        np.testing.assert_array_equal(part, case.replays[seed])


def test_fm_runs_on_the_finest_levels_of_full_pipelines_only(case):
    """`GREEDY_FM` appears twice in `strong`'s list: a pipeline that is
    not light, on a level <= `max_level`, calls it twice; every other
    pipeline never."""
    max_level = FMRefinementContext().max_level
    assert case.pipelines
    for level, light, k, fm_calls in case.pipelines:
        expected = 0 if light or level > max_level else 2
        assert fm_calls == expected, (level, light, k)
    assert sum(calls for *_, calls in case.pipelines) == sum(
        c for p, c in case.tree.items()
        if p.rsplit(".", 1)[-1] == FM_SCOPE) > 0
    assert not any("fm-numpy" in p for p in case.tree)
    if case.name == "grid-96x96-k16":
        # 9,216 nodes reach k = 16 over three doublings on level 0, the
        # first of them intermediate: refined lightly, without FM
        assert any(light for _, light, _, _ in case.pipelines)


@pytest.mark.parametrize("level, light, runs", [
    (0, False, True), (1, False, True), (2, False, False),
    (0, True, False), (1, True, False)])
def test_the_fm_gate_is_the_level_and_the_light_flag(level, light, runs):
    """The small cases have two levels; the gate itself, for the levels
    they lack: a step exists on levels <= `max_level` (1) of a pipeline
    that is not light, and nowhere else (decided before the graph is
    looked at)."""
    from kaminpar_tpu.context import RefinementAlgorithm

    ctx = ktp.context_from_preset("strong")
    assert ctx.refinement.fm.max_level == 1
    assert ctx.parallel.num_workers == 1  # what replays bitwise
    assert ctx.refinement.algorithms.count(RefinementAlgorithm.GREEDY_FM) == 2
    assert RefinementAlgorithm.LABEL_PROPAGATION not in (
        ctx.refinement.algorithms)
    pipeline = refiner_mod.RefinerPipeline(ctx, 16, light=light)
    step = pipeline._make_step(
        RefinementAlgorithm.GREEDY_FM, None, 16, None, None, None, 1,
        level, 3)
    assert (step is not None) == runs


def test_strong_runs_no_lp_refinement_and_default_no_fm(case):
    names = {p.rsplit(".", 1)[-1] for p in case.tree}
    assert "jet" in names and "lp-refinement" not in names
    default_names = {p.rsplit(".", 1)[-1] for p in case.default_tree}
    assert "lp-refinement" in default_names
    assert not default_names & (UNDER_FM - {"graph-download"} | {FM_SCOPE})


def test_strong_cuts_no_more_than_default(case):
    strong = median(m["cut"] for _, m in case.runs.values())
    default = median(m["cut"] for _, m in case.default.values())
    assert strong <= default


def test_cut_is_near_the_plain_reference(case):
    """The plain partitioners know the geometry and nothing of the edges,
    and are exactly balanced: the grid cut into 4 x 4 rectangles (2 x 1
    at k = 2) is near the best a structured grid allows; recursive
    coordinate bisection is what a solver without a partitioner does to
    a Delaunay mesh, and `strong` has to beat it.  The multilevel
    partitioner may use the 3 %."""
    assert np.bincount(case.reference, minlength=case.k).max() == math.ceil(
        case.n / case.k)
    reference_cut = host_partition_metrics(
        case.graph, case.reference, case.k)["cut"]
    if case.count is not None:
        assert reference_cut == case.count(case.reference)
    strong = median(m["cut"] for _, m in case.runs.values())
    assert strong <= (1.10 if case.count is not None else 1.0) * reference_cut


# --- the host FM engines against a recount ---------------------------------

def _heavy(graph: HostGraph) -> HostGraph:
    """The same edges with node weights 1-9 and symmetric edge weights
    1-97, both from the ids."""
    src = graph.edge_sources().astype(np.int64)
    dst = graph.adjncy.astype(np.int64)
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    return HostGraph(xadj=graph.xadj, adjncy=graph.adjncy,
                     node_weights=1 + np.arange(graph.n) * 7 % 9,
                     edge_weights=1 + (lo * 31 + hi * 17) % 97)


FM_GRAPHS = {"grid": lambda: factories.make_fe_grid(24, 24),
             "rmat": lambda: factories.make_rmat(512, 3000, seed=5)}


@pytest.mark.parametrize("engine", ["native", "numpy"])
@pytest.mark.parametrize("weights", ["unit", "heavy"])
@pytest.mark.parametrize("k", [2, 4, 16])
@pytest.mark.parametrize("family", list(FM_GRAPHS))
def test_fm_against_a_recount(family, k, weights, engine, monkeypatch):
    """From a random feasible partition each engine never raises the
    recounted cut and keeps every cap; the improvement the native call
    returns is the recounted drop exactly."""
    graph = FM_GRAPHS[family]()
    if weights == "heavy":
        graph = _heavy(graph)
    rng = np.random.default_rng(k)
    part = (rng.permutation(graph.n) % k).astype(np.int32)
    before = host_partition_metrics(graph, part, k)
    caps = np.full(k, int(1.03 * before["block_weights"].max()) + 1,
                   dtype=np.int64)
    ctx = FMRefinementContext()
    if engine == "native":
        assert native.get_lib() is not None
        refined = part.copy()
        improvement = native.fm_refine(graph, refined, k, caps, ctx, seed=1)
    else:
        monkeypatch.setenv("KAMINPAR_TPU_NO_NATIVE_FM", "1")
        device = device_graph_from_host(graph)
        padded = np.zeros(device.n_pad, dtype=np.int32)
        padded[: graph.n] = part
        refined = np.asarray(fm_refine_host(
            device, jax.numpy.asarray(padded), k, caps, ctx, seed=1))
        assert (refined[graph.n:] == 0).all()
        refined = refined[: graph.n]
        improvement = None
    after = host_partition_metrics(graph, refined, k)
    assert refined.min() >= 0 and refined.max() < k
    assert after["cut"] < before["cut"]  # a random partition has slack
    assert (after["block_weights"] <= caps).all()
    if improvement is not None:
        assert improvement == before["cut"] - after["cut"]
