"""The native k-way FM's worker pool (native/fm.cpp) and what rides on it:
rounds whose commits keep a threaded call from returning a worse cut than
it was given and make its labels independent of the thread count, the
stats out-array and the process-level account that sums two of its
counters (refinement/fm.fm_account), and the `strong-parallel` preset's
worker count.  The plain recount is `graphs.host.host_partition_metrics`."""

import functools
import math

import numpy as np
import pytest

from mesh_reference import delaunay_mesh, recursive_coordinate_bisection
from kaminpar_tpu import native, presets
from kaminpar_tpu.context import FMRefinementContext
from kaminpar_tpu.graphs import device_graph_from_host, factories
from kaminpar_tpu.graphs.host import host_partition_metrics
from kaminpar_tpu.refinement.fm import ACCOUNTED, FMAccount, fm_refine_host

K = 8
#: three graphs a family, ten FM seeds a graph: 30 trials a case
GRAPH_SEEDS = (0, 1, 2)
FM_SEEDS = range(10)


@pytest.fixture(autouse=True)
def _native():
    if not native.available():
        pytest.skip("no native lib")


@functools.lru_cache(maxsize=None)
def _case(family: str, graph_seed: int):
    """`(graph, start, caps)`: a start that knows nothing of FM and caps
    1 % over the mean block, so that FM fills blocks to the cap and the
    batches of a round contend for the last room."""
    if family == "mesh":
        points, g = delaunay_mesh(3000, seed=graph_seed)
        start = recursive_coordinate_bisection(points, K)
    else:
        g = factories.make_rmat(1 << 11, 16000, seed=graph_seed)
        start = (np.arange(g.n) * K // g.n).astype(np.int32)
    mean = math.ceil(g.node_weight_array().sum() / K)
    return g, start, np.full(K, int(1.01 * mean), np.int64)


def _refine(family, graph_seed, seed, threads):
    g, start, caps = _case(family, graph_seed)
    part = start.copy()
    stats = {}
    gain = native.fm_refine(g, part, K, caps, FMRefinementContext(),
                            seed=seed, threads=threads, stats=stats)
    return g, start, caps, part, gain, stats


@pytest.mark.parametrize("threads", [2, 4, 8])
@pytest.mark.parametrize("family", ["mesh", "rmat"])
def test_threaded_fm_never_returns_a_worse_cut(family, threads):
    """Every output within the caps; the return value is the exact
    recounted improvement and never negative; the counters add up."""
    refused = 0
    for graph_seed in GRAPH_SEEDS:
        for seed in FM_SEEDS:
            g, start, caps, part, gain, stats = _refine(
                family, graph_seed, seed, threads)
            cut_in = host_partition_metrics(g, start, K)["cut"]
            cut_out = host_partition_metrics(g, part, K)["cut"]
            weights = np.bincount(part, weights=g.node_weight_array(),
                                  minlength=K)
            assert weights.max() <= caps[0], (graph_seed, seed)
            assert gain == cut_in - cut_out >= 0, (graph_seed, seed)
            assert stats["exact_gain"] == gain
            assert stats["threads"] == threads
            assert stats["committed"] > 0 and stats["undone_moves"] >= 0
            refused += stats["cap_refusals"]
    # the caps are tight enough that the cap refuses commits (the same
    # count on every thread count: the run does not hang on timing)
    assert refused > 0


@pytest.mark.parametrize("family", ["mesh", "rmat"])
def test_threaded_fm_labels_do_not_depend_on_the_thread_count(family):
    """A round's regions are grown against the state the last round left
    and committed in batch order: 2, 4 and 8 threads give the same labels
    and counters (but for `threads`), call after call."""
    for seed in FM_SEEDS:
        runs = [_refine(family, 0, seed, threads)
                for threads in (2, 4, 8, 4)]
        for _, _, _, part, gain, stats in runs[1:]:
            assert np.array_equal(part, runs[0][3]), seed
            assert gain == runs[0][4]
            assert {**stats, "threads": 0} == {**runs[0][5], "threads": 0}


@pytest.mark.parametrize("family", ["mesh", "rmat"])
def test_one_thread_takes_no_guard(family):
    """At T = 1 the delta is exact and no commit can be refused or
    undone: those counters read 0 and the estimate is the exact gain."""
    g, start, caps = _case(family, 0)
    part = start.copy()
    stats = {}
    gain = native.fm_refine(g, part, K, caps, FMRefinementContext(), seed=3,
                            stats=stats)
    assert gain > 0
    assert stats["threads"] == 1 and stats["batches"] > 0
    assert stats["committed"] > 0 and stats["passes"] >= 1
    for name in ("cap_refusals", "undone_moves"):
        assert stats[name] == 0, name
    assert stats["estimated_gain"] == stats["exact_gain"] == gain


@pytest.mark.parametrize("threads", [1, 4])
def test_fm_account_sums_the_calls(threads, monkeypatch):
    """`fm_refine_host` books each native call's `ACCOUNTED` counters in
    the account under the open request's ordinal (0 outside any): the
    totals are the calls' sums, and at T = 1 they stay 0."""
    account = FMAccount()
    monkeypatch.setattr("kaminpar_tpu.refinement.fm.fm_account", account)
    seen = []
    real = native.fm_refine

    def spy(*args, **kwargs):
        out = real(*args, **kwargs)
        seen.append(dict(kwargs["stats"]))
        return out

    monkeypatch.setattr(native, "fm_refine", spy)
    g, start, caps = _case("mesh", 1)
    dg = device_graph_from_host(g)
    part = np.zeros(dg.n_pad, np.int32)
    part[: g.n] = start
    for seed in (1, 2, 3):
        part = np.asarray(fm_refine_host(dg, part, K, caps,
                                         FMRefinementContext(), seed=seed,
                                         threads=threads))
    summary = account.summary()
    assert list(summary["by_request"]) == [0]
    sums = summary["by_request"][0]
    assert len(seen) == 3 and set(sums) == set(ACCOUNTED)
    for name in ACCOUNTED:
        assert sums[name] == sum(s[name] for s in seen), name
    assert all(s["threads"] == threads for s in seen)
    if threads == 1:
        assert sums["cap_refusals"] == sums["undone_moves"] == 0


@pytest.mark.parametrize(
    "cores,workers", [(13, 12), (2, 1), (1, 1)],
)
def test_strong_parallel_takes_the_host_cores_less_one(cores, workers,
                                                       monkeypatch):
    """`strong-parallel` is `strong` with the affinity mask's cores less
    the dispatch thread's, at least 1."""
    monkeypatch.setattr(presets.os, "sched_getaffinity",
                        lambda pid: set(range(cores)), raising=False)
    ctx = presets.create_context_by_preset_name("strong-parallel")
    strong = presets.create_context_by_preset_name("strong")
    assert ctx.preset_name == "strong-parallel"
    assert ctx.parallel.num_workers == workers
    assert ctx.refinement.algorithms == strong.refinement.algorithms
    assert ctx.refinement.fm == strong.refinement.fm
    assert strong.parallel.num_workers == 1


def test_strong_parallel_without_an_affinity_mask(monkeypatch):
    monkeypatch.delattr(presets.os, "sched_getaffinity", raising=False)
    monkeypatch.setattr(presets.os, "cpu_count", lambda: 5)
    assert presets.host_worker_count() == 4


@pytest.mark.parametrize("k,workers", [(16, 12), (4, 12), (8, 12), (16, 1)])
def test_the_fm_pool_runs_at_every_k(k, workers, monkeypatch):
    """FM gets `num_workers` threads wherever the pipeline refines, at
    the requested k and at every intermediate one, as the reference's
    `-t` pool does."""
    from kaminpar_tpu.context import RefinementAlgorithm
    from kaminpar_tpu.partitioning import refiner as refiner_mod

    monkeypatch.setattr(presets, "host_worker_count", lambda: workers)
    ctx = presets.create_context_by_preset_name("strong-parallel")
    ctx.partition.k = 16
    seen = []
    monkeypatch.setattr(
        "kaminpar_tpu.refinement.fm.fm_refine_host",
        lambda *args, threads, **kwargs: seen.append(threads))
    pipeline = refiner_mod.RefinerPipeline(ctx, k)
    step = pipeline._make_step(RefinementAlgorithm.GREEDY_FM, None, k,
                               np.zeros(k), None, None, 1, 0, 3)
    step(None)
    assert seen == [workers]


def test_the_benchmark_reads_the_median_of_the_window(monkeypatch):
    """`fm_cap_refusals` / `fm_undone_moves` read the account's sums of
    the window's requests (ordinals 2 on; request 1 is the warm-up), 0
    for a request without FM, and nothing from a program without it."""
    from kaminpar_tpu.refinement import fm as fm_mod
    from kaminpar_tpu.telemetry import compile_account
    from perfbench.layer_metrics import fm_cap_refusals, fm_undone_moves

    account = FMAccount()
    monkeypatch.setattr(fm_mod, "fm_account", account)
    for ordinal, refused, undone in ((1, 9, 90), (2, 3, 30), (4, 5, 50),
                                     (4, 1, 10)):
        monkeypatch.setattr(compile_account, "open_request",
                            lambda ordinal=ordinal: ordinal)
        account.record({"cap_refusals": refused, "undone_moves": undone})
    monkeypatch.setattr(compile_account, "requests_begun", lambda: 4)
    # the window: request 2 (3, 30), request 3 (no FM), request 4 (6, 60)
    assert fm_cap_refusals.read({}) == 3
    assert fm_undone_moves.read({}) == 30
    monkeypatch.delattr(fm_mod, "fm_account")
    assert fm_cap_refusals.read({}) is None
