"""Initial bipartitioning tests (analog of the reference's initial
partitioning coverage inside e2e tests)."""

import numpy as np
import pytest

from kaminpar_tpu.context import InitialPartitioningContext, InitialRefinementContext
from kaminpar_tpu.graphs import factories
from kaminpar_tpu.initial import bipartition, fm_bipartition_refine
from kaminpar_tpu.initial.bipartitioner import _host_block_weights, _host_cut
from kaminpar_tpu.initial.flat import (
    bfs_bipartition,
    ggg_bipartition,
    random_bipartition,
)


def test_flat_bipartitioners_produce_valid_partitions():
    g = factories.make_grid_graph(10, 10)
    mw = np.array([55, 55])
    rng = np.random.default_rng(0)
    for fn in (random_bipartition, bfs_bipartition, ggg_bipartition):
        part = fn(g, mw, rng)
        assert set(np.unique(part)) <= {0, 1}
        bw = _host_block_weights(g, part)
        assert bw.sum() == 100


def test_fm_refine_reduces_cut():
    g = factories.make_grid_graph(8, 8)
    rng = np.random.default_rng(1)
    part = rng.integers(0, 2, 64).astype(np.int8)
    before = _host_cut(g, part)
    imp = fm_bipartition_refine(
        g, part, np.array([40, 40]), InitialRefinementContext(), rng
    )
    after = _host_cut(g, part)
    assert imp >= 0 and after <= before
    assert (_host_block_weights(g, part) <= 40).all()


@pytest.mark.parametrize("native_ip", [True, False])
def test_multilevel_bipartition_quality_path(native_ip, monkeypatch):
    if not native_ip:
        monkeypatch.setenv("KAMINPAR_TPU_NO_NATIVE_IP", "1")
    g = factories.make_path(200)
    part = bipartition(
        g, np.array([103, 103]), InitialPartitioningContext(),
        np.random.default_rng(0),
    )
    assert _host_cut(g, part) <= 3  # optimum is 1


@pytest.mark.parametrize("native_ip", [True, False])
def test_multilevel_bipartition_grid(native_ip, monkeypatch):
    if not native_ip:
        monkeypatch.setenv("KAMINPAR_TPU_NO_NATIVE_IP", "1")
    g = factories.make_grid_graph(16, 16)
    part = bipartition(
        g, np.array([135, 135]), InitialPartitioningContext(),
        np.random.default_rng(0),
    )
    cut = _host_cut(g, part)
    bw = _host_block_weights(g, part)
    assert (bw <= 135).all()
    assert cut <= 32  # optimum 16

@pytest.mark.parametrize("native_ip", [True, False])
def test_weighted_bipartition(native_ip, monkeypatch):
    if not native_ip:
        monkeypatch.setenv("KAMINPAR_TPU_NO_NATIVE_IP", "1")
    g = factories.make_path(20)
    g.node_weights = np.ones(20, dtype=np.int64)
    g.node_weights[0] = 10
    part = bipartition(
        g, np.array([16, 16]), InitialPartitioningContext(),
        np.random.default_rng(3),
    )
    assert (_host_block_weights(g, part) <= 16).all()


def test_native_bipartitioner_matches_python_class():
    """The native (C++) multilevel bipartitioner must produce feasible
    partitions of the same quality class as the numpy path (it replaces
    it whenever the toolchain is available — ip.cpp)."""
    from kaminpar_tpu import native

    if not native.available():
        import pytest

        pytest.skip("native toolchain unavailable")
    g = factories.make_grid_graph(24, 24)
    ctx = InitialPartitioningContext()
    caps = np.array([297, 297])
    part = native.ml_bipartition(g, caps, ctx, seed=11)
    assert part is not None and part.dtype == np.int8
    assert set(np.unique(part)) <= {0, 1}
    assert (_host_block_weights(g, part) <= caps).all()
    assert _host_cut(g, part) <= 48  # optimum 24, same band as python

    # determinism: same seed, same result
    part2 = native.ml_bipartition(g, caps, ctx, seed=11)
    assert np.array_equal(part, part2)


def test_native_bipartitioner_weighted_feasible():
    from kaminpar_tpu import native

    if not native.available():
        import pytest

        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(2)
    g = factories.make_grid_graph(16, 16)
    g.node_weights = rng.integers(1, 9, g.n).astype(np.int64)
    total = int(g.node_weights.sum())
    cap = int(1.05 * np.ceil(total / 2))
    part = native.ml_bipartition(g, [cap, cap], InitialPartitioningContext(), seed=5)
    assert (_host_block_weights(g, part) <= cap).all()


# ---------------------------------------------------------------------------
# native attempts (PR 26): several independent multilevel bipartitions a
# call, the best kept
# ---------------------------------------------------------------------------


def _mesh(n=1500, seed=4):
    return factories.make_delaunay(n, seed=seed)


def _native_or_skip():
    from kaminpar_tpu import native

    if not native.available():
        pytest.skip("native toolchain unavailable")
    return native


def _caps(g, eps=0.03):
    cap = int((1 + eps) * np.ceil(g.total_node_weight / 2))
    return np.array([cap, cap], dtype=np.int64)


def test_native_attempts_report_their_cut_and_keep_attempt_zero():
    native = _native_or_skip()
    g, ctx = _mesh(), InitialPartitioningContext()
    seeds = [11, 12, 13, 14]
    attempts = native.ml_bipartition_attempts(g, _caps(g), ctx, seeds)
    assert len(attempts) == len(seeds)
    for (part, cut), seed in zip(attempts, seeds):
        assert part.dtype == np.int8 and part.shape == (g.n,)
        assert cut == _host_cut(g, part.astype(np.int64))
        # the threads share nothing: each attempt is the single call's
        assert np.array_equal(
            part, native.ml_bipartition(g, _caps(g), ctx, seed=seed))
    assert len({cut for _, cut in attempts}) > 1  # independent hierarchies


@pytest.mark.parametrize("graph_seed", [1, 2, 3])
def test_bipartition_keeps_the_best_attempt(graph_seed):
    """What `bipartition` returns is the attempt of least overload, then
    lowest cut, among NATIVE_ATTEMPTS seeds strided from the one seed it
    draws; never worse than attempt 0, which is all it ran before; and
    the same on a second call (the threads' order decides nothing)."""
    from kaminpar_tpu.initial import bipartitioner as B

    native = _native_or_skip()
    g, ctx = _mesh(seed=graph_seed), InitialPartitioningContext()
    caps = _caps(g)
    part = bipartition(g, caps, ctx, np.random.default_rng(7))
    again = bipartition(g, caps, ctx, np.random.default_rng(7))
    assert np.array_equal(part, again)

    seed = int(np.random.default_rng(7).integers(0, 2**62))
    seeds = [(seed + i * B._ATTEMPT_SEED_STRIDE) & 0xFFFFFFFFFFFFFFFF
             for i in range(B.NATIVE_ATTEMPTS)]
    attempts = native.ml_bipartition_attempts(g, caps, ctx, seeds)

    def key(a):
        bw = _host_block_weights(g, a[0].astype(np.int64))
        return int(np.maximum(bw - caps, 0).sum()), a[1]

    assert key((part, _host_cut(g, part.astype(np.int64)))) == min(
        key(a) for a in attempts)
    assert key((part, _host_cut(g, part.astype(np.int64)))) <= key(
        attempts[0])
    assert (_host_block_weights(g, part.astype(np.int64)) <= caps).all()


def test_one_pool_repetition_means_one_attempt():
    """A preset that caps the pool at one repetition (`fast`) gets the
    single bipartition of the seed it draws."""
    from kaminpar_tpu.presets import create_context_by_preset_name

    native = _native_or_skip()
    ctx = create_context_by_preset_name("fast").initial_partitioning
    assert ctx.pool.max_num_repetitions == 1
    g = _mesh()
    part = bipartition(g, _caps(g), ctx, np.random.default_rng(5))
    seed = int(np.random.default_rng(5).integers(0, 2**62))
    assert np.array_equal(
        part, native.ml_bipartition(g, _caps(g), ctx, seed=seed))
