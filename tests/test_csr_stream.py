"""CSR-order streaming helpers (ops/segments.expand_rows,
csr_block_ratings): bitwise equality with the gather / scatter forms they
replace, on both sides of the shape rule, and end to end through Jet and
LP refinement."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaminpar_tpu.context import JetRefinementContext
from kaminpar_tpu.graphs import device_graph_from_host, factories
from kaminpar_tpu.graphs.host import HostGraph
from kaminpar_tpu.ops import balancer, jet, lp, segments
from kaminpar_tpu.ops.segments import (
    conn_table_streams,
    csr_block_ratings,
    dense_block_ratings,
    expand_rows,
    packed_afterburner_gain,
    packed_afterburner_gain_rows,
)
from kaminpar_tpu.utils import statistics

INT32_MAX = np.iinfo(np.int32).max
INT32_MIN = np.iinfo(np.int32).min


def _device_layout(degrees, n_pad, m_pad, seed=0, max_w=1):
    """A DeviceGraph with the given row lengths (targets random: the
    helpers under test never need symmetry)."""
    rng = np.random.default_rng(seed)
    degrees = np.asarray(degrees, dtype=np.int64)
    n, m = len(degrees), int(degrees.sum())
    g = HostGraph(
        xadj=np.concatenate([[0], np.cumsum(degrees)]),
        adjncy=rng.integers(0, n, m).astype(np.int32),
        edge_weights=rng.integers(1, max_w + 1, m) if max_w > 1 else None,
    )
    return device_graph_from_host(g, n_pad=n_pad, m_pad=m_pad)


# name -> (degrees, n_pad, m_pad)
_LAYOUTS = {
    "empty_rows_middle_and_end": ([3, 0, 0, 5, 1, 0, 0, 2, 0, 0], 16, 32),
    "isolated_tail": ([4, 4, 4] + [0] * 40, 64, 64),
    "leading_empty_rows": ([0, 0, 0, 7, 2], 8, 16),
    "m_equals_m_pad": ([5, 0, 6, 0, 5], 8, 16),
    "one_hub_row": ([1, 1, 200, 1, 1, 0], 8, 256),
    "no_edges": ([0, 0, 0], 4, 8),
    "two_level_scan": ([0, 90_000, 0, 7, 30_000, 0], 8, 1 << 17),
}


@pytest.mark.parametrize("values", ["extremes", "random", "blocks"])
@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_expand_rows_is_values_of_src(layout, values):
    degrees, n_pad, m_pad = _LAYOUTS[layout]
    g = _device_layout(degrees, n_pad, m_pad)
    if layout == "m_equals_m_pad":
        assert int(g.m) == g.m_pad  # a row start at m_pad is dropped
    rng = np.random.default_rng(len(layout))
    if values == "extremes":
        # alternate the int32 extremes so first differences wrap
        v = np.where(np.arange(n_pad) % 2 == 0, INT32_MAX, INT32_MIN)
        v[-1] = INT32_MIN + 1  # the pad node's value fills the pad slots
        v = v.astype(np.int32)
    elif values == "random":
        v = rng.integers(INT32_MIN, INT32_MAX, n_pad, dtype=np.int64).astype(
            np.int32
        )
    else:
        v = rng.integers(0, 16, n_pad).astype(np.int32)
    v = jnp.asarray(v)
    got = expand_rows(v, g.row_ptr, g.m_pad)
    assert got.dtype == v.dtype and got.shape == (g.m_pad,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(v[g.src]))


@pytest.mark.parametrize("shape", [(1 << 17,), (3, 1 << 17), (1 << 16,),
                                   (1 << 17) + 8],
                         ids=["flat", "rows", "short", "odd"])
def test_cumsum_minor_is_cumsum(shape):
    """The two-level scan (taken from 128 * CUMSUM_ROWS words up, where
    the length divides) equals jnp.cumsum bitwise, wrap-around
    included."""
    shape = shape if isinstance(shape, tuple) else (shape,)
    two_level = (shape[-1] % segments.CUMSUM_ROWS == 0
                 and shape[-1] >= 128 * segments.CUMSUM_ROWS)
    assert two_level == (shape[-1] == 1 << 17)
    rng = np.random.default_rng(shape[-1])
    x = jnp.asarray(
        rng.integers(INT32_MIN, INT32_MAX, shape, dtype=np.int64).astype(
            np.int32
        )
    )
    np.testing.assert_array_equal(
        np.asarray(segments._cumsum_minor(x)),
        np.asarray(jnp.cumsum(x, axis=-1)),
    )


# (n, m, n_pad, m_pad): one level that streams at every tested k and one
# that scatters at every k
_STREAMS = (63, 3_000, 64, 1 << 15)
_STREAMS_TWO_LEVEL = (63, 3_000, 64, 1 << 17)  # long enough for the row scan
_SCATTERS = (4_000, 6_000, 4_096, 1 << 13)


@pytest.mark.parametrize("max_w", [1, 1 << 24], ids=["unit", "heavy"])
@pytest.mark.parametrize("k", [2, 4, 16, 64])
@pytest.mark.parametrize("shape", [_STREAMS, _STREAMS_TWO_LEVEL, _SCATTERS],
                         ids=["streams", "streams_two_level", "scatters"])
def test_csr_block_ratings_is_dense_block_ratings(shape, k, max_w):
    n, m, n_pad, m_pad = shape
    assert conn_table_streams(k, n_pad, m_pad) == (shape is not _SCATTERS)
    rng = np.random.default_rng(k)
    g = _device_layout(rng.multinomial(m, np.full(n, 1.0 / n)), n_pad,
                       m_pad, seed=k, max_w=max_w)
    # labels out of range on purpose: both engines clip alike
    labels = jnp.asarray(rng.integers(-1, k + 1, n_pad).astype(np.int32))
    want = dense_block_ratings(g.src, g.dst, g.edge_w, labels, n_pad, k)
    got = csr_block_ratings(g, labels, k)
    assert got.shape == (n_pad, k) and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the streaming engine itself, whatever the rule says of this shape,
    # in whole steps and with a last partial one
    for columns in (min(k, segments.conn_stream_columns(m_pad)), 3):
        got = segments._stream_block_ratings(g, labels, k, columns)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("k,n_pad,m_pad,streams", [
    # the benchmark's levels (PERF.md, PR 25): coarse, fine, and a mesh
    (2, 1 << 13, 1 << 20, True), (16, 1 << 13, 1 << 20, True),
    (64, 1 << 13, 1 << 20, True), (128, 1 << 13, 1 << 20, False),
    (2, 1 << 16, 1 << 21, True), (16, 1 << 16, 1 << 21, True),
    (32, 1 << 16, 1 << 21, True), (64, 1 << 16, 1 << 21, False),
    (16, 1 << 17, 1 << 20, True), (32, 1 << 17, 1 << 20, False),
    (16, 1 << 16, 1 << 22, True), (32, 1 << 16, 1 << 22, False),
    (2, 1 << 21, 1 << 22, False),
    # rmat-s16-kway.k64's coarse levels (PERF.md): 8 steps stream
    (64, 1 << 15, 1 << 20, True), (64, 1 << 14, 1 << 20, True),
])
def test_conn_table_rule_on_the_benchmark_shapes(k, n_pad, m_pad, streams):
    assert conn_table_streams(k, n_pad, m_pad) == streams


@pytest.mark.parametrize("weight_scale", [50, 50_000],
                         ids=["packed", "exact"])
def test_afterburner_streamed_owner_equals_gather_form(weight_scale):
    """packed_afterburner_gain (owner columns streamed) against the
    row-buffer entry point fed the same CSR as a buffer (owner columns
    gathered), in the packed branch and, with heavy weights at k=256,
    in the exact one (test_afterburner_clip_guard_heavy_weights' graph)."""
    k = 256
    g = device_graph_from_host(factories.make_rmat(512, 4_000, seed=9))
    n_pad = g.n_pad
    rng = np.random.default_rng(3)
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
    gain = jnp.asarray(
        rng.integers(-3 * weight_scale, 3 * weight_scale + 1, n_pad)
        .astype(np.int32)
    )
    gain_bits = 31 - 2 * 8
    takes_exact = int(jnp.max(jnp.where(cand, jnp.abs(gain), 0))) >= (
        1 << (gain_bits - 1)
    )
    assert takes_exact == (weight_scale == 50_000)
    streamed = packed_afterburner_gain(
        g.src, g.dst, edge_w, g.row_ptr, part, next_part, gain, cand, k
    )
    gathered, from_u, to_u = packed_afterburner_gain_rows(
        g.src, g.dst, edge_w, g.row_ptr[:-1], g.row_ptr[1:],
        part, next_part, gain, cand, k,
    )
    np.testing.assert_array_equal(np.asarray(streamed), np.asarray(gathered))
    np.testing.assert_array_equal(np.asarray(from_u), np.asarray(part[g.src]))
    np.testing.assert_array_equal(
        np.asarray(to_u), np.asarray(next_part[g.src])
    )


def _refine_case():
    g = device_graph_from_host(factories.make_rmat(1 << 9, 24_000, seed=5))
    k = 4
    assert conn_table_streams(k, g.n_pad, g.m_pad)
    rng = np.random.default_rng(1)
    part = np.zeros(g.n_pad, np.int32)
    part[: int(g.n)] = rng.integers(0, k, int(g.n))
    total = int(np.asarray(g.node_w).sum())
    caps = jnp.full(k, int(1.03 * np.ceil(total / k)), dtype=jnp.int32)
    return g, k, jnp.asarray(part), caps


def _refine(which, g, k, part, caps):
    if which == "jet":
        return jet.jet_refine(
            g, part, k, caps, jnp.int32(7), JetRefinementContext()
        )
    return lp.lp_refine(
        g, part, k, caps, jnp.int32(7),
        lp.LPConfig(num_iterations=5, refinement=True, use_active_set=True),
    )


@pytest.mark.parametrize("which", ["jet", "lp"])
def test_refiners_return_the_gather_scatter_partition(which, monkeypatch):
    """Same partition with the helpers swapped (here only) for the
    gather and the flat segment_sum they replace."""
    g, k, part, caps = _refine_case()
    streamed = np.asarray(_refine(which, g, k, part, caps))

    def scatter_ratings(graph, labels, num_blocks):
        return dense_block_ratings(graph.src, graph.dst, graph.edge_w,
                                   labels, graph.n_pad, num_blocks)

    monkeypatch.setattr(segments, "expand_rows",
                        lambda values, row_ptr, m_pad: values[g.src])
    monkeypatch.setattr(jet, "csr_block_ratings", scatter_ratings)
    monkeypatch.setattr(balancer, "csr_block_ratings", scatter_ratings)
    jax.clear_caches()  # the jitted refiners must trace the swapped helpers
    try:
        gathered = np.asarray(_refine(which, g, k, part, caps))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert (streamed != np.asarray(part)).any()  # the refiner did move
    np.testing.assert_array_equal(streamed, gathered)


def test_conn_engine_is_counted_per_refiner_call():
    g, k, part, caps = _refine_case()
    was_enabled = statistics.enabled()
    statistics.enable()
    before = statistics.counters_snapshot()
    try:
        _refine("jet", g, k, part, caps)
        balancer.overload_balance(g, part, k, caps, jnp.int32(1))
        balancer.underload_balance(
            g, part, 128, jnp.full(128, 10**6, jnp.int32),
            jnp.zeros(128, jnp.int32), jnp.int32(1),
        )
        delta = statistics.counters_delta(before)
    finally:
        if not was_enabled:
            statistics.disable()
    assert not conn_table_streams(128, g.n_pad, g.m_pad)
    assert delta.get("conn_streamed") == 2
    assert delta.get("conn_scattered") == 1
