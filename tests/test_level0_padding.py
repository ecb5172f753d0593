"""The request's own graph (level 0) is uploaded at an eighth of its
power-of-two shape bucket (`caching.input_pad_size`), while every path
decision and buffer width reads the bucket (`caching.shape_bucket`,
`DeviceGraph.n_bucket` / `m_bucket`).

(a) the bucket function against its contract; (b) whole requests give
the same labels, bit for bit, whether level 0 is uploaded at its eighth
or at its power-of-two bucket (the pad slots are inert), with the shape
floors lowered so level 0 sits above them and the coarse levels below,
as on the chip; (c) the gates read the bucket: a level whose width lies
under a lowered gate but whose bucket does not takes the bucket's path.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import kaminpar_tpu as ktp
from kaminpar_tpu import caching
from kaminpar_tpu.graphs import csr as csr_mod
from kaminpar_tpu.graphs import factories
from kaminpar_tpu.graphs.csr import device_graph_from_host, upload_input_graph
from kaminpar_tpu.graphs.host import remove_isolated_nodes
from kaminpar_tpu.ops import jet as jet_mod
from kaminpar_tpu.ops import lp as lp_mod
from kaminpar_tpu.ops import segments
from kaminpar_tpu.utils.logger import OutputLevel

FLOORS = (1 << 10, 1 << 14)
_UPLOAD = csr_mod.device_graph_from_host
_STREAMS = segments.conn_table_streams


# ---------------------------------------------------------------------------
# (a) the bucket function
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("x, floor, expected", [
    (1, 256, 256),                   # below the floor: the floor
    (200, 256, 256),
    (256, 256, 256),                 # at the floor
    (257, 256, 288),                 # just above: 512's first eighth
    (41_761, 8192, 45_056),          # rmat-s16's nodes + 1
    (1_083_716, 1 << 20, 1_179_648),  # rmat-s16's edge slots
    (80_170, 8192, 81_920),          # rmat-s17's nodes + 1
    (2_207_668, 1 << 20, 2_359_296),  # rmat-s17's edge slots
    (131_073, 8192, 147_456),        # delaunay-n17's nodes + 1
    (786_374, 1 << 20, 1 << 20),     # delaunay-n17's slots, under the floor
    (786_374, 1 << 14, 786_432),     # ... and above a lower floor
    (1 << 21, 1 << 20, 1 << 21),     # a full bucket stays whole
])
def test_input_pad_size_values(x, floor, expected):
    got = caching.input_pad_size(x, floor)
    assert got == expected
    assert got >= x
    assert caching.shape_bucket(got) == caching.pad_size(x, floor)


@pytest.mark.parametrize("octave", [9, 13, 17, 21])
def test_input_pad_size_has_eight_buckets_an_octave(octave):
    p = 1 << octave
    xs = np.unique(np.linspace(p // 2 + 1, p, 301).astype(int))
    widths = {caching.input_pad_size(int(x), 256) for x in xs}
    assert widths == {p // 16 * j for j in range(9, 17)}
    for x in xs:
        w = caching.input_pad_size(int(x), 256)
        assert x <= w < x + p // 16
        assert caching.shape_bucket(w) == caching.pad_size(int(x), 256) == p


@pytest.mark.parametrize("x, floor", [
    (100, 256), (5000, 256), (41_761, 8192), (1_083_716, 1 << 20),
])
def test_input_pad_size_leaves_tight_to_pad_size(x, floor):
    with caching.pad_policy_scope("tight"):
        assert caching.input_pad_size(x, floor) == caching.pad_size(x, floor)
        assert caching.pad_size(x, floor) == max(-(-x // floor) * floor,
                                                 floor)


def test_bucket_key_names_the_input_upload():
    n, m = 3000, 40_000
    assert caching.bucket_key(n, m, 5) == (
        caching.input_pad_size(n + 1), caching.input_pad_size(m), 8)


# ---------------------------------------------------------------------------
# (b) the partition does not depend on level 0's pad
# ---------------------------------------------------------------------------


def _rmat():
    return remove_isolated_nodes(
        factories.make_rmat(1 << 12, 30_000, seed=3))[0]


def _mesh():
    return factories.make_delaunay(4096, seed=5)


REQUESTS = {
    "rmat-k2": ("default", _rmat, 2),
    "rmat-k16": ("default", _rmat, 16),
    "mesh-k16": ("default", _mesh, 16),
    "rmat-kway-k64": ("mtkahypar-kway", _rmat, 64),
}


def _partition(monkeypatch, preset, graph, k, upload_pad):
    monkeypatch.setattr(csr_mod, "shape_floors", lambda: FLOORS)
    monkeypatch.setattr(csr_mod, "input_pad_size", upload_pad)
    seen = []

    def recorded(g, n_pad=None, m_pad=None, device=None):
        dg = _UPLOAD(g, n_pad=n_pad, m_pad=m_pad, device=device)
        if g is graph:
            seen.append((dg.n_pad, dg.m_pad))
        return dg

    monkeypatch.setattr(csr_mod, "device_graph_from_host", recorded)
    solver = ktp.KaMinPar(preset)
    solver.set_output_level(OutputLevel.QUIET)
    part = solver.set_graph(graph).compute_partition(
        k=k, epsilon=0.03, seed=1)
    return np.asarray(part), seen


@pytest.mark.parametrize("name", sorted(REQUESTS))
def test_partition_is_bitwise_the_power_of_two_uploads(monkeypatch, name):
    preset, make, k = REQUESTS[name]
    graph = make()
    n_floor, m_floor = FLOORS
    eighth = (caching.input_pad_size(graph.n + 1, n_floor),
              caching.input_pad_size(graph.m, m_floor))
    whole = (caching.pad_size(graph.n + 1, n_floor),
             caching.pad_size(graph.m, m_floor))
    assert eighth[0] < whole[0] and eighth[1] < whole[1]

    fine, fine_seen = _partition(
        monkeypatch, preset, graph, k, caching.input_pad_size)
    pow2, pow2_seen = _partition(
        monkeypatch, preset, graph, k, caching.pad_size)
    assert fine_seen == [eighth] and pow2_seen == [whole]
    np.testing.assert_array_equal(fine, pow2)


# ---------------------------------------------------------------------------
# (c) the gates read the bucket
# ---------------------------------------------------------------------------


def _cycle(n):
    ring = np.arange(n)
    return factories.from_edge_list(n, np.stack([ring, (ring + 1) % n], 1))


@pytest.fixture
def uploads():
    """The same 462-slot graph at its eighth (480 slots) and at its
    power-of-two bucket (512), CPU floors."""
    graph = _cycle(231)
    fine = upload_input_graph(graph)
    whole = device_graph_from_host(graph)
    assert (fine.m_pad, fine.m_bucket, whole.m_pad) == (480, 512, 512)
    return fine, whole


def _jet_path(monkeypatch, graph):
    monkeypatch.setattr(jet_mod, "DELTA_MIN_EDGE_SLOTS", 500)
    return jet_mod.iteration_path(graph, 4)


def _jet_buffers(monkeypatch, graph):
    monkeypatch.setattr(jet_mod, "DELTA_MIN_EDGE_SLOTS", 500)
    return jet_mod._delta_slots(graph), jet_mod._conn_slots(graph)


def _lp_delta_buffer(monkeypatch, graph):
    monkeypatch.setattr(lp_mod, "DELTA_MIN_EDGE_SLOTS", 500)
    cfg = lp_mod.LPConfig(rating="sort2")
    return lp_mod._delta_slots(graph, cfg, "sort2")


def _conn_engine(monkeypatch, graph):
    asked = []

    def recorded(k, n_pad, m_pad):
        asked.append((k, n_pad, m_pad))
        return _STREAMS(k, n_pad, m_pad)

    monkeypatch.setattr(segments, "conn_table_streams", recorded)
    labels = jnp.arange(graph.n_pad, dtype=jnp.int32) % 4
    segments.csr_block_ratings(graph, labels, 4)
    return asked


GATES = {
    "jet-path": (_jet_path, "jet-rows"),
    "jet-buffers": (_jet_buffers, (128, 32)),
    "lp-delta-buffer": (_lp_delta_buffer, 128),
    "conn-engine": (_conn_engine, [(4, 256, 512)]),
}


@pytest.mark.parametrize("gate", sorted(GATES))
def test_gates_read_the_bucket(monkeypatch, uploads, gate):
    decide, expected = GATES[gate]
    fine, whole = uploads
    got = decide(monkeypatch, fine)
    assert got == decide(monkeypatch, whole) == expected
