"""IO tests (analog of kaminpar-io usage in the reference test suite)."""

import numpy as np
import pytest

from kaminpar_tpu.graphs import factories, validate
from kaminpar_tpu.io import (
    load_graph,
    load_metis,
    load_parhip,
    parse_metis,
    read_partition,
    write_metis,
    write_parhip,
    write_partition,
)


def test_parse_metis_unweighted():
    g = parse_metis("3 2\n2\n1 3\n2\n")
    assert g.n == 3 and g.m == 4
    assert list(g.neighbors(1)) == [0, 2]
    assert g.node_weights is None and g.edge_weights is None


def test_parse_metis_weighted():
    text = "2 1 11\n5 2 7\n3 1 7\n"
    g = parse_metis(text)
    assert list(g.node_weights) == [5, 3]
    assert list(g.edge_weights) == [7, 7]


def test_parse_metis_comments_and_isolated():
    g = parse_metis("% hello\n3 1\n2\n1\n\n")
    assert g.n == 3 and g.m == 2
    assert g.degrees()[2] == 0


def test_sample_graph_formats_agree(rgg2d_files):
    metis = load_metis(str(rgg2d_files / "rgg2d.metis"))
    p32 = load_parhip(str(rgg2d_files / "rgg2d-32bit.parhip"))
    p64 = load_parhip(str(rgg2d_files / "rgg2d-64bit.parhip"))
    for other in (p32, p64):
        assert np.array_equal(metis.xadj, other.xadj)
        assert np.array_equal(metis.adjncy, other.adjncy)
    validate(metis)
    assert metis.n == 1024 and metis.m == 2 * 3911


def test_metis_round_trip(tmp_path):
    g = factories.make_grid_graph(5, 5)
    path = str(tmp_path / "g.metis")
    write_metis(g, path)
    g2 = load_metis(path)
    assert np.array_equal(g.xadj, g2.xadj)
    assert np.array_equal(g.adjncy, g2.adjncy)


def test_parhip_round_trip(tmp_path):
    g = factories.make_rgg2d(200, seed=3)
    nw = np.arange(1, g.n + 1, dtype=np.int64)
    g.node_weights = nw
    path = str(tmp_path / "g.parhip")
    write_parhip(g, path)
    g2 = load_parhip(path)
    assert np.array_equal(g.xadj, g2.xadj)
    assert np.array_equal(g.adjncy, g2.adjncy)
    assert np.array_equal(g2.node_weights, nw)


def test_partition_round_trip(tmp_path):
    part = np.array([0, 1, 2, 1, 0], dtype=np.int32)
    path = str(tmp_path / "part.txt")
    write_partition(path, part)
    assert np.array_equal(read_partition(path), part)


def test_load_graph_auto_detect(tmp_path):
    g = factories.make_path(10)
    mp = str(tmp_path / "a.graph")
    pp = str(tmp_path / "a.parhip")
    write_metis(g, mp)
    write_parhip(g, pp)
    assert load_graph(mp).m == g.m
    assert load_graph(pp).m == g.m


def test_load_graph_degree_bucket_ordering(rgg2d_path, tmp_path):
    """read_graph NodeOrdering analog: degree-buckets rearrangement."""
    import numpy as np

    from kaminpar_tpu.io import load_graph, write_remapping

    g_nat = load_graph(rgg2d_path)
    g_db = load_graph(rgg2d_path, ordering="degree-buckets")
    assert g_db.n == g_nat.n and g_db.m == g_nat.m
    deg = np.diff(g_db.xadj)
    # bucket = floor(log2(deg)) + 1 (0 for isolated) must be sorted
    bucket = np.where(
        deg > 0, np.floor(np.log2(np.maximum(deg, 1))) + 1, 0
    )
    assert (np.diff(bucket) >= 0).all()

    path = tmp_path / "remap.txt"
    write_remapping(str(path), np.arange(g_db.n))
    assert np.loadtxt(path, dtype=np.int64).shape == (g_db.n,)


# ---------------------------------------------------------------------------
# lazy/mmap compressed containers (the external scheme's disk tier)
# ---------------------------------------------------------------------------


def test_lazy_compressed_load_mmaps_and_decodes_identically(tmp_path):
    """load_compressed(lazy=True) on a raw-stored container mmaps the
    byte streams (chunk-granular page-in) and decodes bitwise-identically
    to the eager path."""
    import numpy as np

    from kaminpar_tpu.graphs.factories import make_rgg2d
    from kaminpar_tpu.graphs.compressed import compress_host_graph
    from kaminpar_tpu.io.compressed_binary import (
        is_compressed_file,
        load_compressed,
        write_compressed,
    )

    g = make_rgg2d(4000, avg_degree=8, seed=9)
    cg = compress_host_graph(g)
    path = str(tmp_path / "g.npz")
    write_compressed(path, cg, compress=False)
    assert is_compressed_file(path)
    lazy = load_compressed(path, lazy=True)
    assert isinstance(lazy.data, np.memmap)
    eager = load_compressed(path)
    for v0, v1 in ((0, 128), (1000, 1600), (g.n - 64, g.n)):
        xr1, a1, w1 = lazy.decode_range(v0, v1)
        xr2, a2, w2 = eager.decode_range(v0, v1)
        assert np.array_equal(np.asarray(a1), np.asarray(a2))
        assert np.array_equal(np.asarray(xr1), np.asarray(xr2))
    assert lazy.decode().m == g.m


def test_lazy_compressed_load_bounded_peak(tmp_path):
    """The lazy path's host allocation stays bounded: loading + one
    chunk decode allocates a small fraction of what the eager
    full-container materialization pays (the full-file RAM spike the
    satellite exists to remove).  Measured with tracemalloc — the
    host-side twin of the PR-7 device-memory sampler (numpy routes
    allocations through the traced PyDataMem domain; np.memmap pages
    are owned by the OS cache and never hit it)."""
    import tracemalloc

    import numpy as np

    from kaminpar_tpu.graphs.host import HostGraph
    from kaminpar_tpu.graphs.compressed import compress_host_graph
    from kaminpar_tpu.io.compressed_binary import (
        load_compressed,
        write_compressed,
    )

    # a ring graph with a large, incompressible-ish payload: every
    # varint stream byte matters, so the container's `data` member is
    # the dominant cost the lazy path must NOT materialize
    n = 200_000
    src = np.arange(n, dtype=np.int64)
    right = (src + 1) % n
    left = (src - 1) % n
    adj = np.empty(2 * n, dtype=np.int32)
    adj[0::2] = np.minimum(left, right)
    adj[1::2] = np.maximum(left, right)
    xadj = np.arange(0, 2 * n + 1, 2, dtype=np.int64)
    g = HostGraph(xadj=xadj, adjncy=adj)
    cg = compress_host_graph(g)
    path = str(tmp_path / "big.npz")
    write_compressed(path, cg, compress=False)
    data_bytes = int(cg.data.nbytes)

    def peak(load):
        tracemalloc.start()
        graph = load()
        graph.decode_range(0, 4096)  # one chunk's worth of pages
        _, p = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        del graph
        return p

    lazy_peak = peak(lambda: load_compressed(path, lazy=True))
    eager_peak = peak(lambda: load_compressed(path))
    # the eager path materializes the full data member; the lazy path
    # must stay well under it (O(n) offsets + one decoded chunk)
    assert eager_peak >= data_bytes, (eager_peak, data_bytes)
    assert lazy_peak < 0.5 * eager_peak, (lazy_peak, eager_peak)
