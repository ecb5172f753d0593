"""Tool subcommand + aux subsystem tests (apps/tools analogs)."""

import numpy as np

from kaminpar_tpu.tools import main as tools_main


def test_properties(rgg2d_path, capfd):
    assert tools_main(["properties", rgg2d_path]) == 0
    out = capfd.readouterr().out
    assert "n=1024 m=3911" in out
    assert "isolated_nodes=1" in out  # the seeded sample has 1 isolated node


def test_partition_properties(rgg2d_path, tmp_path, capfd):
    part = tmp_path / "p.txt"
    np.savetxt(part, np.arange(1024) % 4, fmt="%d")
    assert tools_main(["partition-properties", rgg2d_path, str(part)]) == 0
    out = capfd.readouterr().out
    assert "k=4 cut=" in out


def test_compress_decompress_roundtrip(rgg2d_path, tmp_path, capfd):
    comp = tmp_path / "g.npz"
    back = tmp_path / "g.metis"
    assert tools_main(["compress", rgg2d_path, "-o", str(comp)]) == 0
    assert tools_main(["decompress", str(comp), "-o", str(back)]) == 0
    from kaminpar_tpu.io import load_graph

    a = load_graph(rgg2d_path)
    b = load_graph(str(back))
    # compression sorts neighborhoods; compare canonical forms
    assert (a.xadj == b.xadj).all()
    for u in range(a.n):
        assert (np.sort(a.neighbors(u)) == np.sort(b.neighbors(u))).all()


def test_rearrange_preserves_structure(rgg2d_path, tmp_path):
    out = tmp_path / "r.metis"
    assert tools_main(["rearrange", rgg2d_path, "-o", str(out)]) == 0
    from kaminpar_tpu.io import load_graph

    a = load_graph(rgg2d_path)
    b = load_graph(str(out))
    assert a.n == b.n and a.m == b.m
    # degree multiset preserved
    assert sorted(a.degrees()) == sorted(b.degrees())


def test_components_tool(rgg2d_path, capfd):
    assert tools_main(["components", rgg2d_path]) == 0
    out = capfd.readouterr().out
    assert "components=" in out


def test_components_kernel_matches_host():
    import jax.numpy as jnp

    from kaminpar_tpu.graphs.csr import device_graph_from_host
    from kaminpar_tpu.graphs.factories import make_grid_graph, make_matching_graph
    from kaminpar_tpu.ops.components import count_components

    g = make_grid_graph(8, 8)
    assert count_components(device_graph_from_host(g)) == 1
    g2 = make_matching_graph(10)  # 10 disjoint edges
    assert count_components(device_graph_from_host(g2)) == 10


def test_heap_profiler_and_statistics(rgg2d_path, capfd):
    from kaminpar_tpu.cli import main as cli_main
    from kaminpar_tpu.utils import heap_profiler, statistics

    try:
        rc = cli_main([rgg2d_path, "-k", "2", "-H", "--statistics"])
        assert rc == 0
        out = capfd.readouterr().out
        assert "partitioning: peak" in out
        # live-HBM tracking (device-buffer peak via jax.live_arrays
        # sampling at level boundaries) — works on every backend
        assert "live HBM" in out
        assert "STATS" in out
        assert "cut_after_jet" in out  # default refiner is Jet
    finally:
        heap_profiler.disable()
        heap_profiler.reset()
        statistics.disable()
        statistics.reset()
