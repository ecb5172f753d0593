"""Distributed CLI tests (apps/dKaMinPar.cc surface)."""

import numpy as np

from kaminpar_tpu.dcli import main


def test_dcli_partitions_file_graph(rgg2d_path, tmp_path, capfd):
    out = tmp_path / "part.txt"
    rc = main(
        [rgg2d_path, "-k", "4", "-n", "2", "-o", str(out), "-T", "--validate"]
    )
    assert rc == 0
    captured = capfd.readouterr()
    # the facade logs the single RESULT line (stderr); the CLI prints TIME
    assert "RESULT cut=" in captured.err
    assert "devices=2" in captured.err
    assert "TIME io=" in captured.out
    # -T prints the finalized dist timer: min/avg/max per scope
    # (kaminpar-dist/timer.cc analog; one process -> min == max)
    assert "min=" in captured.out and "max=" in captured.out
    part = np.loadtxt(out, dtype=np.int64)
    assert part.shape == (1024,)
    assert set(np.unique(part)) <= set(range(4))


def test_dcli_generator_input(capfd):
    rc = main(["gen:rmat;n=256;m=1024;seed=1", "-k", "2", "-n", "2", "-q"])
    assert rc == 0


def test_dcli_streamed_generator_input(capfd):
    """--stream-chunks routes gen: input through the KaGen streaming
    analog (io/skagen.py) — same graph, bounded generation memory."""
    rc = main(
        ["gen:rmat;n=256;m=1024;seed=1", "-k", "2", "-n", "2", "-q",
         "--stream-chunks", "4"]
    )
    assert rc == 0


def test_dcli_errors_without_k(rgg2d_path, capfd):
    assert main([rgg2d_path]) == 1
    assert "need -k" in capfd.readouterr().err


def test_dcli_compressed_input(rgg2d_path, tmp_path, capfd):
    """dKaMinPar decodes compressed graphs eagerly (terapart input)."""
    from kaminpar_tpu.graphs.compressed import compress_host_graph
    from kaminpar_tpu.io import load_graph, write_compressed

    path = str(tmp_path / "rgg2d.npz")
    write_compressed(path, compress_host_graph(load_graph(rgg2d_path)))
    rc = main([path, "-k", "2", "-n", "2", "-f", "compressed", "-q"])
    assert rc == 0


def test_timer_aggregation_single_process():
    """aggregate_across_processes must expose every scope with
    min == avg == max on a single process (the multi-host reduction
    degenerates to the local tree)."""
    from kaminpar_tpu.utils.timer import (
        Timer,
        aggregate_across_processes,
        render_aggregated,
    )

    t = Timer()
    with t.scope("outer"):
        with t.scope("inner"):
            pass
    agg = aggregate_across_processes(t)
    assert set(agg) == {"outer", "outer.inner"}
    s = agg["outer"]
    assert s["min"] == s["avg"] == s["max"] >= 0.0
    assert s["count"] == 1
    out = render_aggregated(agg)
    assert "inner" in out and "min=" in out
