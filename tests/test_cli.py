"""CLI tests (apps/KaMinPar.cc surface)."""

import io as std_io
import os
import sys

import numpy as np
import pytest

from kaminpar_tpu.cli import (
    apply_dict_to_context,
    build_parser,
    context_to_dict,
    dump_toml,
    main,
)
from kaminpar_tpu.presets import create_context_by_preset_name


def test_dump_config_roundtrips_through_toml(tmp_path):
    import tomllib

    ctx = create_context_by_preset_name("strong")
    text = "\n".join(dump_toml(context_to_dict(ctx)))
    data = tomllib.loads(text)
    ctx2 = create_context_by_preset_name("default")
    apply_dict_to_context(ctx2, data)
    assert context_to_dict(ctx2) == context_to_dict(ctx)


def test_cli_partitions_and_writes_output(rgg2d_path, tmp_path, capfd):
    out = tmp_path / "part.txt"
    sizes = tmp_path / "sizes.txt"
    rc = main(
        [
            rgg2d_path,
            "-k",
            "4",
            "-e",
            "0.03",
            "-o",
            str(out),
            "--output-block-sizes",
            str(sizes),
            "-T",
            "--validate",
        ]
    )
    assert rc == 0
    captured = capfd.readouterr()  # fd-level: the logger binds the real stderr
    assert "RESULT cut=" in captured.err
    assert "TIME io=" in captured.out
    # -T: the timer tree, then the compile account (telemetry off)
    assert "set-up of this process:" in captured.out

    part = np.loadtxt(out, dtype=np.int32)
    assert part.shape == (1024,)
    assert part.min() >= 0 and part.max() < 4
    bs = np.loadtxt(sizes, dtype=np.int64)
    assert bs.sum() == 1024


def test_cli_config_file_override(rgg2d_path, tmp_path):
    cfg = tmp_path / "cfg.toml"
    cfg.write_text("[coarsening]\ncontraction_limit = 123\n")
    parser = build_parser()
    args = parser.parse_args([rgg2d_path, "-k", "2", "-C", str(cfg)])
    from kaminpar_tpu.cli import make_context

    ctx = make_context(args)
    assert ctx.coarsening.contraction_limit == 123


def test_cli_refinement_override(rgg2d_path):
    parser = build_parser()
    args = parser.parse_args([rgg2d_path, "-k", "2", "--refinement", "lp;jet"])
    from kaminpar_tpu.cli import make_context
    from kaminpar_tpu.context import RefinementAlgorithm

    ctx = make_context(args)
    assert ctx.refinement.algorithms == [
        RefinementAlgorithm.LABEL_PROPAGATION,
        RefinementAlgorithm.JET,
    ]


def test_cli_errors_without_k(rgg2d_path, capfd):
    assert main([rgg2d_path]) == 1
    assert main([]) == 1


def test_cli_machine_timers(rgg2d_path, capfd):
    rc = main([rgg2d_path, "-k", "2", "--machine-timers"])
    assert rc == 0
    out = capfd.readouterr().out
    line = [l for l in out.splitlines() if l.startswith("TIMERS ")]
    assert line, out
    pairs = dict(p.split("=") for p in line[0][len("TIMERS "):].split())
    assert "partitioning" in pairs
    assert float(pairs["partitioning"]) > 0
    assert any(key.startswith("partitioning.") for key in pairs)


def test_cli_degree_bucket_ordering_outputs_file_order(rgg2d_path, tmp_path):
    """--node-ordering reorders internally but the written partition is
    in original file order (permutation-aware output)."""
    out_nat = tmp_path / "nat.txt"
    out_db = tmp_path / "db.txt"
    remap = tmp_path / "remap.txt"
    assert main([rgg2d_path, "-k", "4", "-q", "-o", str(out_nat)]) == 0
    assert main([rgg2d_path, "-k", "4", "-q", "--node-ordering", "degree-buckets",
                 "-o", str(out_db), "--output-remapping", str(remap)]) == 0
    mapping = np.loadtxt(remap, dtype=np.int64)
    assert sorted(mapping.tolist()) == list(range(1024))
    from kaminpar_tpu.io import load_graph

    g = load_graph(rgg2d_path)
    src, dst = g.edge_sources(), g.adjncy
    for path in (out_nat, out_db):
        part = np.loadtxt(path, dtype=np.int64)
        assert part.shape == (g.n,)
        cut = int((part[src] != part[dst]).sum()) // 2
        assert 0 < cut < g.m  # sane cut in FILE order for both runs
