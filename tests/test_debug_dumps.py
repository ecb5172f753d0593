"""Hierarchy dump tests (kaminpar-shm/partitioning/debug.cc analog)."""

import glob
import os

import numpy as np

from kaminpar_tpu.cli import main
from kaminpar_tpu.io import load_graph


def test_debug_dumps_write_hierarchy_files(rgg2d_path, tmp_path):
    rc = main(
        [
            rgg2d_path, "-k", "4", "-q",
            # rgg2d is below the default contraction limit (no levels);
            # force a real hierarchy so the per-level dumps exist
            "--contraction-limit", "64",
            "--debug-dump", "toplevel-graph", "toplevel-partition",
            "coarsest-graph", "coarsest-partition", "graph-hierarchy",
            "partition-hierarchy",
            "--debug-dump-dir", str(tmp_path),
        ]
    )
    assert rc == 0

    # toplevel graph round-trips through the METIS writer
    top = load_graph(str(tmp_path / "rgg2d.toplevel.metis"))
    orig = load_graph(rgg2d_path)
    assert top.n == orig.n and top.m == orig.m

    # toplevel partition matches the input size and k
    part = np.loadtxt(tmp_path / "rgg2d.toplevel.part", dtype=np.int64)
    assert part.shape == (orig.n,)
    assert set(np.unique(part)) <= set(range(4))

    # coarsest artifacts and at least one per-level artifact exist
    assert (tmp_path / "rgg2d.coarsest.metis").exists()
    assert (tmp_path / "rgg2d.coarsest.part").exists()
    coarsest = load_graph(str(tmp_path / "rgg2d.coarsest.metis"))
    cpart = np.loadtxt(tmp_path / "rgg2d.coarsest.part", dtype=np.int64)
    assert cpart.shape == (coarsest.n,)
    assert glob.glob(os.path.join(tmp_path, "rgg2d.level*.metis"))
    assert glob.glob(os.path.join(tmp_path, "rgg2d.level*.part"))
