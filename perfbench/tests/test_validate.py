"""The validator that decides ``correct`` (run by hand: pytest perfbench/tests)."""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.generators._csr import csr_from_edges  # noqa: E402
from perfbench.harness.validate import check_partition, edge_cut  # noqa: E402


def _path(n):
    return csr_from_edges(n, np.stack([np.arange(n - 1), np.arange(1, n)], 1))


def test_valid_partition_and_cut():
    csr = _path(8)
    part = np.array([0, 0, 0, 0, 1, 1, 1, 1], dtype=np.int32)
    out = check_partition(csr, part, 2, 0.03)
    assert out["errors"] == [] and out["cut"] == 1
    assert out["max_block_weight"] == 4


def test_weighted_cut_counts_merged_edges():
    # the edge 0-1 drawn three times is one edge of weight 3
    csr = csr_from_edges(3, np.array([[0, 1], [0, 1], [1, 0], [1, 2]]))
    assert list(csr["edge_weights"]) == [3, 3, 1, 1]
    assert edge_cut(csr, np.array([0, 1, 1])) == 3
    assert edge_cut(csr, np.array([0, 0, 1])) == 1


def test_rejects_infeasible():
    part = np.array([0, 0, 0, 0, 0, 1, 1, 1], dtype=np.int32)
    out = check_partition(_path(8), part, 2, 0.03)
    assert any("infeasible" in e for e in out["errors"])


def test_rejects_out_of_range_shape_and_dtype():
    csr = _path(8)
    assert check_partition(csr, np.array([0, 0, 0, 0, 1, 1, 1, 2]), 2,
                           0.03)["errors"]
    assert check_partition(csr, np.array([0, 0, 0, 0, 1, 1, 1, -1]), 2,
                           0.03)["errors"]
    assert check_partition(csr, np.zeros(7, dtype=np.int32), 2, 0.03)["errors"]
    assert check_partition(csr, np.zeros(8), 2, 0.03)["errors"]


def test_wrong_reported_cut_is_an_error():
    """``Request.serve`` compares the program's cut with the benchmark's."""
    from perfbench.harness import window

    csr = _path(8)
    part = np.array([0, 0, 0, 0, 1, 1, 1, 1], dtype=np.int32)

    class Solver:
        last_anytime = None

        def result_metrics(self, graph, partition):
            return {"cut": 2}

    errors = window.check_sample(Solver(), None, csr, part, 2, 0.03)["errors"]
    assert any("reports cut 2" in e for e in errors)
