"""Delaunay triangulation of uniform random points on the unit square: a
copy of ``kaminpar_tpu/graphs/factories.make_delaunay`` as of PR 22."""

from __future__ import annotations

import numpy as np

from ._csr import csr_from_edges


def generate(params: dict, seed: int) -> dict:
    """``params``: ``n`` (number of points)."""
    from scipy.spatial import Delaunay  # part of the installation

    n = int(params["n"])
    rng = np.random.default_rng(seed)
    tri = Delaunay(rng.random((n, 2)))
    s = tri.simplices
    e = np.concatenate([s[:, [0, 1]], s[:, [1, 2]], s[:, [0, 2]]])
    e = np.unique(np.sort(e, axis=1), axis=0)
    return csr_from_edges(n, e.astype(np.int64))
