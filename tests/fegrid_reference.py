"""The plain reference of the triangulated FE grid
(`graphs/factories.make_fe_grid`, the repo's structured stand-in of a
finite-element mesh), for the tests of the `strong` preset
(tests/test_strong_deployment.py; the Delaunay mesh's reference is
tests/mesh_reference.py).

Semantics: `graphs.host.host_partition_metrics` recounts cut and block
weights of any partition.  Quality: a structured grid has an independent
plain partitioner of the same semantics that knows nothing of the edges:
cut the rows into `across` bands and the columns into `along` bands; the
blocks are the rectangles.  `cut_of` counts a partition's cut straight
from the grid's three edge families, in numpy, without the graph.
"""

import numpy as np


def rectangles(rows: int, cols: int, k: int) -> np.ndarray:
    """Blocks `[0, k)` of the `rows * cols` vertices (row-major ids, as
    `factories.make_fe_grid` numbers them): k = `across` x `along`
    rectangles, the squarest split of a power-of-two k (16: 4 x 4, 2:
    2 x 1); a band boundary falls where `i * parts // size` steps."""
    across = 1 << (k.bit_length() // 2)
    along = k // across
    assert across * along == k, "k is a power of two"
    band_r = np.arange(rows) * across // rows
    band_c = np.arange(cols) * along // cols
    return (band_r[:, None] * along + band_c[None, :]).ravel().astype(np.int32)


def cut_of(rows: int, cols: int, part: np.ndarray) -> int:
    """Edges of the triangulated grid (right, down, and the one diagonal
    of every unit cell) whose ends lie in different blocks."""
    p = np.asarray(part).reshape(rows, cols)
    return int((p[:, :-1] != p[:, 1:]).sum() + (p[:-1, :] != p[1:, :]).sum()
               + (p[:-1, :-1] != p[1:, 1:]).sum())
