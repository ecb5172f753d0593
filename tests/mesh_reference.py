"""The plain reference of the mesh deployment (DIMACS-10 Delaunay family,
perfbench/configs/delaunay-n17-default.json), for the tests.

Semantics: `graphs.host.host_partition_metrics` recounts cut and block
weights of any partition.  Quality: a mesh has an independent plain
partitioner of the same semantics, recursive coordinate bisection of
the points the triangulation was made from, which is exactly balanced
at a power-of-two k and knows nothing of the edges.
"""

import numpy as np

from kaminpar_tpu.graphs import factories


def delaunay_mesh(n: int, seed: int):
    """`(points, graph)`: the points `factories.make_delaunay(n, seed)`
    triangulates (the same generator state), and its graph."""
    points = np.random.default_rng(seed).random((n, 2))
    return points, factories.make_delaunay(n, seed=seed)


def recursive_coordinate_bisection(points: np.ndarray, k: int) -> np.ndarray:
    """Blocks `[0, k)` of `points`: sort by the longer axis of the
    bounding box, split at the median (in proportion where k is odd),
    recurse."""
    part = np.zeros(len(points), dtype=np.int32)

    def split(ids: np.ndarray, first: int, last: int) -> None:
        if last - first == 1:
            part[ids] = first
            return
        box = points[ids]
        axis = int(np.argmax(box.max(axis=0) - box.min(axis=0)))
        order = ids[np.argsort(box[:, axis], kind="stable")]
        middle = (first + last) // 2
        at = len(order) * (middle - first) // (last - first)
        split(order[:at], first, middle)
        split(order[at:], middle, last)

    split(np.arange(len(points)), 0, k)
    return part
