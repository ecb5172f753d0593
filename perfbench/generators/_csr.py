"""Edge list -> CSR, in plain numpy.  A copy of what
``kaminpar_tpu/graphs/host.from_edge_list`` does (PR 22), kept here so
that no later PR can move the benchmark's inputs: both directions of
every undirected edge, self-loops dropped, parallel edges merged into
one edge whose weight is their number."""

from __future__ import annotations

import numpy as np


def csr_from_edges(n: int, edges: np.ndarray) -> dict:
    """``edges`` is an (e, 2) array of undirected edges with unit weight.
    Returns ``{"xadj", "adjncy", "edge_weights"}``; ``edge_weights`` is
    None where every merged weight is 1."""
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    src = np.concatenate([edges[:, 0], edges[:, 1]])
    dst = np.concatenate([edges[:, 1], edges[:, 0]])
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = src * n + dst
    order = np.argsort(key, kind="stable")
    key, src, dst = key[order], src[order], dst[order]
    weights = np.ones(0, dtype=np.int64)
    if len(key):
        first = np.empty(len(key), dtype=bool)
        first[0] = True
        first[1:] = key[1:] != key[:-1]
        weights = np.bincount(np.cumsum(first) - 1).astype(np.int64)
        src, dst = src[first], dst[first]
    xadj = np.zeros(n + 1, dtype=np.int64)
    np.add.at(xadj, src + 1, 1)
    xadj = np.cumsum(xadj)
    unit = bool((weights == 1).all())
    return {"xadj": xadj, "adjncy": dst.astype(np.int32),
            "edge_weights": None if unit else weights}
