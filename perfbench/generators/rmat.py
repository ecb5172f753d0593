"""R-MAT generator: a copy of ``kaminpar_tpu/graphs/factories.make_rmat``
as of PR 22 (same draws from the same numpy Generator, so the same seed
gives the same graph as ``gen:rmat;n=..;m=..;seed=..``)."""

from __future__ import annotations

import numpy as np

from ._csr import csr_from_edges


def generate(params: dict, seed: int) -> dict:
    """``params``: ``n`` (a power of two), ``m`` (requested edges), ``a``,
    ``b``, ``c`` (quadrant probabilities; d is the rest)."""
    n, m = int(params["n"]), int(params["m"])
    scale = int(np.log2(n))
    if 1 << scale != n:
        raise ValueError("rmat n must be a power of two")
    a, b, c = float(params["a"]), float(params["b"]), float(params["c"])
    probs = np.array([a, b, c, 1.0 - a - b - c])
    rng = np.random.default_rng(seed)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for _ in range(scale):
        quad = rng.choice(4, size=m, p=probs)
        src = (src << 1) | (quad >> 1)
        dst = (dst << 1) | (quad & 1)
    return csr_from_edges(n, np.stack([src, dst], axis=1))
