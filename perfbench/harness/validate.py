"""The comparison that decides ``correct``, in plain numpy on the CSR
arrays the benchmark generated; independent of ``graphs/host.py``."""

from __future__ import annotations

import numpy as np


def edge_cut(csr: dict, part: np.ndarray) -> int:
    """Weight of the edges whose ends lie in different blocks.  The CSR
    holds every undirected edge twice, hence the halving."""
    xadj, adjncy = csr["xadj"], csr["adjncy"]
    src = np.repeat(np.arange(len(xadj) - 1), np.diff(xadj))
    crossing = part[src] != part[adjncy]
    weights = csr.get("edge_weights")
    if weights is None:
        return int(crossing.sum()) // 2
    return int(weights[crossing].sum()) // 2


def max_block_weight(total_weight: int, k: int, epsilon: float) -> float:
    """The reference's strict balance bound: (1 + eps) * ceil(W / k)."""
    return (1 + epsilon) * -(-int(total_weight) // int(k))


def check_partition(csr: dict, part, k: int, epsilon: float) -> dict:
    """``{"cut", "max_block_weight", "bound", "errors"}``; ``errors`` is
    empty for a valid, feasible partition.  Node weights are unit unless
    the CSR carries ``node_weights``."""
    def invalid(why: str) -> dict:
        return {"cut": None, "max_block_weight": None, "bound": None,
                "errors": [why]}

    n = len(csr["xadj"]) - 1
    errors = []
    part = np.asarray(part)
    if part.shape != (n,):
        return invalid(f"shape {part.shape} != ({n},)")
    if not np.issubdtype(part.dtype, np.integer):
        return invalid(f"dtype {part.dtype} is not an integer type")
    lo, hi = int(part.min()), int(part.max())
    if lo < 0 or hi >= k:
        return invalid(f"labels [{lo}, {hi}] outside [0, {k})")
    node_w = csr.get("node_weights")
    if node_w is None:
        block_w = np.bincount(part, minlength=k)
        total = n
    else:
        block_w = np.bincount(part, weights=node_w, minlength=k)
        total = int(np.sum(node_w))
    bound = max_block_weight(total, k, epsilon)
    heaviest = int(block_w.max())
    if heaviest > bound:
        errors.append(f"infeasible: block weight {heaviest} > "
                      f"(1 + {epsilon}) * ceil({total} / {k}) = {bound}")
    return {"cut": edge_cut(csr, part), "max_block_weight": heaviest,
            "bound": bound, "errors": errors}
