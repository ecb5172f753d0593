"""Sequential multilevel 2-way bipartitioner + adaptive pool.

Analog of kaminpar-shm/initial_partitioning/:
  * InitialMultilevelBipartitioner (initial_multilevel_bipartitioner.cc:
    55 initialize, 83 partition): sequential LP coarsening, flat
    bipartitioner pool on the coarsest level, 2-way FM at every level of
    the uncoarsening.
  * InitialPoolBipartitioner (initial_pool_bipartitioner.h:24-56): runs
    repetitions of the enabled flat bipartitioners, keeps the best result,
    and adaptively disables bipartitioners whose running score is worst
    (use_adaptive_bipartitioner_selection).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import numpy as np

from ..context import InitialPartitioningContext, InitialPoolContext
from ..graphs.host import HostGraph
from ..utils import timer
from .coarsening import coarsen_for_bipartition
from .flat import bfs_bipartition, ggg_bipartition, random_bipartition
from .fm import fm_bipartition_refine


def _host_cut(graph: HostGraph, partition: np.ndarray) -> int:
    src = graph.edge_sources()
    ew = graph.edge_weight_array()
    return int(ew[partition[src] != partition[graph.adjncy]].sum()) // 2


def _host_block_weights(graph: HostGraph, partition: np.ndarray) -> np.ndarray:
    bw = np.zeros(2, dtype=np.int64)
    np.add.at(bw, partition, graph.node_weight_array())
    return bw


# Independent native multilevel bipartitions per call, of which the one
# with the least overload, then the lowest cut, then the lowest index is
# kept.  The pool's repetitions all share ONE coarsening hierarchy, and
# on a mesh that hierarchy decides where the cut runs: one attempt put
# the first bisection of a 131k-node Delaunay mesh between 1.0x and 1.5x
# of a straight line's cut and left blocks at their caps, which Jet then
# pays for with a balancer round and a table rebuild per iteration.  At
# k = 16 over 40 seeds one attempt cut 4,364-4,968 (sd 2.8 %), eight cut
# 4,157-4,462 (sd 1.5 %) with a fifth of the rebuilds (CPU, the
# arithmetic is integer; PERF.md, PR 26).  R-MAT cuts move by under
# 0.5 % either way.  Never more attempts than the pool may repeat: a
# preset that asks for one repetition gets one attempt.
NATIVE_ATTEMPTS = 8
_ATTEMPT_SEED_STRIDE = 0x9E3779B97F4A7C15


def _best_attempt(
    graph: HostGraph, attempts, max_block_weights: np.ndarray
) -> np.ndarray:
    """``attempts``: ``[(int8 partition, cut), ...]``."""
    node_w = graph.node_weight_array()

    def key(i: int) -> Tuple[int, int, int]:
        part, cut = attempts[i]
        bw = np.bincount(part, weights=node_w, minlength=2).astype(np.int64)
        return int(np.maximum(bw - max_block_weights, 0).sum()), cut, i

    return attempts[min(range(len(attempts)), key=key)][0]


@dataclass
class _PoolEntry:
    name: str
    fn: Callable
    runs: int = 0
    mean: float = 0.0
    m2: float = 0.0

    def record(self, cut: int) -> None:
        self.runs += 1
        delta = cut - self.mean
        self.mean += delta / self.runs
        self.m2 += delta * (cut - self.mean)

    def score(self) -> float:
        return self.mean


class PoolBipartitioner:
    """Adaptive pool over the flat bipartitioners
    (initial_pool_bipartitioner.h:24-56)."""

    def __init__(self, ctx: InitialPoolContext):
        self.ctx = ctx
        self.entries: List[_PoolEntry] = []
        if ctx.enable_bfs_bipartitioner:
            self.entries.append(_PoolEntry("bfs", bfs_bipartition))
        if ctx.enable_ggg_bipartitioner:
            self.entries.append(_PoolEntry("ggg", ggg_bipartition))
        if ctx.enable_random_bipartitioner:
            self.entries.append(_PoolEntry("random", random_bipartition))
        if not self.entries:
            self.entries.append(_PoolEntry("random", random_bipartition))

    def bipartition(
        self,
        graph: HostGraph,
        max_block_weights: np.ndarray,
        rng: np.random.Generator,
    ) -> np.ndarray:
        ctx = self.ctx
        n_reps = int(
            np.clip(
                round(ctx.repetition_multiplier * ctx.min_num_repetitions),
                1,
                ctx.max_num_repetitions,
            )
        )
        best_part: Optional[np.ndarray] = None
        best_key: Tuple[int, int] = (1 << 62, 1 << 62)
        for rep in range(n_reps):
            active = self.entries
            if (
                ctx.use_adaptive_bipartitioner_selection
                and rep >= ctx.min_num_non_adaptive_repetitions
                and len(self.entries) > 1
            ):
                # keep all but the worst-scoring bipartitioner
                ranked = sorted(self.entries, key=lambda e: e.score())
                active = ranked[:-1]
            for entry in active:
                with timer.scoped_timer(f"ip-flat-{entry.name}"):
                    part = entry.fn(graph, max_block_weights, rng)
                if not ctx.refinement.disabled:
                    with timer.scoped_timer("ip-fm"):
                        fm_bipartition_refine(
                            graph, part, max_block_weights, ctx.refinement, rng
                        )
                cut = _host_cut(graph, part)
                bw = _host_block_weights(graph, part)
                overload = int(
                    np.maximum(bw - np.asarray(max_block_weights), 0).sum()
                )
                entry.record(cut + overload * 1000)
                key = (overload, cut)
                if key < best_key:
                    best_key = key
                    best_part = part.copy()
        assert best_part is not None
        return best_part


class InitialMultilevelBipartitioner:
    """Sequential multilevel bipartitioner
    (initial_multilevel_bipartitioner.cc)."""

    def __init__(self, ctx: InitialPartitioningContext):
        self.ctx = ctx
        self.pool = PoolBipartitioner(ctx.pool)

    def bipartition(
        self,
        graph: HostGraph,
        max_block_weights: np.ndarray,
        rng: np.random.Generator,
    ) -> np.ndarray:
        """Coarsen -> flat pool bipartition -> uncoarsen with FM refinement.
        Returns int8 partition of `graph`.

        Runs the native (C++) multilevel bipartitioner when the library is
        available — the reference's design point of sequential native
        initial partitioning (initial_bipartitioner_worker_pool.h:42); the
        numpy/python path below is the fallback and the behavioral spec."""
        if graph.n == 0:
            return np.zeros(0, dtype=np.int8)
        max_block_weights = np.asarray(max_block_weights, dtype=np.int64)
        if os.environ.get("KAMINPAR_TPU_NO_NATIVE_IP", "") != "1":
            from .. import native
            from ..resilience import NativeUnavailable, with_fallback

            # check availability BEFORE drawing the seed: the fallback
            # must see the same rng stream whether the native path was
            # skipped by env flag or by a missing toolchain
            if native.available():
                seed = int(rng.integers(0, 2**62))
                # ONE draw from the shared stream whatever the number
                # of attempts: their seeds are strided from it
                seeds = [
                    (seed + i * _ATTEMPT_SEED_STRIDE) & 0xFFFFFFFFFFFFFFFF
                    for i in range(
                        min(NATIVE_ATTEMPTS,
                            self.ctx.pool.max_num_repetitions)
                    )
                ]

                def _native_ip():
                    with timer.scoped_timer("ip-native"):
                        attempts = native.ml_bipartition_attempts(
                            graph, max_block_weights, self.ctx, seeds
                        )
                    if attempts is None:
                        raise NativeUnavailable(
                            "native bipartitioner unavailable"
                        )
                    return _best_attempt(graph, attempts, max_block_weights)

                # fallback: fall through to the numpy multilevel path
                # below (the behavioral spec of the native engine)
                part = with_fallback(
                    _native_ip, lambda exc: None, site="native-ip"
                )
                if part is not None:
                    return part
        with timer.scoped_timer("ip-coarsen"):
            levels = coarsen_for_bipartition(
                graph,
                self.ctx.coarsening,
                rng,
                max_block_weight=int(max_block_weights.max()),
            )
        coarsest = levels[-1].graph if levels else graph
        part = self.pool.bipartition(coarsest, max_block_weights, rng)

        for i in range(len(levels) - 1, -1, -1):
            part = part[levels[i].cmap]  # project up
            fine_graph = levels[i - 1].graph if i > 0 else graph
            if not self.ctx.refinement.disabled:
                with timer.scoped_timer("ip-fm"):
                    fm_bipartition_refine(
                        fine_graph, part, max_block_weights,
                        self.ctx.refinement, rng,
                    )
        return part.astype(np.int8)


def bipartition(
    graph: HostGraph,
    max_block_weights: np.ndarray,
    ctx: InitialPartitioningContext,
    rng: np.random.Generator,
) -> np.ndarray:
    """Convenience entry point (InitialBipartitionerWorkerPool analog)."""
    return InitialMultilevelBipartitioner(ctx).bipartition(
        graph, max_block_weights, rng
    )
