"""V-cycle partitioner.

Analog of kaminpar-shm/partitioning/deep/vcycle_deep_multilevel.cc:
iterated deep multilevel with community restriction — run deep multilevel
once, then for each configured v-cycle re-coarsen the graph with clustering
restricted to the current blocks (communities), project the partition down,
and refine back up.  Each cycle can only improve the cut because the
community restriction keeps the projected partition valid at every level.
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

from ..dtypes import WEIGHT_DTYPE, WMAX
from ..context import Context
from ..graphs.csr import upload_input_graph
from ..graphs.host import HostGraph
from ..ops.contraction import contract_clustering
from ..ops.lp import LPConfig, lp_cluster
from ..utils import timer
from ..utils.logger import log_progress
from .deep import DeepMultilevelPartitioner
from .refiner import RefinerPipeline


class VcycleDeepMultilevelPartitioner:
    def __init__(self, ctx: Context, initial_partition=None,
                 max_levels: int | None = None):
        """``initial_partition`` warm-starts the cycles: the initial
        deep multilevel run is skipped and the given (valid, full-k)
        partition seeds cycle 0 — the dynamic-repartitioning driver's
        entry (dynamic/repartition.py).  ``max_levels`` bounds the
        restricted-coarsening depth per cycle (0 = a pure refinement
        pass at the fine level); None = coarsen to the usual threshold.
        """
        self.ctx = ctx
        self.initial_partition = initial_partition
        self.max_levels = max_levels

    def partition(self, graph: HostGraph) -> np.ndarray:
        ctx = self.ctx
        k = ctx.partition.k
        from ..resilience import checkpoint as ckpt

        # checkpoint resume (resilience/checkpoint.py): a `vcycle` stage
        # holds the partition after a completed cycle — skip the initial
        # deep run and every finished cycle.  A kill DURING the initial
        # deep run instead left a `deep`-scheme checkpoint, which the
        # embedded deep driver below resumes on its own.
        resume = ckpt.take_resume("vcycle")
        start_cycle = 0
        part = None
        if resume is not None and "state" in resume["arrays"]:
            part = np.asarray(
                resume["arrays"]["state"]["partition"], dtype=np.int32
            )
            start_cycle = int(resume.get("level") or 0) + 1
            from .. import telemetry

            telemetry.event(
                "resume", scheme="vcycle", stage=resume["stage"],
                level=resume.get("level"),
            )

        if part is None and self.initial_partition is not None:
            # warm start: the previous (session) partition replaces the
            # initial deep run; a checkpoint resume above still wins —
            # kill-and-resume must re-enter the recorded cycle, not
            # restart from the warm seed
            part = np.asarray(self.initial_partition, dtype=np.int32)
            if part.shape != (graph.n,):
                raise ValueError(
                    f"warm-start partition shape {part.shape} != "
                    f"({graph.n},)")
            if len(part) and (int(part.min()) < 0
                              or int(part.max()) >= k):
                raise ValueError(
                    "warm-start partition labels out of range "
                    f"[0, {k})")
        if part is None:
            # initial partition via one full deep multilevel run
            deep_ctx = ctx.copy()
            from ..context import PartitioningMode

            deep_ctx.partitioning.mode = PartitioningMode.DEEP
            deep_ctx.partition = ctx.partition  # share the configured weights
            part = DeepMultilevelPartitioner(deep_ctx).partition(graph)

        from .. import telemetry
        from ..graphs.host import host_partition_metrics

        num_cycles = max(len(ctx.partitioning.vcycles), 1)
        for cycle in range(start_cycle, num_cycles):
            from ..resilience import deadline as deadline_mod

            if deadline_mod.should_stop():
                # anytime wind-down: cycles only improve an already-valid
                # partition — stop starting new ones
                break
            with timer.scoped_timer(f"vcycle-{cycle}"):
                part = self._one_vcycle(graph, part, cycle)
            # cut per cycle only for plain CSR inputs (compressed graphs
            # lack the host edge arrays; the facade decodes before vcycle
            # dispatch, but direct callers may not)
            if telemetry.enabled() and isinstance(graph, HostGraph):
                telemetry.event(
                    "vcycle",
                    cycle=cycle,
                    cut=int(host_partition_metrics(graph, part, k)["cut"]),
                )
            part_now = part
            ckpt.barrier(
                "vcycle", level=cycle, scheme="vcycle",
                payload=lambda: {"state": {
                    "partition": np.asarray(part_now, dtype=np.int32),
                }},
            )
        return part

    def _one_vcycle(
        self, graph: HostGraph, part: np.ndarray, cycle: int
    ) -> np.ndarray:
        """Community-restricted coarsen -> project down -> refine up."""
        from ..telemetry import quality as quality_mod

        # quality observatory: each cycle records its own hierarchy
        # (last finalize wins the report section, so the FINAL cycle's
        # attribution describes the returned partition)
        qh = quality_mod.begin("vcycle")
        try:
            return self._one_vcycle_recorded(graph, part, cycle, qh)
        finally:
            quality_mod.end(qh)

    def _one_vcycle_recorded(
        self, graph: HostGraph, part: np.ndarray, cycle: int, qh
    ) -> np.ndarray:
        from ..telemetry import quality as quality_mod

        ctx = self.ctx
        k = ctx.partition.k
        dgraph = upload_input_graph(graph)
        padded = np.zeros(dgraph.n_pad, dtype=np.int32)
        padded[: graph.n] = part
        partition = jnp.asarray(padded)

        max_bw = jnp.asarray(
            np.minimum(ctx.partition.max_block_weights, WMAX),
            dtype=WEIGHT_DTYPE,
        )
        min_bw = (
            jnp.asarray(
                np.minimum(ctx.partition.min_block_weights, WMAX),
                dtype=WEIGHT_DTYPE,
            )
            if ctx.partition.min_block_weights is not None
            else None
        )
        lp_cfg = LPConfig(
            num_iterations=ctx.coarsening.clustering.lp.num_iterations,
            participation=ctx.coarsening.clustering.lp.participation,
        )

        # coarsen with community restriction
        levels = []
        current = dgraph
        current_part = partition
        current_n = graph.n
        threshold = max(2 * ctx.coarsening.contraction_limit, 2)
        level = 0
        while current_n > threshold and (
            self.max_levels is None or level < self.max_levels
        ):
            max_cw = max(
                1,
                ctx.coarsening.max_cluster_weight(
                    current_n, ctx.partition.total_node_weight, ctx.partition
                ),
            )
            seed = jnp.int32(
                (ctx.seed * 65713 + cycle * 977 + level * 31337) & 0x7FFFFFFF
            )
            labels = lp_cluster(
                current,
                jnp.asarray(min(max_cw, WMAX), dtype=WEIGHT_DTYPE),
                seed,
                lp_cfg,
                communities=current_part,
            )
            coarse, c_n, c_m = contract_clustering(current, labels)
            if c_n >= (1.0 - ctx.coarsening.convergence_threshold) * current_n:
                break
            # project the partition down: clusters never span blocks
            coarse_part = coarse.project_down(current_part)
            levels.append((current, coarse, current_part))
            quality_mod.note_cmap(
                level=len(levels), cmap=coarse.cmap, fine_n=current_n
            )
            quality_mod.note_contraction(
                level=len(levels), fine_graph=current, coarse=coarse,
                fine_n=current_n, coarse_n=c_n, coarse_m=c_m,
                max_cluster_weight=max_cw,
                total_node_weight=int(ctx.partition.total_node_weight),
            )
            current = coarse.graph
            current_part = coarse_part
            current_n = c_n
            level += 1
            log_progress(f"vcycle coarsening level {level}: n={c_n}")

        # refine back up
        refiner = RefinerPipeline(ctx, k)
        num_levels = len(levels) + 1
        quality_mod.note_projected(len(levels), current, current_part, k=k)
        current_part = refiner.refine(
            current,
            current_part,
            max_bw,
            min_bw,
            seed=ctx.seed + cycle,
            level=len(levels),
            num_levels=num_levels,
        )
        quality_mod.note_refined(len(levels), current, current_part, k=k)
        for lvl in range(len(levels) - 1, -1, -1):
            fine_graph, coarse, _ = levels[lvl]
            current_part = coarse.project_up(current_part)
            quality_mod.note_projected(lvl, fine_graph, current_part, k=k)
            current_part = refiner.refine(
                fine_graph,
                current_part,
                max_bw,
                min_bw,
                seed=ctx.seed + cycle,
                level=lvl,
                num_levels=num_levels,
            )
            quality_mod.note_refined(lvl, fine_graph, current_part, k=k)

        current_part = refiner.enforce_balance_host(
            dgraph, current_part,
            np.asarray(ctx.partition.max_block_weights), where="vcycle",
        )
        quality_mod.finalize_device(qh, dgraph, current_part, graph.n)
        return np.asarray(current_part)[: graph.n]
