"""k-way multilevel partitioner.

Analog of kaminpar-shm/partitioning/kway/kway_multilevel.cc: coarsen on
device until n <= k * contraction_limit (kway_multilevel.cc:144-146), move
the coarsest graph to the host for direct k-way initial partitioning via
recursive bisection, then uncoarsen with device refinement at every level.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from ..dtypes import WEIGHT_DTYPE, WMAX
from ..context import Context
from ..graphs.csr import (
    DeviceGraph,
    device_graph_from_host,
    host_graph_from_device,
)
from ..graphs.host import HostGraph
from ..utils import rng as rng_mod
from ..utils import timer
from ..utils.logger import log_progress
from .coarsener import Coarsener
from .refiner import RefinerPipeline
from .rb import recursive_bipartition


class KWayMultilevelPartitioner:
    def __init__(self, ctx: Context):
        self.ctx = ctx

    @staticmethod
    def _ckpt_state_payload(partition, n: int) -> dict:
        """Checkpoint barrier payload: the current partition pulled to
        host (deliberate, checkpoint-only transfer — the barrier defers
        this call, so disabled runs pull nothing)."""
        return {"state": {
            "partition": np.asarray(partition)[:n].astype(np.int32),
        }}

    def partition(self, graph: HostGraph) -> np.ndarray:
        from ..resilience import memory as memory_mod
        from ..telemetry import quality as quality_mod

        # pre-upload budget check (see deep.py): a budget the bucket
        # cannot fit is refused before the upload, not after the OOM
        memory_mod.preflight(
            graph.n, graph.m, self.ctx.partition.k, where="kway"
        )
        # quality observatory: one hierarchy recording scope per run
        # (telemetry/quality.py; no-op while disabled)
        qh = quality_mod.begin("kway")
        try:
            return self._partition_recorded(graph, qh)
        finally:
            quality_mod.end(qh)

    def _partition_recorded(self, graph: HostGraph, qh) -> np.ndarray:
        ctx = self.ctx
        k = ctx.partition.k
        rng = rng_mod.host_rng(ctx.seed)
        from ..resilience import checkpoint as ckpt
        from ..telemetry import quality as quality_mod
        with timer.scoped_timer("device-upload"):
            dgraph = device_graph_from_host(graph)

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

        # --- coarsening (kway_multilevel.cc:91-142) ---
        from . import debug

        coarsener = Coarsener(ctx, dgraph, graph.n)
        threshold = max(k * ctx.coarsening.contraction_limit, 1)

        # checkpoint resume (resilience/checkpoint.py): rebuild the
        # recorded hierarchy/partition and skip completed stages
        from .coarsener import newest_level_snapshot, restore_levels

        resume = ckpt.take_resume("kway")
        stage = None
        partition = None
        num_levels = None
        if resume is not None:
            stage = resume["stage"]
            meta = resume.get("meta", {})
            restored = restore_levels(coarsener, dgraph, resume["arrays"])
            num_levels = meta.get("num_levels")
            st = resume["arrays"].get("state")
            if st is not None:
                padded = np.zeros(coarsener.current.n_pad, dtype=np.int32)
                part_host = np.asarray(st["partition"], dtype=np.int32)
                padded[: part_host.shape[0]] = part_host
                partition = jnp.asarray(padded)
            from .. import telemetry

            telemetry.event(
                "resume", scheme="kway", stage=stage,
                level=resume.get("level"), levels_restored=restored,
            )

        if stage is None or stage == "coarsen":
            with timer.scoped_timer("coarsening"):
                while coarsener.current_n > threshold:
                    if not coarsener.coarsen():
                        break
                    log_progress(
                        f"coarsening level {coarsener.level}: "
                        f"n={coarsener.current_n}"
                    )
                    if ctx.debug.dump_graph_hierarchy:
                        debug.dump_graph_hierarchy(
                            ctx,
                            host_graph_from_device(coarsener.current),
                            coarsener.level,
                        )
                    if not ckpt.barrier(
                        "coarsen", level=coarsener.level, scheme="kway",
                        payload=lambda: {
                            f"level-{coarsener.level - 1}":
                                newest_level_snapshot(coarsener)
                        },
                        keep=[
                            f"level-{j}" for j in range(coarsener.level - 1)
                        ],
                    ):
                        break  # deadline wind-down

        if stage in (None, "coarsen"):
            # --- initial partitioning on host (rb to k) ---
            with timer.scoped_timer("initial-partitioning"):
                from .. import telemetry

                telemetry.event(
                    "initial-partitioning",
                    n=int(coarsener.current_n),
                    k=int(k),
                    levels=int(coarsener.level),
                )
                coarsest_host = host_graph_from_device(coarsener.current)
                debug.dump_coarsest_graph(ctx, coarsest_host)
                init_part = recursive_bipartition(coarsest_host, k, ctx, rng)
                debug.dump_coarsest_partition(ctx, init_part)
                part_padded = np.zeros(coarsener.current.n_pad, dtype=np.int32)
                part_padded[: coarsest_host.n] = init_part
                partition = jnp.asarray(part_padded)
                # quality: the coarsest level's entry cut
                quality_mod.note_projected(
                    coarsener.level, coarsener.current, partition, k=k
                )
            num_levels = coarsener.level + 1
            ckpt.barrier(
                "initial", level=coarsener.level, scheme="kway",
                payload=lambda: self._ckpt_state_payload(
                    partition, coarsener.current_n
                ),
                keep=[f"level-{j}" for j in range(coarsener.level)],
                meta={"num_levels": num_levels},
            )

        # --- uncoarsening + refinement (kway_multilevel.cc:70-89) ---
        refiner = RefinerPipeline(ctx, k)
        if num_levels is None:
            num_levels = coarsener.level + 1
        # debug hierarchy dumps are STAGED: collected by reference during
        # the span, pulled to host only after it closes, so the
        # uncoarsening span never carries the readback (tpulint R1)
        pending_dumps = []
        with timer.scoped_timer("uncoarsening"):
            level = coarsener.level
            if stage != "uncoarsen":
                partition = refiner.refine(
                    coarsener.current,
                    partition,
                    max_bw,
                    min_bw,
                    seed=ctx.seed,
                    level=level,
                    num_levels=num_levels,
                )
                quality_mod.note_refined(
                    level, coarsener.current, partition, k=k
                )
                part_now = partition
                ckpt.barrier(
                    "uncoarsen", level=level, scheme="kway",
                    payload=lambda: self._ckpt_state_payload(
                        part_now, coarsener.current_n
                    ),
                    keep=[f"level-{j}" for j in range(level)],
                    meta={"num_levels": num_levels},
                )
            while not coarsener.empty():
                fine_graph, partition = coarsener.uncoarsen(partition)
                level -= 1
                quality_mod.note_projected(level, fine_graph, partition, k=k)
                partition = refiner.refine(
                    fine_graph,
                    partition,
                    max_bw,
                    min_bw,
                    seed=ctx.seed,
                    level=level,
                    num_levels=num_levels,
                )
                quality_mod.note_refined(level, fine_graph, partition, k=k)
                if ctx.debug.dump_partition_hierarchy:
                    pending_dumps.append(
                        (level, partition, coarsener.current_n)
                    )
                part_now = partition
                ckpt.barrier(
                    "uncoarsen", level=level, scheme="kway",
                    payload=lambda: self._ckpt_state_payload(
                        part_now, coarsener.current_n
                    ),
                    keep=[f"level-{j}" for j in range(level)],
                    meta={"num_levels": num_levels},
                )
        for dump_level, dump_part, dump_n in pending_dumps:
            debug.dump_partition_hierarchy(
                ctx, np.asarray(dump_part)[:dump_n], dump_level
            )

        # strict balance backstop on the finest level; readback scopes
        # as deep's: the overload check is the first host read after the
        # last refiner, the download the last of the request
        with timer.scoped_timer("balance-check", sync=True):
            partition = refiner.enforce_balance_host(
                dgraph, partition,
                np.asarray(ctx.partition.max_block_weights), where="kway",
            )
        # quality: coarsening floors + per-level attribution from the
        # final partition (telemetry/quality.py)
        quality_mod.finalize_device(qh, dgraph, partition, graph.n)
        with timer.scoped_timer("partition-download", sync=True):
            return np.asarray(partition)[: graph.n]
