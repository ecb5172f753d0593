"""Recursive bipartitioning scheme.

Analog of kaminpar-shm/partitioning/rb/rb_multilevel.cc: partition into 2,
recurse per block.  Each bisection is a full sequential multilevel
bipartition (partitioning/rb.py); the finest-level partition is then refined
on device with the context's refiner pipeline.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..dtypes import WEIGHT_DTYPE, WMAX
from ..context import Context
from ..graphs.csr import upload_input_graph
from ..graphs.host import HostGraph
from ..utils import rng as rng_mod
from ..utils import timer
from .refiner import RefinerPipeline
from .rb import recursive_bipartition


class RBMultilevelPartitioner:
    def __init__(self, ctx: Context):
        self.ctx = ctx

    def partition(self, graph: HostGraph) -> np.ndarray:
        ctx = self.ctx
        k = ctx.partition.k
        rng = rng_mod.host_rng(ctx.seed ^ 0x5B)
        with timer.scoped_timer("recursive-bipartitioning"):
            part = recursive_bipartition(graph, k, ctx, rng)

        # barrier (resilience/checkpoint.py): rb has no multilevel
        # hierarchy to snapshot — the bisection tree IS the work — but
        # the deadline/preemption wind-down still gates the optional
        # top-level polish (the facade's result checkpoint covers
        # durability)
        from ..resilience import checkpoint as ckpt

        proceed = ckpt.barrier("rb-toplevel", scheme="rb")
        if proceed and ctx.partitioning.rb_enable_kway_toplevel_refinement:
            with timer.scoped_timer("toplevel-refinement"):
                dgraph = upload_input_graph(graph)
                padded = np.zeros(dgraph.n_pad, dtype=np.int32)
                padded[: graph.n] = part
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
                refiner = RefinerPipeline(ctx, k)
                refined = refiner.refine(
                    dgraph, jnp.asarray(padded), max_bw, min_bw, seed=ctx.seed
                )
            # the balance backstop and final readback live OUTSIDE the
            # refinement span: both are host-phase work, and keeping the
            # device->host pull out of the timed region keeps the span
            # honest about refinement cost (tpulint R1)
            refined = refiner.enforce_balance_host(
                dgraph, refined,
                np.asarray(ctx.partition.max_block_weights), where="rb",
            )
            part = np.asarray(refined)[: graph.n]
        return part
