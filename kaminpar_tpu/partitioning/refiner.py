"""Refinement pipeline (analog of kaminpar-shm/refinement/multi_refiner.cc
+ factories.cc:96-145 create_refiner).

Maps the ordered RefinementAlgorithm list from the context onto the device
kernels: LP refinement (ops/lp.lp_refine), overload/underload balancing
(ops/balancer), Jet (ops/jet).  The host FM refiner plugs in here as well.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..context import Context, RefinementAlgorithm
from ..graphs.csr import DeviceGraph, host_graph_from_device
from ..ops import balancer as balancer_ops
from ..ops import metrics
from ..ops.lp import LPConfig, lp_refine
from ..utils import timer
from ..utils.logger import log_debug, log_warning


class RefinerPipeline:
    """Runs the context's refiner list in order (MultiRefiner analog).

    `light=True` marks refinement of an intermediate k-doubling
    extension (another doubling immediately follows): Jet runs a single
    round there — the partition gets its full-strength refine at the
    final extension of the level."""

    def __init__(self, ctx: Context, k: int, light: bool = False):
        self.ctx = ctx
        self.k = k
        self.light = light
        self._lp_cfg = LPConfig(
            num_iterations=ctx.refinement.lp.num_iterations,
            participation=ctx.refinement.lp.participation,
            allow_tie_moves=False,
            use_active_set=True,
            refinement=True,
        )

    def refine(
        self,
        graph: DeviceGraph,
        partition: jax.Array,
        max_block_weights: jax.Array,
        min_block_weights: Optional[jax.Array],
        seed: int,
        level: int = 0,
        num_levels: int = 1,
    ) -> jax.Array:
        from ..telemetry import progress as progress_mod
        from ..ops.segments import pad_k_bucket
        from ..resilience import maybe_inject

        # `device-oom` chaos injection at refinement entry: OUTSIDE the
        # per-step `refiner` rollback wrappers below, so the failure
        # reaches the facade's memory-governor recovery ladder instead
        # of a step rollback
        maybe_inject("device-oom")
        k, max_block_weights, min_block_weights = pad_k_bucket(
            self.k, max_block_weights, min_block_weights
        )
        # label every refiner's progress series with the uncoarsening
        # level — the timer path repeats per level, the tag does not.
        # num_levels rides along so the quality observatory's verdicts
        # (telemetry/quality.py) can tell a coarse-level stall from a
        # fine-level one, and the active hierarchy id keeps a nested IP
        # run's series (same stream, same level numbering) out of the
        # outer hierarchy's verdict join.
        from ..telemetry import quality as quality_mod

        from ..resilience import integrity as integrity_mod

        with progress_mod.tag(
            level=level, num_levels=num_levels,
            quality_hierarchy=quality_mod.current_id(),
        ):
            # refinement sentinels (resilience/integrity.py): probe
            # (cut, feasibility, label range) before and after the
            # accepted pass — a feasible->feasible pass that RAISED the
            # cut, or a label outside [0, k), is silent corruption, not
            # a degradation.  `bit-flip:partition` chaos mutates the
            # refined vector in flight so the detector is exercised
            # end-to-end.  Separate small jitted reductions; the
            # LP/Jet/balancer jaxprs are untouched either way.
            before = integrity_mod.refine_probe(
                graph, partition, max_block_weights, min_block_weights
            )
            refined = self._refine_tagged(
                graph, partition, k, max_block_weights, min_block_weights,
                seed, level, num_levels,
            )
            refined = integrity_mod.chaos_corrupt_partition(refined)
            after = integrity_mod.refine_probe(
                graph, refined, max_block_weights, min_block_weights
            )
            integrity_mod.check_refinement(
                before, after, k=int(k), level=level
            )
            if after is not None:
                integrity_mod.audit_refine_cut(
                    graph, refined, after[0], level=level
                )
            return refined

    def _refine_tagged(
        self, graph, partition, k, max_block_weights, min_block_weights,
        seed, level, num_levels,
    ):
        from ..resilience import deadline as deadline_mod
        from ..resilience import with_fallback
        from ..utils import statistics

        for i, algorithm in enumerate(self.ctx.refinement.algorithms):
            # anytime wind-down (resilience/deadline.py): once the budget
            # expires or a preemption signal arrived, stop STARTING
            # refiner steps — the drivers' enforce_balance_host and the
            # output gate keep the balance guarantee on the best
            # partition reached so far
            if deadline_mod.should_stop():
                # the quality observatory joins this into the level's
                # refinement-efficacy verdict: a skipped refiner is
                # budget-capped by definition, not stalled
                from .. import telemetry

                from ..telemetry import quality as quality_mod

                telemetry.event(
                    "refine-skipped",
                    level=level,
                    algorithm=algorithm.value,
                    reason="deadline",
                    quality_hierarchy=quality_mod.current_id(),
                )
                log_debug(
                    f"deadline: skipping {algorithm.value} at level "
                    f"{level} (wind-down)"
                )
                break
            salt = jnp.int32((seed * 2654435761 + i * 40503 + level) & 0x7FFFFFFF)
            if algorithm == RefinementAlgorithm.NOOP:
                continue
            step = self._make_step(
                algorithm, graph, k, max_block_weights, min_block_weights,
                salt, seed + i, level, num_levels,
            )
            if step is None:
                continue
            # Jet-style recoverability (the Gilbert et al. / Mt-KaHyPar
            # discipline): a refiner step that fails — device OOM, a
            # refusal, an injected chaos fault — is rolled back to the
            # best-known partition (its input) instead of aborting the
            # run; the balancer step instead degrades to the exact host
            # balancer so the balance guarantee is not lost with it.
            prev = partition
            if algorithm == RefinementAlgorithm.OVERLOAD_BALANCER:
                partition = with_fallback(
                    lambda s=step: s(prev),
                    lambda exc: self._host_balance(
                        graph, prev, np.asarray(max_block_weights)
                    ),
                    site="device-balancer",
                    where=f"level{level}",
                )
            else:
                partition = with_fallback(
                    lambda s=step: s(prev),
                    lambda exc: prev,
                    site="refiner",
                    where=f"{algorithm.value}@level{level}",
                )
            if statistics.enabled():
                statistics.track(
                    f"cut_after_{algorithm.value}",
                    int(metrics.edge_cut(graph, partition)),
                )
                statistics.count(f"runs_{algorithm.value}")
        return partition

    def _make_step(
        self, algorithm, graph, k, max_block_weights, min_block_weights,
        salt, seed, level, num_levels,
    ):
        """One refinement algorithm as a partition -> partition closure
        (the unit the degradation contract wraps); None = skipped."""
        if algorithm == RefinementAlgorithm.LABEL_PROPAGATION:
            def step(partition):
                with timer.scoped_timer("lp-refinement"):
                    return lp_refine(
                        graph, partition, k, max_block_weights, salt,
                        self._lp_cfg,
                    )
        elif algorithm == RefinementAlgorithm.OVERLOAD_BALANCER:
            def step(partition):
                with timer.scoped_timer("overload-balancer"):
                    return balancer_ops.overload_balance(
                        graph,
                        partition,
                        k,
                        max_block_weights,
                        salt,
                        max_rounds=self.ctx.refinement.balancer.max_rounds,
                    )
        elif algorithm == RefinementAlgorithm.UNDERLOAD_BALANCER:
            if min_block_weights is None:
                return None

            def step(partition):
                with timer.scoped_timer("underload-balancer"):
                    return balancer_ops.underload_balance(
                        graph,
                        partition,
                        k,
                        max_block_weights,
                        min_block_weights,
                        salt,
                        max_rounds=self.ctx.refinement.balancer.max_rounds,
                    )
        elif algorithm == RefinementAlgorithm.JET:
            from ..ops.jet import iteration_path, jet_refine

            jet_ctx = self.ctx.refinement.jet
            if self.light:
                jet_ctx = dataclasses.replace(
                    jet_ctx,
                    num_rounds_on_fine_level=1,
                    num_rounds_on_coarse_level=1,
                )

            def step(partition):
                # directly under `jet`, the iteration the shapes resolve
                # to (`jet-rows`, `jet-edges`, `jet-lp`): the pattern of
                # coarsener._lp_clustering_scope's `rating-<engine>`
                with timer.scoped_timer("jet"), timer.scoped_timer(
                    iteration_path(graph, k)
                ):
                    return jet_refine(
                        graph,
                        partition,
                        k,
                        max_block_weights,
                        salt,
                        jet_ctx,
                        level=level,
                        num_levels=num_levels,
                    )
        elif algorithm == RefinementAlgorithm.MTKAHYPAR:
            from ..refinement.mtkahypar import mtkahypar_refine_host

            def step(partition):
                # the host pulls happen BEFORE the span opens: the
                # mtkahypar span times the external refiner, not the
                # device->host transfer (tpulint R1)
                host = host_graph_from_device(graph)
                part_h = np.asarray(partition)[: host.n]
                caps_h = np.asarray(max_block_weights)[: self.k]
                with timer.scoped_timer("mtkahypar"):
                    # host refiners see the real k, not the padded bucket
                    refined = mtkahypar_refine_host(
                        host,
                        part_h,
                        self.k,
                        max_block_weights=caps_h,
                        epsilon=self.ctx.partition.epsilon,
                        seed=seed,
                        threads=self.ctx.parallel.num_workers,
                    )
                    full = np.zeros(graph.n_pad, dtype=np.int32)
                    full[: host.n] = refined
                    return jnp.asarray(full)
        elif algorithm == RefinementAlgorithm.GREEDY_FM:
            # FM earns its host round-trip where moves are worth the
            # most polish: the finest levels (coarse-level structure
            # is Jet's job, and a full FM pass there re-pays ~0.1%
            # cut for full pass cost).  Light intermediate extensions
            # skip it entirely like they skip full Jet.
            if self.light or level > self.ctx.refinement.fm.max_level:
                return None
            from ..refinement.fm import fm_refine_host

            def step(partition):
                with timer.scoped_timer("kway-fm"):
                    return fm_refine_host(
                        graph,
                        partition,
                        self.k,
                        max_block_weights[: self.k],
                        self.ctx.refinement.fm,
                        seed=seed,
                        # worker pool (fm_refiner.cc:48): 1 in every
                        # preset but strong-parallel; any count gives
                        # the same labels on more than one (fm.cpp)
                        threads=self.ctx.parallel.num_workers,
                    )
        else:
            log_warning(f"unknown refinement algorithm: {algorithm}")
            return None
        return step

    def _host_balance(
        self,
        graph: DeviceGraph,
        partition: jax.Array,
        max_block_weights: np.ndarray,
    ) -> jax.Array:
        """The exact host balancer as a device-partition transform (the
        device-balancer site's fallback and enforce_balance_host's
        engine)."""
        host = host_graph_from_device(graph)
        n = host.n
        part_h = np.asarray(partition)[:n].copy()
        balanced = balancer_ops.host_balance(
            host.node_weight_array(),
            (host.xadj, host.adjncy, host.edge_weight_array()),
            part_h,
            np.asarray(max_block_weights),
        )
        full = np.zeros(graph.n_pad, dtype=np.int32)
        full[:n] = balanced
        return jnp.asarray(full)

    def enforce_balance_host(
        self,
        graph: DeviceGraph,
        partition: jax.Array,
        max_block_weights: np.ndarray,
        where: str = "",
    ) -> jax.Array:
        """Exact host fallback for the strict balance guarantee
        (README.MD:18) when device balancing rounds stall.  `where`
        labels the calling driver phase in the telemetry event, so a
        degraded balancer in `deep` uncoarsening reads differently from
        one in a `vcycle` restart."""
        over = int(
            metrics.total_overload(
                graph, partition, jnp.asarray(max_block_weights)
            )
        )
        if over == 0:
            return partition
        from .. import telemetry

        # the device balancers stalled with residual overload — a silent
        # quality/perf decision the run report must show
        telemetry.event(
            "balancer-host-fallback",
            residual_overload=over,
            where=where or None,
        )
        log_debug(
            f"host balance fallback{' (' + where + ')' if where else ''}, "
            f"residual overload {over}"
        )
        return self._host_balance(graph, partition, max_block_weights)
