"""Distributed deep-multilevel partitioner facade (dKaMinPar analog).

Mirrors kaminpar-dist's orchestration (kaminpar-dist/dkaminpar.cc:496
compute_partition + partitioning/deep_multilevel.cc):

  coarsening   distributed LP clustering over the device mesh
               (parallel/dist_lp.dist_lp_cluster — the GlobalLPClusteringImpl
               analog), followed by contraction.  The reference migrates
               coarse nodes/edges between PEs with sparse alltoalls
               (global_cluster_contraction.cc); here graphs that fit one
               device are contracted by the DEVICE kernel (the sort-based
               dedup in ops/contraction — labels are consistent across
               devices, so a single device-resident contraction replaces
               per-PE rating maps), and only the coarse CSR is pulled back
               to re-shard onto the mesh for the next level.  Graphs above
               the single-device budget run the SHARDED contraction
               (parallel/dist_contraction.py: per-shard dedup + one
               all_to_all coarse-edge migration) so the fine edge list
               never materializes on one device; either way coarse levels
               are geometrically smaller and the fine-level LP rounds
               (the dominant cost) stay fully on-device.

  initial      the coarsest graph is partitioned by the shared-memory
  partitioning KaMinPar pipeline — exactly the reference's scheme of
               replicating the coarsest graph onto every PE and running shm
               KaMinPar (deep_multilevel.cc:125-176, kaminpar_initial_
               partitioner.cc); with a replicated-per-device mesh there is
               one host, so replication is the identity.

  uncoarsening project up through the stored cluster maps and run
               distributed LP refinement per level (the batched LP refiner
               analog, kaminpar-dist/refinement/lp/lp_refiner.cc).
"""

from __future__ import annotations

from typing import List, Optional, Tuple, Union

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..dtypes import WEIGHT_DTYPE, WMAX
from ..context import Context
from ..graphs.csr import device_graph_from_host, host_graph_from_device
from ..graphs.host import HostGraph
from ..ops.contraction import contract_clustering
from .dist_contraction import dist_contract_clustering
from ..ops.segments import MAX_FUSED_EDGE_SLOTS
from ..utils import timer
from ..utils.logger import log
from ..utils.platform import configure_compile_cache
from .dist_context import (
    DistContext,
    create_dist_clusterer,
    create_dist_context_by_preset_name,
    create_dist_refiner,
)
from .dist_graph import (
    DistGraph,
    dist_graph_from_compressed,
    dist_graph_from_host,
)
from .dist_metrics import dist_edge_cut
from .mesh import make_mesh


class dKaMinPar:
    """Distributed partitioner with the dKaMinPar builder surface
    (include/kaminpar-dist/dkaminpar.h:516+)."""

    def __init__(
        self,
        ctx: Union[DistContext, Context, str, None] = None,
        mesh: Optional[Mesh] = None,
        n_devices: Optional[int] = None,
    ):
        # before the first compile of any run this instance starts
        configure_compile_cache()
        if ctx is None:
            ctx = create_dist_context_by_preset_name("default")
        elif isinstance(ctx, str):
            ctx = create_dist_context_by_preset_name(ctx)
        elif isinstance(ctx, Context):  # shm context: wrap (legacy surface)
            ctx = DistContext(shm=ctx)
        self.ctx = ctx
        self.mesh = mesh if mesh is not None else make_mesh(n_devices)
        self._graph: Optional[HostGraph] = None
        # (source graph, decoded HostGraph) — keyed on the source object
        self._plain_cache: Optional[Tuple[object, HostGraph]] = None
        self._fine_dg: Optional[DistGraph] = None
        # set by _replicated_phase when mesh-subgroup replication fires
        self._replication_info: Optional[dict] = None
        # the live coarsening hierarchy (_DistLevel list) — held on the
        # instance so the memory governor's spiller hook can drop cold
        # per-level DistGraphs at the barriers (rung >= 2)
        self._levels: Optional[List["_DistLevel"]] = None
        # per-rank shard fingerprints of the input's 1D sharding plan
        # (dist_graph.shard_fingerprints), stamped into every dist
        # checkpoint barrier's manifest meta
        self._shard_fps: List[str] = []

    def set_graph(self, graph) -> "dKaMinPar":
        """Accepts a HostGraph or a CompressedHostGraph.  A compressed
        graph is KEPT compressed (the DistributedCompressedGraph analog,
        kaminpar-dist/datastructures/distributed_compressed_graph.h):
        the finest-level device ingestion streams one decoded node-range
        shard at a time (dist_graph_from_compressed), and the plain fine
        CSR materializes lazily only if a host-side consumer demands it
        — in the terapart regime (kway mode, graph above the
        single-device contraction budget, singleton post-passes not
        firing) it never does."""
        self._graph = graph
        self._plain_cache = None
        self._fine_dg = None
        return self

    def _is_compressed(self, g) -> bool:
        from ..graphs.compressed import CompressedHostGraph

        return isinstance(g, CompressedHostGraph)

    def _plain(self, g) -> HostGraph:
        """Materialize a possibly-compressed fine graph (cached, keyed
        on the source object so a different graph can never be handed
        someone else's decode)."""
        if not self._is_compressed(g):
            return g
        if self._plain_cache is None or self._plain_cache[0] is not g:
            self._plain_cache = (g, g.decode())
        return self._plain_cache[1]

    def set_output_level(self, level) -> "dKaMinPar":
        """Instance-scoped output level (dkaminpar.h set_output_level
        analog): applied to the process-global logger only while
        compute_partition runs."""
        from ..utils.logger import OutputLevel

        self._output_level = OutputLevel(level)
        return self

    def copy_graph(self, vtxdist, xadj, adjncy, vwgt=None, adjwgt=None):
        """ParMETIS-style ingestion (dkaminpar.cc:400-448).  vtxdist is
        accepted for API parity; the host assembles the global graph."""
        self._graph = HostGraph(
            xadj=np.asarray(xadj),
            adjncy=np.asarray(adjncy, dtype=np.int32),
            node_weights=None if vwgt is None else np.asarray(vwgt),
            edge_weights=None if adjwgt is None else np.asarray(adjwgt),
        )
        self._plain_cache = None
        self._fine_dg = None
        return self

    def compute_partition(
        self,
        k: Optional[int] = None,
        epsilon: Optional[float] = None,
        seed: Optional[int] = None,
    ) -> np.ndarray:
        if self._graph is None:
            raise RuntimeError("no graph set")
        graph = self._graph
        ctx = self.ctx
        if seed is not None:
            ctx.seed = int(seed)
        ctx.partition.setup(graph, k=k, epsilon=epsilon)
        k = ctx.partition.k

        from .. import telemetry
        from ..utils.logger import output_level, set_output_level

        import time as _time

        t_run0 = _time.perf_counter()
        owns_stream = timer.GLOBAL_TIMER.idle()
        if owns_stream:
            from .mesh import reset_comm_log

            # per-run observability: without these resets, a second
            # compute in the same process reports the first run's traced
            # comm rows and doubled timer scopes attributed to one run's
            # seed/k/result — the report must misattribute nothing, even
            # if cache-hit runs then show an empty comm table (the
            # documented COMM_CAVEAT tradeoff)
            reset_comm_log()
            timer.GLOBAL_TIMER.reset()
            telemetry.reset()
            telemetry.annotate(
                seed=int(ctx.seed),
                k=int(k),
                epsilon=float(ctx.partition.epsilon),
                mode=self.ctx.mode.value,
                devices=int(self.mesh.devices.size),
                graph={"n": int(graph.n), "m": int(graph.m)},
            )

        # preemption safety (kaminpar.py twin): the stream-owning run may
        # arm a deadline and a checkpoint manager; stage ids below are
        # derived from loop indices every rank computes identically
        # (barrier-consistent), and the manager lets only rank 0 write.
        from ..resilience import checkpoint as ckpt_mod
        from ..resilience import deadline as deadline_mod

        mgr = None
        res_ctx = self.ctx.shm.resilience
        from ..resilience import agreement as agreement_mod
        from ..resilience import memory as memory_mod

        if owns_stream:
            # same arm-and-maybe-resume policy as the shm facade
            # (checkpoint.create_manager / deadline.begin_run keep the
            # two from drifting apart)
            ckpt_mod.deactivate()
            deadline_mod.begin_run(
                res_ctx.time_budget or None, res_ctx.budget_grace,
                getattr(res_ctx, "hard_deadline_factor", None),
            )
            mgr = ckpt_mod.create_manager(res_ctx, graph, self.ctx)
            if mgr is not None:
                ckpt_mod.activate(mgr)
            # the per-rank shard fingerprints of the 1D sharding plan:
            # stamped into every dist barrier's manifest meta, and the
            # key that detects a resume under a DIFFERENT device count
            # below (docs/robustness.md, dist resilience contract)
            from .dist_graph import shard_fingerprints, shard_sizes

            devices = max(1, int(self.mesh.devices.size))
            self._shard_fps = shard_fingerprints(graph, devices)
            if mgr is not None:
                pending = mgr.pending_resume()
                if pending is not None and pending.get("scheme") == "dist":
                    recorded = pending.get("meta", {}).get("shards")
                    if (
                        recorded is not None
                        and list(recorded) != list(self._shard_fps)
                    ):
                        # shard state (cmaps, per-level layouts) from a
                        # different sharding plan cannot be restored
                        # without risking a wrong answer: logged clean
                        # restart, never a silent mis-resume
                        mgr.drop_resume(
                            "dist shard fingerprints changed (checkpoint "
                            f"has {len(list(recorded))} shard(s), current "
                            f"mesh has {len(self._shard_fps)}) — device "
                            "count or input sharding differs"
                        )
            # memory governor (resilience/memory.py): the budget
            # (KAMINPAR_TPU_HBM_BYTES / --memory-budget) is PER-DEVICE
            # and dist_graph shards the node/edge arrays across the
            # mesh, so price the ACTUAL max padded shard from the
            # sharding plan — ceil(n/D)/ceil(m/D) undercounts the
            # heaviest rank of a skewed edge distribution, and pricing
            # the whole graph refuses multi-chip runs that fit after
            # sharding
            n_loc, m_loc, _ = shard_sizes(
                np.asarray(graph.xadj, dtype=np.int64), devices
            )
            memory_mod.begin_run(
                graph, self.ctx, price_shape=(n_loc, m_loc)
            )
            memory_mod.register_spiller(self)
            memory_mod.preflight(n_loc, m_loc, k, where="dist")
            # divergence sentinels (resilience/agreement.py): every dist
            # barrier audits [stage, rung, run fingerprint] across the
            # fleet — silent rank divergence becomes a structured
            # RankDivergence with a per-rank dump
            agreement_mod.arm(
                "dist",
                ckpt_mod.graph_fingerprint(graph),
                ckpt_mod.ctx_fingerprint(self.ctx),
                self._shard_fps,
            )

        prior_level = output_level()
        try:
            set_output_level(
                getattr(self, "_output_level", prior_level)
            )
            with timer.scoped_timer("dist-partitioning"):
                # a run preempted after its final barrier resumes
                # instantly from the `result` snapshot; mid-pipeline
                # dist stages re-enter at the recorded barrier via the
                # dist-scheme resume inside _partition_recorded (full-
                # hierarchy dist resume, docs/robustness.md).  The core
                # runs under the cross-rank agreed OOM recovery ladder:
                # a DeviceOOM on any rank unwinds every rank to the
                # same rung (tight pads -> host-spilled shard
                # hierarchy -> host-only) instead of deadlocking the
                # survivors inside shard_map collectives.
                resumed = (
                    mgr.take_result_resume() if mgr is not None else None
                )
                if resumed is not None and resumed.shape == (graph.n,):
                    partition = resumed
                else:
                    partition = memory_mod.run_dist_ladder(
                        lambda: self._partition(graph, k),
                        graph, self.ctx, self,
                    )

            # strict-balance output gate (resilience/gate.py): the dist
            # result now passes the same end-of-pipeline validation +
            # greedy repair as the shm facade's (compressed inputs are
            # chunk-stream recomputed, never decoded whole)
            from ..resilience import gate as output_gate

            if output_gate.gate_enabled() and res_ctx.output_gate:
                # already host-side: the pipeline returns numpy
                partition = np.asarray(partition, dtype=np.int32)
                with timer.scoped_timer("output-gate"):
                    partition, gate_verdict = output_gate.check_and_repair(
                        graph, partition, ctx.partition,
                        repair=res_ctx.repair,
                    )
                if owns_stream:
                    telemetry.annotate(output_gate=gate_verdict)

            if self._is_compressed(graph) and self._fine_dg is not None:
                # still-compressed input: cut from the finest-level
                # sharded graph (no CSR materialization), imbalance from
                # node weights alone
                full = np.zeros(self._fine_dg.n_pad, dtype=np.int32)
                full[: graph.n] = partition
                # `collective` degradation site: the sharded cut
                # reduction can time out / OOM on a sick link — degrade
                # to the host-side cut (decoding if needed) rather than
                # losing the whole run at the metrics step
                from ..resilience import with_fallback

                fine_dg = self._fine_dg
                cut = with_fallback(
                    lambda: dist_edge_cut_of(fine_dg, jnp.asarray(full)),
                    lambda exc: self._host_cut(
                        self._plain(graph), partition
                    ),
                    site="collective",
                    where="dist-result-cut",
                )
                import math as pymath

                nw = graph.node_weight_array()
                bw = np.zeros(k, dtype=np.int64)
                np.add.at(bw, partition, nw)
                # same definition as host_partition_metrics (ceil'd
                # perfect weight) so the two RESULT paths cannot drift
                perfect = max(1, pymath.ceil(int(nw.sum()) / k))
                imbalance = float(bw.max() / perfect - 1.0)
                feasible = bool((bw <= ctx.partition.max_block_weights).all())
                # the finest sharded arrays are only retained for this
                # metrics call — release the device memory
                self._fine_dg = None
            else:
                from ..graphs.host import host_partition_metrics

                res = host_partition_metrics(self._plain(graph), partition, k)
                cut, imbalance = res["cut"], res["imbalance"]
                feasible = bool(
                    (res["block_weights"] <= ctx.partition.max_block_weights)
                    .all()
                )
            if owns_stream:  # nested runs don't own the stream
                telemetry.annotate(
                    result={
                        "cut": int(cut),
                        "imbalance": float(imbalance),
                        "feasible": feasible,
                    }
                )
            if owns_stream:
                # per-rank memory rollup (perf.memory.ranks): collective
                # — every process gathers its live-HBM figure, so the
                # report shows residency skew between ranks the same way
                # the aggregated timers show wall skew.  perf.enabled()
                # is env+telemetry state, identical on all ranks.
                from ..telemetry import perf as perf_mod

                if perf_mod.enabled():
                    telemetry.annotate(
                        perf_ranks=perf_mod.rank_memory_rollup()
                    )
                # per-rank quality rollup (quality.ranks): collective —
                # every rank contributes its attribution headline, so
                # the dist report shows where cut responsibility sits
                # per rank next to the residency/wall skew
                from ..telemetry import quality as quality_mod

                if quality_mod.enabled():
                    telemetry.annotate(
                        quality_ranks=quality_mod.rank_rollup()
                    )
                if mgr is not None and mgr.enabled:
                    final_part = partition
                    ckpt_mod.barrier(
                        "result", scheme="dist-facade",
                        payload=lambda: {"state": {
                            "partition": np.asarray(
                                final_part, dtype=np.int32
                            ),
                        }},
                    )
                if deadline_mod.triggered():
                    telemetry.annotate(anytime=deadline_mod.state())
                if mgr is not None:
                    telemetry.annotate(checkpoint=mgr.summary())
                mem_summary = memory_mod.summary()
                if mem_summary.get("enabled"):
                    telemetry.annotate(memory_budget=mem_summary)
                # dist resilience audit trail (schema v8): sentinel
                # counters + the shard-fingerprint vector + the agreed
                # ladder rung + what (if anything) was resumed
                dist_sect = agreement_mod.section()
                if dist_sect.get("enabled"):
                    dist_sect["shard_fingerprints"] = list(self._shard_fps)
                    dist_sect["ladder"] = {
                        "agreed": True,
                        "rung": int(mem_summary.get("rung", 0) or 0),
                    }
                    if mgr is not None and mgr.resumed_from() is not None:
                        dist_sect["resumed_from"] = mgr.resumed_from()
                    telemetry.annotate(dist_resilience=dist_sect)
                ckpt_mod.deactivate()
            log(
                f"RESULT cut={cut} imbalance={imbalance:.6f} "
                f"k={k} devices={self.mesh.devices.size}"
            )
            # request tracing (telemetry/tracing.py): when a serving
            # request drove this compute, attach a rank-annotated span
            # to its trace — the agreement rollup's rank model
            # (agreement.py rank() = process_index, 0 without a live
            # multi-process backend) so multi-rank timelines stay
            # attributable per process
            from ..telemetry import tracing
            from ..utils.platform import process_index

            tid = tracing.current()
            if tid:
                tracing.span(
                    tid, "dist-compute", start=t_run0,
                    duration_s=_time.perf_counter() - t_run0,
                    origin="dist", rank=int(process_index()),
                    devices=int(self.mesh.devices.size), k=int(k),
                )
        finally:
            set_output_level(prior_level)
            if owns_stream:
                agreement_mod.disarm()
            self._levels = None
        return partition

    # -- multilevel driver ------------------------------------------------

    def _partition(self, graph: HostGraph, k: int) -> np.ndarray:
        from ..telemetry import quality as quality_mod

        # quality observatory (telemetry/quality.py): the dist driver
        # records its own hierarchy; nested shm IP runs open (and close)
        # their own scopes below this one without corrupting it
        qh = quality_mod.begin("dist")
        try:
            return self._partition_recorded(graph, k, qh)
        finally:
            quality_mod.end(qh)

    def _quality_cut(self, dg, n: int, partition) -> Optional[int]:
        """Sharded cut of a host partition, only when the quality layer
        is live (collective — quality.enabled() is env+telemetry state,
        identical on all ranks, so every rank calls or none does)."""
        from ..telemetry import quality as quality_mod

        if not quality_mod.enabled():
            return None
        full = np.zeros(dg.n_pad, dtype=np.int32)
        full[: int(n)] = partition
        return dist_edge_cut_of(dg, jnp.asarray(full))

    def _partition_recorded(
        self, graph: HostGraph, k: int, qh
    ) -> np.ndarray:
        from ..resilience import checkpoint as ckpt
        from ..telemetry import quality as quality_mod

        ctx = self.ctx
        c_ctx = ctx.coarsening
        total_node_weight = ctx.partition.total_node_weight
        clusterer = create_dist_clusterer(ctx)
        refiner = create_dist_refiner(ctx)

        from ..context import PartitioningMode

        deep = self.ctx.mode == PartitioningMode.DEEP

        # --- full-hierarchy dist resume: rebuild the recorded level
        # stack (coarse host CSRs + cmaps by reference; the sharded
        # DistGraphs are deterministic caches rebuilt on demand) and
        # re-enter at the recorded dist barrier — no completed level
        # re-runs (docs/robustness.md, dist resilience contract)
        resume = ckpt.take_resume("dist")
        r_stage: Optional[str] = None
        r_level: Optional[int] = None
        levels: List[_DistLevel] = []
        current = graph
        partition: Optional[np.ndarray] = None
        spans = None
        current_k: Optional[int] = None
        num_levels_meta: Optional[int] = None
        if resume is not None:
            r_stage = resume["stage"]
            r_level = resume.get("level")
            meta = resume.get("meta", {})
            levels, current = self._restore_dist_levels(
                graph, resume["arrays"]
            )
            state = resume["arrays"].get("state")
            if (
                r_stage in ("dist-initial", "dist-uncoarsen")
                and state is not None
                and "partition" in state
                and "spans" in state  # pre-v12 dist states lack spans:
                # fall through to the level-only (or clean) restart
            ):
                partition = np.asarray(state["partition"], dtype=np.int32)
                spans = [
                    (int(f), int(c))
                    for f, c in np.asarray(state["spans"]).tolist()
                ]
                current_k = int(meta.get("current_k", len(spans)))
                num_levels_meta = meta.get("num_levels")
            else:
                # only hierarchy levels were recorded: re-enter the
                # coarsening loop where it left off
                r_stage = "dist-coarsen"
            # replay the cluster maps into the quality recorder so the
            # final attribution composes over the FULL hierarchy
            for i, lvl in enumerate(levels):
                quality_mod.note_cmap(
                    level=i + 1, cmap=lvl.cmap, fine_n=lvl.fine_host.n
                )
            from .. import telemetry

            telemetry.event(
                "resume", scheme="dist", stage=r_stage, level=r_level,
                levels_restored=len(levels),
            )
            log(
                f"resumed dist pipeline at {r_stage}"
                f"{'' if r_level is None else ':' + str(r_level)} "
                f"({len(levels)} hierarchy level(s) restored)"
            )
        self._levels = levels

        # coarsening (deep_multilevel.cc:75-118 analog); skipped
        # entirely when the resume restored a partition already
        skip_to_uncoarsen = partition is not None
        threshold = max(2 * c_ctx.contraction_limit, k)
        if not skip_to_uncoarsen:
            with timer.scoped_timer("dist-coarsening"):
                while current.n > threshold:
                    if deep and self._replication_factor(current.n) > 1:
                        # the graph is too small to keep every device
                        # busy: hand over to the mesh-subgroup
                        # replication phase (deep_multilevel.cc:79-153
                        # analog) below
                        break
                    if self._is_compressed(current):
                        # still-compressed fine level: stream shards from
                        # the compressed rows (bitwise-identical result)
                        dg = dist_graph_from_compressed(current, self.mesh)
                        self._fine_dg = dg
                    else:
                        dg = dist_graph_from_host(current, self.mesh)
                    mcw = max(
                        1,
                        c_ctx.max_cluster_weight(
                            current.n, total_node_weight, ctx.partition
                        ),
                    )
                    lvl_seed = (
                        ctx.seed * 7919 + len(levels) * 31337
                    ) & 0x7FFFFFFF
                    from .mesh import comm_phase

                    with comm_phase(f"coarsening-L{len(levels)}"):
                        labels = clusterer(
                            dg, min(mcw, WMAX), jnp.int32(lvl_seed)
                        )
                    # singleton post-passes (two-hop + isolated packing)
                    # — the reference runs them wherever LP clusters
                    # (label_propagation.h:872-1191); without them
                    # low-degree graphs under-coarsen on the mesh
                    from .dist_lp import dist_singleton_postpasses

                    fine = current  # may be compressed; _plain caches
                    # the device labels go in raw: the post-pass owns its
                    # own pull (the staged host boundary), so the span
                    # never carries a caller-side np.asarray
                    labels = dist_singleton_postpasses(
                        current, labels, min(mcw, WMAX),
                        materialize=lambda: self._plain(fine),
                    )
                    contracted = self._contract_level(current, dg, labels)
                    if contracted is None:  # converged
                        break
                    coarse, cmap = contracted
                    fine_n = int(current.n)
                    levels.append(_DistLevel(current, cmap, dg, self.mesh))
                    quality_mod.note_cmap(
                        level=len(levels), cmap=cmap, fine_n=fine_n
                    )
                    if quality_mod.enabled():
                        # coarsening-quality stats, host-side; compressed
                        # fine levels skip the edge-weight sum (no decode)
                        quality_mod.note_contraction_host(
                            level=len(levels), coarse_host=coarse,
                            cmap=cmap, fine_n=fine_n,
                            max_cluster_weight=mcw,
                            total_node_weight=int(total_node_weight),
                            fine_edge_weight=(
                                None if self._is_compressed(current)
                                else int(current.edge_weight_array().sum())
                            ),
                        )
                    current = coarse
                    lvl_no = len(levels)
                    if not ckpt.barrier(
                        "dist-coarsen", level=lvl_no, scheme="dist",
                        # the level snapshot: coarse host CSR + cmap —
                        # deferred (disabled runs build nothing), prior
                        # levels carried forward by reference
                        payload=lambda c=coarse, cm=cmap, fn=fine_n,
                        no=lvl_no: _dist_level_payload(no, c, cm, fn),
                        keep=[f"dist-level-{j}" for j in range(1, lvl_no)],
                        meta=self._dist_meta(num_levels=lvl_no),
                        agree=True,  # next level clusters collectively
                    ):
                        break  # deadline wind-down: stop deepening

        # mesh-subgroup replication (deep_multilevel.cc:79-153 +
        # replicator.cc analog): the graph is too small for the whole
        # mesh, so G replicas coarsen + IP + refine independently on
        # D/G-device subgroups (one block-diagonal union graph — see
        # parallel/replication.py) and the best replica's partition
        # continues into the main uncoarsening below
        replicated = False
        if (
            not skip_to_uncoarsen
            and deep
            and current.n > threshold
            and self._replication_factor(current.n) > 1
        ):
            with timer.scoped_timer("dist-replicated-coarsening"):
                # a compressed input can reach this point un-decoded (the
                # loop breaks before the streaming branch); the union
                # builder needs plain CSR rows
                partition, ip_k = self._replicated_phase(
                    self._plain(current), k, clusterer, threshold
                )
            replicated = True

        # DEEP mode partitions the coarsest at a reduced k' and doubles k
        # on the mesh during uncoarsening; KWAY partitions at full k.
        # With no dist levels there is nothing to double over — the shm
        # IP result IS the final partition, so it must run at full k.
        if skip_to_uncoarsen:
            ip_k = int(current_k)  # the resumed partition's k
        elif replicated:
            pass
        elif deep and levels:
            from ..partitioning.deep import compute_k_for_n

            ip_k = max(2, min(k, compute_k_for_n(current.n, self.ctx.shm)))
        else:
            ip_k = k
        if spans is None:
            spans = self._initial_spans(ip_k, k)

        # initial partitioning: shm pipeline on the coarsest graph.  The
        # reference replicates the coarsest graph onto every PE, runs shm
        # KaMinPar per PE with that PE's seed, and keeps the best cut
        # (replicate_graph_everywhere + distribute_best_partition,
        # kaminpar-dist/partitioning/deep_multilevel.cc:125-176).  When
        # the mesh-subgroup replication phase ran, each replica already
        # carried its own IP and the best partition was selected there;
        # otherwise one host plays all PEs with independent seeded runs.
        best_cut = None
        if not replicated and not skip_to_uncoarsen:
            with timer.scoped_timer("dist-initial-partitioning"):
                num_replicas = max(1, min(self.mesh.devices.size, 4))
                partition = None
                for r in range(num_replicas):
                    cand = self._initial_partition(
                        self._plain(current), ip_k, k, spans,
                        (self.ctx.seed * 31 + r * 7907) & 0x7FFFFFFF,
                    )
                    cut = self._host_cut(self._plain(current), cand)
                    if best_cut is None or cut < best_cut:
                        partition, best_cut = cand, cut
        if not skip_to_uncoarsen:
            part_ip, spans_ip = partition, spans
            ckpt.barrier(
                "dist-initial", level=len(levels), scheme="dist",
                payload=lambda: _dist_state_payload(part_ip, spans_ip),
                keep=[
                    f"dist-level-{j}" for j in range(1, len(levels) + 1)
                ],
                meta=self._dist_meta(
                    num_levels=len(levels), current_k=int(ip_k),
                ),
            )
        # quality: the coarsest level's cut — dist runs no coarsest-level
        # refinement, so projected == refined there (both recorded so
        # the level still gets an attribution row)
        coarsest_cut = (
            (self._replication_info or {}).get("cut") if replicated
            else best_cut
        )
        if coarsest_cut is not None:
            quality_mod.note_projected(
                len(levels), cut=coarsest_cut, k=ip_k
            )
            quality_mod.note_refined(
                len(levels), cut=coarsest_cut, k=ip_k,
                spans=spans, input_k=k,
            )

        # uncoarsening + distributed refinement (deep_multilevel.cc:181+):
        # project up, refine at the current k, and in DEEP mode extend the
        # partition on the mesh while the level's size supports more
        # blocks (the extend_partition lineage, helper.cc:220)
        current_k = ip_k
        # num_levels is the FULL hierarchy depth — after a resume whose
        # keep-list already pruned consumed levels, len(levels) < depth,
        # and the per-level seeds below must match the uninterrupted
        # run's (cut-identical resume)
        num_levels = (
            int(num_levels_meta) if num_levels_meta else len(levels)
        )
        start_level = (
            int(r_level) if r_stage == "dist-uncoarsen" and r_level
            is not None else len(levels)
        )
        with timer.scoped_timer("dist-uncoarsening"):
            for level in range(start_level - 1, -1, -1):
                lvl = levels[level]
                dg = lvl.dg()  # rebuilt on demand when spilled/resumed
                fine_host = lvl.fine_host
                partition = partition[lvl.cmap]  # project up
                level_idx = num_levels - 1 - level
                cut = self._quality_cut(dg, fine_host.n, partition)
                if cut is not None:
                    quality_mod.note_projected(level, cut=cut, k=current_k)
                seed = (self.ctx.seed * 92821 + level_idx) & 0x7FFFFFFF
                partition = self._refine_dist(
                    refiner, dg, fine_host, partition, current_k, spans,
                    seed, level,
                )
                if deep:
                    from ..partitioning.deep import compute_k_for_n

                    target_k = min(
                        k, compute_k_for_n(fine_host.n, self.ctx.shm)
                    )
                    while current_k < target_k:
                        partition, spans, current_k = self._extend_on_mesh(
                            fine_host, partition, spans
                        )
                        partition = self._refine_dist(
                            refiner, dg, fine_host, partition, current_k,
                            spans, seed ^ (0x9E37 + current_k), level,
                        )
                cut = self._quality_cut(dg, fine_host.n, partition)
                if cut is not None:
                    quality_mod.note_refined(
                        level, cut=cut, k=current_k,
                        spans=spans, input_k=k,
                    )
                part_now, spans_now, k_now = partition, spans, current_k
                ckpt.barrier(
                    "dist-uncoarsen", level=level, scheme="dist",
                    payload=lambda: _dist_state_payload(part_now, spans_now),
                    # levels 0..level-1 are still pending; their fine
                    # CSRs/cmaps live in snapshots 1..level
                    keep=[f"dist-level-{j}" for j in range(1, level + 1)],
                    meta=self._dist_meta(
                        num_levels=num_levels, current_k=int(k_now),
                    ),
                )
        # final extensions to k (finest level).  `skip_to_uncoarsen`
        # joins the condition: a resume at dist-uncoarsen:0 with
        # current_k < k has already PRUNED every level snapshot (the
        # keep list at the finest barrier is empty), so `levels` is
        # empty — but the restored partition lives on the input graph
        # and must extend on the mesh exactly like the uninterrupted
        # run would; the shm fallback below would discard it
        if (
            deep
            and (levels or replicated or skip_to_uncoarsen)
            and current_k < k
        ):
            if levels:
                lvl0 = levels[0]
                dg, fine_host = lvl0.dg(), lvl0.fine_host
            else:
                # replication fired at the input level (or a finest-
                # barrier resume restored an all-levels-pruned state):
                # no dist levels exist, but the finest-level graph
                # (= the input) still extends on the mesh
                fine_host = self._plain(current)
                dg = dist_graph_from_host(fine_host, self.mesh)
            while current_k < k:
                partition, spans, current_k = self._extend_on_mesh(
                    fine_host, partition, spans
                )
                partition = self._refine_dist(
                    refiner, dg, fine_host, partition, current_k, spans,
                    (self.ctx.seed * 48947 + current_k) & 0x7FFFFFFF, 0,
                )
        elif current_k < k:
            # no dist levels (tiny graph): the shm IP already ran at ip_k;
            # fall back to a full-k shm partition
            from ..kaminpar import KaMinPar

            shm = KaMinPar(self.ctx.shm.copy())
            partition = shm.set_graph(self._plain(graph)).compute_partition(
                k=k, epsilon=self.ctx.partition.epsilon, seed=self.ctx.seed
            )
            current_k = k
        # quality: coarsening floors from the final partition.  A
        # still-compressed input is not decoded just for the floors —
        # the attribution keeps the recorded cut rows only (documented
        # caveat, docs/observability.md).
        if not self._is_compressed(graph):
            quality_mod.finalize_host(qh, graph, partition)
        elif self._plain_cache is not None and self._plain_cache[0] is graph:
            quality_mod.finalize_host(qh, self._plain_cache[1], partition)
        return partition

    # -- deep-mode helpers -------------------------------------------------

    def _initial_partition(self, host, ip_k, k, spans, seed) -> np.ndarray:
        """Coarsest-graph initial partitioner dispatch (the
        create_initial_partitioner seam, kaminpar-dist/factories.cc:72-88:
        KAMINPAR / MTKAHYPAR / RANDOM)."""
        from .dist_context import DistInitialPartitioningAlgorithm as Alg

        algo = getattr(
            self.ctx, "initial_partitioning", Alg.KAMINPAR
        )
        if algo == Alg.RANDOM:
            # random_initial_partitioner.cc: uniform block per node; any
            # imbalance is left to the balancers/refiners downstream
            rng = np.random.RandomState(seed & 0x7FFFFFFF)
            return rng.randint(0, ip_k, host.n).astype(np.int32)
        if algo == Alg.MTKAHYPAR:
            # mtkahypar_initial_partitioner.cc — gated on the external
            # package exactly like the refinement adapter
            from ..refinement.mtkahypar import (
                mtkahypar_available,
                mtkahypar_refine_host,
            )

            if not mtkahypar_available():
                raise RuntimeError(
                    "initial_partitioning=mtkahypar requires the external "
                    "'mtkahypar' package (the analog of building the "
                    "reference with KAMINPAR_BUILD_WITH_MTKAHYPAR)"
                )
            rng = np.random.RandomState(seed & 0x7FFFFFFF)
            start = rng.randint(0, ip_k, host.n).astype(np.int32)
            return mtkahypar_refine_host(
                host, start, ip_k,
                epsilon=self.ctx.partition.epsilon, seed=seed,
            ).astype(np.int32)
        return self._shm_ip(host, ip_k, k, spans, seed)

    def _shm_ip(self, host, ip_k, k, spans, seed) -> np.ndarray:
        """One seeded shm-KaMinPar run on a coarsest(-replica) graph with
        span-aware caps (when ip_k does not divide k the current blocks
        carry UNEQUAL final-block counts, and the IP must balance to
        those targets or the first refinement inherits systematic
        overloads).  Quiet, without leaking the global logger level."""
        from ..kaminpar import KaMinPar
        from ..utils.logger import OutputLevel, output_level, set_output_level

        outer_level = output_level()
        try:
            shm = KaMinPar(self.ctx.shm.copy())
            shm.set_output_level(OutputLevel.QUIET)
            shm.set_graph(host)
            p_ = self.ctx.partition
            ip_caps = np.array(
                [
                    p_.total_max_block_weights(first, first + count)
                    for first, count in spans
                ],
                dtype=np.int64,
            )
            return shm.compute_partition(
                k=ip_k,
                epsilon=self.ctx.partition.epsilon,
                max_block_weights=(None if ip_k == k else ip_caps),
                seed=seed,
            )
        finally:
            set_output_level(outer_level)

    def _replication_factor(self, n: int) -> int:
        from .replication import choose_replication_factor

        return choose_replication_factor(
            n,
            int(self.mesh.devices.size),
            int(getattr(self.ctx, "replication_min_nodes_per_device", 0)),
        )

    # host-boundary contract: contraction hands the coarse graph and its
    # cmap back to the host to re-shard the next level — the pulls ARE
    # the phase the dist-coarsening span times
    # tpulint: disable=R1
    def _contract_level(self, current: HostGraph, dg, labels):
        """Contract one coarsening level; returns (coarse, cmap) or None
        when the clustering converged (coarse nearly as big as fine)."""
        c_ctx = self.ctx.coarsening
        if current.m <= MAX_FUSED_EDGE_SLOTS:
            # contraction on DEVICE (sort-based dedup kernel; see module
            # docstring): only the coarse CSR is pulled back, to re-shard
            # it for the next level's 1D node distribution (the
            # reference's migrate step, global_cluster_contraction.cc:1100+)
            fine_dev = device_graph_from_host(self._plain(current))
            lab_dev = jnp.asarray(labels)[: fine_dev.n_pad]
            if lab_dev.shape[0] < fine_dev.n_pad:
                lab_dev = jnp.concatenate([
                    lab_dev,
                    jnp.arange(lab_dev.shape[0], fine_dev.n_pad,
                               dtype=jnp.int32),
                ])
            coarse_dev, c_n, _c_m = contract_clustering(fine_dev, lab_dev)
            if c_n >= (1.0 - c_ctx.convergence_threshold) * current.n:
                return None
            cmap = np.asarray(coarse_dev.cmap)[: current.n]
            coarse = host_graph_from_device(coarse_dev.graph)
        else:
            # beyond the single-device budget: SHARDED contraction
            # (per-shard dedup + coarse-edge migrate all_to_all,
            # parallel/dist_contraction.py — the
            # global_cluster_contraction.cc:1100+ analog); the fine edge
            # list never leaves its shards
            coarse, cmap = dist_contract_clustering(
                dg, current.n, current.node_weight_array(),
                np.asarray(labels),
            )
            if coarse.n >= (1.0 - c_ctx.convergence_threshold) * current.n:
                return None
        return coarse, cmap

    # host-boundary contract: the replica phase selects + pulls the best
    # replica's partition to host for the main uncoarsening — the
    # dist-replicated-coarsening span times this hybrid phase
    # tpulint: disable=R1
    def _replicated_phase(
        self, split_host: HostGraph, k: int, clusterer, threshold: int,
    ):
        """Coarsen G replicas of `split_host` as one block-diagonal union
        over the mesh, IP each replica, refine the replica hierarchies in
        lockstep union launches, and return the best replica's partition
        at the split level (deep_multilevel.cc:79-153 +
        replicator.cc:26-34; see parallel/replication.py for why a union
        graph realizes PE-subgroup splitting on a device mesh).

        Returns (partition i32[split_host.n] in [0, ip_k), ip_k)."""
        from ..partitioning.deep import compute_k_for_n
        from .dist_lp import dist_singleton_postpasses
        from .replication import (
            best_replica_partition,
            replica_bounds_after_contraction,
            slice_replica,
            union_graph,
        )

        ctx = self.ctx
        c_ctx = ctx.coarsening
        n_split = split_host.n
        G = self._replication_factor(n_split)
        # the partition re-enters the main uncoarsening at the split
        # level, so it must carry the k that level supports — each
        # replica's internal shm deep pipeline builds up to ip_k exactly
        # like a reference PE subgroup does
        ip_k = max(2, min(k, compute_k_for_n(n_split, ctx.shm)))
        spans = self._initial_spans(ip_k, k)
        union = union_graph(split_host, G)
        bounds = [g * n_split for g in range(G + 1)]
        self._replication_info = {
            "G": G, "split_n": n_split, "ip_k": ip_k,
        }

        # --- coarsen the union until every replica reaches the IP size;
        # replicas diverge through id-keyed hashing (the id offset is the
        # per-replica seed)
        u_levels = []
        current, cur_bounds = union, bounds
        while max(
            cur_bounds[g + 1] - cur_bounds[g] for g in range(G)
        ) > threshold:
            dg = dist_graph_from_host(current, self.mesh)
            n_rep = max(
                cur_bounds[g + 1] - cur_bounds[g] for g in range(G)
            )
            # per-REPLICA size keeps the cluster-weight cap identical to
            # the unreplicated semantics (clusters never span replicas)
            mcw = max(
                1,
                c_ctx.max_cluster_weight(
                    n_rep, ctx.partition.total_node_weight, ctx.partition
                ),
            )
            lvl_seed = (
                ctx.seed * 7919 + (9601 + len(u_levels)) * 31337
            ) & 0x7FFFFFFF
            from .mesh import comm_phase

            with comm_phase(f"replicated-coarsening-L{len(u_levels)}"):
                labels = np.array(
                    clusterer(dg, min(mcw, WMAX), jnp.int32(lvl_seed))
                )
            # singleton post-passes must not merge across replicas (the
            # isolated-node bins are global) — run them per component
            for g in range(G):
                lo, hi = cur_bounds[g], cur_bounds[g + 1]
                sub = slice_replica(current, lo, hi)
                sub_lab = labels[lo:hi] - lo
                labels[lo:hi] = lo + dist_singleton_postpasses(
                    sub, sub_lab, min(mcw, WMAX)
                )
            contracted = self._contract_level(current, dg, labels)
            if contracted is None:
                break
            coarse, cmap = contracted
            u_levels.append((dg, cmap, current))
            cur_bounds = replica_bounds_after_contraction(cmap, cur_bounds)
            current = coarse

        # --- per-replica IP (each subgroup's seeded shm run).  Always the
        # KAMINPAR algorithm here regardless of ctx.initial_partitioning:
        # the union refinement that follows is positive-gain LP only (see
        # below — balancers could cross replicas), so a balance-ignorant
        # RANDOM start could never be repaired before the best-replica
        # cut comparison, which requires comparably feasible candidates.
        union_part = np.zeros(current.n, dtype=np.int32)
        for g in range(G):
            lo, hi = cur_bounds[g], cur_bounds[g + 1]
            sub = slice_replica(current, lo, hi)
            cand = self._shm_ip(
                sub, ip_k, k, spans,
                (ctx.seed * 31 + g * 7907) & 0x7FFFFFFF,
            )
            union_part[lo:hi] = cand.astype(np.int32) + g * ip_k

        # --- uncoarsen the replica hierarchies in lockstep: one union
        # refinement per level with per-replica block-id ranges and tiled
        # caps, so every subgroup refines its own replica simultaneously.
        # POSITIVE-GAIN LP only: a foreign replica's block always has
        # connection 0 (components are disjoint), so strictly-improving
        # moves can never cross replicas — balancers/Jet could (they
        # accept zero-connection moves for balance) and would corrupt
        # the per-replica block-id ranges
        from ..ops.segments import pad_k_bucket
        from .dist_lp import dist_lp_refine

        base_caps = np.asarray(self._span_caps(spans))
        k_u, union_caps, _ = pad_k_bucket(
            G * ip_k, jnp.asarray(np.tile(base_caps, G))
        )
        for level_idx, (dg, cmap, fine_host) in enumerate(
            reversed(u_levels)
        ):
            union_part = union_part[cmap]
            full = np.zeros(dg.n_pad, dtype=np.int32)
            full[: fine_host.n] = union_part
            seed = (ctx.seed * 50411 + level_idx * 73) & 0x7FFFFFFF
            refined = dist_lp_refine(
                dg, jnp.asarray(full), k_u, union_caps, seed,
                num_iterations=ctx.lp_num_iterations,
            )
            union_part = np.asarray(refined)[: fine_host.n]
        # defensive: every node must still carry a block of ITS replica
        rep_of_node = np.repeat(np.arange(G), n_split)
        if not (
            (union_part >= rep_of_node * ip_k)
            & (union_part < (rep_of_node + 1) * ip_k)
        ).all():
            raise AssertionError(
                "union refinement moved a node across replicas"
            )

        # --- keep the best replica (distribute_best_partition analog) --
        part, g_best, cut = best_replica_partition(
            split_host, union_part, G, ip_k
        )
        self._replication_info.update(
            {"levels": len(u_levels), "best_replica": g_best, "cut": cut}
        )
        from .. import telemetry

        telemetry.event("replicated-coarsening", **self._replication_info)
        log(
            f"replicated coarsening: G={G} replicas x "
            f"{int(self.mesh.devices.size) // G} devices, "
            f"{len(u_levels)} levels, best replica {g_best} cut {cut}"
        )
        return part.astype(np.int32), ip_k

    def _initial_spans(self, current_k: int, final_k: int):
        """Block spans (first final block, count) for the current blocks —
        the shm deep partitioner's bookkeeping (partitioning/deep.py)."""
        from ..partitioning.rb import split_k

        spans: List[Tuple[int, int]] = []

        def rec(first: int, count: int, blocks: int):
            if blocks == 1:
                spans.append((first, count))
                return
            b0 = blocks // 2 + (blocks & 1)
            k0, k1 = split_k(count)
            rec(first, k0, b0)
            rec(first + k0, k1, blocks - b0)

        rec(0, final_k, current_k)
        return spans

    def _span_caps(self, spans) -> jnp.ndarray:
        p = self.ctx.partition
        caps = np.array(
            [
                p.total_max_block_weights(first, first + count)
                for first, count in spans
            ],
            dtype=np.int64,
        )
        return jnp.asarray(np.minimum(caps, WMAX), dtype=WEIGHT_DTYPE)

    # host-boundary contract: distributed refinement returns the refined
    # partition to host per level (the caller projects it up host-side)
    # — the readback is the handoff the dist-uncoarsening span times
    # tpulint: disable=R1
    def _refine_dist(
        self, refiner, dg, fine_host, partition, current_k, spans, seed,
        level,
    ) -> np.ndarray:
        from .mesh import comm_phase
        from ..resilience import deadline as deadline_mod

        if deadline_mod.agreed_stop():
            # anytime wind-down: skip the optional collective refinement
            # round — by the AGREED verdict, so every rank skips or none
            # does (a divergent skip would deadlock the collectives);
            # projection/extension (mandatory for a valid k-way result)
            # still run in the caller
            return partition

        full = np.zeros(dg.n_pad, dtype=np.int32)
        full[: fine_host.n] = partition
        with comm_phase(f"refinement-L{level}-k{current_k}"):
            refined = refiner(
                dg, jnp.asarray(full), current_k, self._span_caps(spans),
                seed, level=level,
            )
        return np.asarray(refined)[: fine_host.n]

    def _extend_on_mesh(self, fine_host: HostGraph, partition, spans):
        """Double k by bipartitioning every multi-span block's induced
        subgraph — the extend_partition lineage (helper.cc:220).  The
        reference extracts block subgraphs onto PE GROUPS and runs shm
        KaMinPar per group (kaminpar-dist/graphutils/subgraph_extractor.cc
        :872, deep_multilevel.cc:181+); on a one-host mesh the group
        parallelism collapses to a loop, so blocks are extracted on the
        host and bipartitioned by the native sequential multilevel
        bipartitioner (native/ip.cpp), after which the caller's
        distributed refinement at the doubled k polishes on the mesh."""
        from ..graphs.host import extract_block_subgraphs
        from ..initial import InitialMultilevelBipartitioner
        from ..partitioning.deep import DeepMultilevelPartitioner
        from ..partitioning.rb import bipartition_max_block_weights, split_k

        fine_host = self._plain(fine_host)  # extraction needs plain rows
        rng = np.random.default_rng(
            (self.ctx.seed * 63018038201 + len(spans)) & 0x7FFFFFFF
        )
        current_k = len(spans)
        ext = extract_block_subgraphs(
            fine_host, partition.astype(np.int64), current_k
        )
        bipartitioner = InitialMultilevelBipartitioner(
            self.ctx.shm.initial_partitioning
        )
        # large blocks route through the shm deep partitioner's device
        # bipartition pipeline, exactly like the shm extension does
        deep_helper = DeepMultilevelPartitioner(self.ctx.shm)
        device_threshold = self.ctx.shm.partitioning.device_bipartition_threshold
        n = fine_host.n
        new_part = np.zeros(n, dtype=np.int32)
        new_spans: List[Tuple[int, int]] = []
        next_id = 0
        for b, (first, count) in enumerate(spans):
            mask = partition == b
            if count <= 1:
                new_part[mask] = next_id
                new_spans.append((first, count))
                next_id += 1
                continue
            sub = ext.subgraphs[b]
            max_w = bipartition_max_block_weights(
                self.ctx.shm, first, count, sub.total_node_weight
            )
            if sub.n >= device_threshold:
                bp = deep_helper._device_bipartition(sub, max_w, rng)
            else:
                bp = bipartitioner.bipartition(sub, max_w, rng)
            k0, k1 = split_k(count)
            new_part[mask] = np.where(
                bp[ext.node_mapping[mask]] == 0, next_id, next_id + 1
            )
            new_spans.append((first, k0))
            new_spans.append((first + k0, k1))
            next_id += 2
        return new_part, new_spans, len(new_spans)

    def _host_cut(self, graph: HostGraph, partition: np.ndarray) -> int:
        src = graph.edge_sources()
        ew = graph.edge_weight_array()
        return int(ew[partition[src] != partition[graph.adjncy]].sum() // 2)

    # -- dist resilience (resilience/{checkpoint,memory,agreement}.py) --

    def _dist_meta(self, num_levels: int,
                   current_k: Optional[int] = None) -> dict:
        """Barrier manifest meta: the per-rank shard-fingerprint vector
        (device-count-change detection on resume), the FULL hierarchy
        depth (per-level seeds must survive keep-list pruning), and the
        current k."""
        meta = {
            "shards": list(self._shard_fps),
            "num_levels": int(num_levels),
        }
        if current_k is not None:
            meta["current_k"] = int(current_k)
        return meta

    def _restore_dist_levels(self, graph, arrays):
        """Rebuild the dist hierarchy from ``dist-level-<i>`` snapshots:
        chain the coarse host CSRs (snapshot i holds contraction i's
        coarse graph + cmap; the fine side of level 0 is the input
        graph, carried by reference through the graph fingerprint).
        The sharded DistGraphs are NOT serialized — dist_graph_from_host
        is deterministic, so each level's is rebuilt on demand, exactly
        like the rung-2 spill path.  Returns (levels, coarsest)."""
        names = sorted(
            (nm for nm in arrays if nm.startswith("dist-level-")),
            key=lambda s: int(s.rsplit("-", 1)[1]),
        )
        levels: List[_DistLevel] = []
        fine = graph
        for nm in names:
            a = arrays[nm]
            coarse = HostGraph(
                xadj=np.asarray(a["xadj"], dtype=np.int64),
                adjncy=np.asarray(a["adjncy"], dtype=np.int32),
                node_weights=np.asarray(a["node_w"]),
                edge_weights=(
                    np.asarray(a["edge_w"]) if a["edge_w"].size else None
                ),
            )
            levels.append(
                _DistLevel(
                    fine, np.asarray(a["cmap"], dtype=np.int32), None,
                    self.mesh,
                )
            )
            fine = coarse
        return levels, fine

    def spill_cold_levels(self) -> int:
        """Memory-governor spiller hook (resilience/memory.py rung >= 2
        and the barrier pressure path): drop EVERY per-level sharded
        DistGraph — during coarsening the next level builds its own
        (the loop's local still references the hot one), and
        uncoarsening rebuilds each level's on demand from its host CSR
        (deterministic builder => cut-identical).  Also releases the
        retained finest-level sharded graph of a compressed input (the
        result cut then degrades to the host path).  Returns the device
        bytes released."""
        from .dist_graph import dist_graph_bytes

        freed = 0
        for lvl in self._levels or []:
            freed += lvl.spill()
        if self._fine_dg is not None:
            freed += dist_graph_bytes(self._fine_dg)
            self._fine_dg = None
        if freed:
            from .. import telemetry
            from ..resilience import memory as memory_mod

            memory_mod.note_spill(freed)
            telemetry.event(
                "memory-spill", bytes=freed, kind="dist-levels",
            )
        return freed


class _DistLevel:
    """One dist coarsening level: the fine-side host graph (by
    reference; plain or compressed), the fine->coarse cluster map, and
    the sharded DistGraph over the fine graph.  The DistGraph is a
    deterministic CACHE (dist_graph_from_host / _from_compressed always
    rebuild the identical arrays), so the rung-2 spill and the
    full-hierarchy resume both drop it and rebuild on demand —
    cut-identical by construction."""

    __slots__ = ("fine_host", "cmap", "_dg", "_mesh")

    def __init__(self, fine_host, cmap, dg, mesh):
        self.fine_host = fine_host
        self.cmap = np.asarray(cmap, dtype=np.int32)
        self._dg = dg
        self._mesh = mesh

    def dg(self) -> DistGraph:
        if self._dg is None:
            from ..graphs.compressed import CompressedHostGraph
            from .dist_graph import dist_graph_bytes

            if isinstance(self.fine_host, CompressedHostGraph):
                self._dg = dist_graph_from_compressed(
                    self.fine_host, self._mesh
                )
            else:
                self._dg = dist_graph_from_host(self.fine_host, self._mesh)
            nbytes = dist_graph_bytes(self._dg)
            from .. import telemetry
            from ..resilience import memory as memory_mod

            memory_mod.note_reload(nbytes)
            telemetry.event(
                "memory-reload", bytes=nbytes, kind="dist-level",
            )
        return self._dg

    def spill(self) -> int:
        """Drop the sharded arrays (0 when already spilled)."""
        if self._dg is None:
            return 0
        from .dist_graph import dist_graph_bytes

        nbytes = dist_graph_bytes(self._dg)
        self._dg = None
        return nbytes


def dist_edge_cut_of(graph: DistGraph, labels) -> int:
    """Convenience wrapper mirroring dist::metrics::edge_cut."""
    return int(dist_edge_cut(graph, labels))


def _ckpt_partition_payload(partition) -> dict:
    """Checkpoint barrier payload: the current (already host-side)
    partition — deferred by the barrier, so disabled runs build nothing."""
    return {"state": {"partition": np.asarray(partition, dtype=np.int32)}}


def _dist_level_payload(level_no: int, coarse: HostGraph, cmap, fine_n: int,
                        ) -> dict:
    """One dist hierarchy level as a named snapshot (contraction
    ``level_no``'s coarse host CSR + fine->coarse cmap) — the dist twin
    of partitioning/coarsener.newest_level_snapshot.  Deferred by the
    barrier, so disabled runs build nothing; levels are serialized once
    and carried forward by reference (``keep``)."""
    return {
        f"dist-level-{int(level_no)}": {
            "xadj": np.asarray(coarse.xadj, dtype=np.int64),
            "adjncy": np.asarray(coarse.adjncy, dtype=np.int32),
            "node_w": np.asarray(coarse.node_weight_array()),
            "edge_w": np.asarray(coarse.edge_weight_array()),
            "cmap": np.asarray(cmap, dtype=np.int32),
            "dims": np.asarray(
                [int(fine_n), int(coarse.n), int(coarse.m)], dtype=np.int64
            ),
        }
    }


def _dist_state_payload(partition, spans) -> dict:
    """The dist driver's state snapshot: the current partition plus the
    block spans (first final block, count) the current k was built
    from — everything a dist-initial / dist-uncoarsen re-entry needs
    beyond the hierarchy levels."""
    return {
        "state": {
            "partition": np.asarray(partition, dtype=np.int32),
            "spans": np.asarray(
                [[int(f), int(c)] for f, c in spans], dtype=np.int64
            ),
        }
    }
