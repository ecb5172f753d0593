"""Public API facade.

Analog of class KaMinPar (include/kaminpar-shm/kaminpar.h:783-976,
kaminpar-shm/kaminpar.cc:297-463): builder-style — construct with a context,
set a graph, then compute partitions with k / epsilon / explicit block
weights.  Handles the same preprocessing as the reference: isolated-node
removal and reintegration (kaminpar.cc:392-431) and permutation-aware output
copy (kaminpar.cc:437-448).
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import numpy as np

from .context import Context, PartitioningMode
from .graphs.host import (
    HostGraph,
    count_isolated_nodes,
    remove_isolated_nodes,
    validate as validate_graph,
)
from .presets import create_context_by_preset_name
from .utils import rng as rng_mod
from .utils import timer
from .utils.logger import OutputLevel, log, set_output_level
from .utils.platform import configure_compile_cache


class KaMinPar:
    """TPU-native k-way graph partitioner with the reference's builder API.

    Usage (mirrors bindings/python/src/kaminpar/__init__.py):
        ctx = kaminpar_tpu.context_from_preset("default")
        partitioner = KaMinPar(ctx)
        partitioner.set_graph(graph)
        part = partitioner.compute_partition(k=16, epsilon=0.03)
    """

    def __init__(self, ctx: Union[Context, str, None] = None):
        # before the first compile of any run this instance starts
        configure_compile_cache()
        if ctx is None:
            ctx = create_context_by_preset_name("default")
        elif isinstance(ctx, str):
            ctx = create_context_by_preset_name(ctx)
        self.ctx = ctx
        self._graph: Optional[HostGraph] = None
        self.output_level = OutputLevel.APPLICATION
        # set by compute_partition when a run wound down early under a
        # deadline/preemption (resilience/deadline.py); None otherwise
        self.last_anytime: Optional[dict] = None
        # warm-start state (dynamic repartitioning, dynamic/): a valid
        # full-k partition that seeds the v-cycle scheme instead of the
        # initial deep run; one-shot — consumed by the next
        # compute_partition call and cleared afterwards
        self._warm_part: Optional[np.ndarray] = None
        self._warm_levels: Optional[int] = None

    # -- graph ingestion (KaMinPar::borrow_and_mutate_graph / copy_graph) --
    def set_graph(self, graph, validate: bool = False) -> "KaMinPar":
        """Accepts a HostGraph or a CompressedHostGraph (terapart mode).
        With ctx.compression.enabled, plain graphs are stored compressed
        (the Graph facade's CSR/compressed dispatch analog,
        kaminpar-shm/datastructures/graph.h:24-62)."""
        from .external.chunkstore import StreamedSpecGraph
        from .graphs.compressed import CompressedHostGraph, compress_host_graph

        from .utils.assertions import heavy_assertions_enabled

        if isinstance(graph, (CompressedHostGraph, StreamedSpecGraph)):
            # compressed containers and generator-spec wrappers pass
            # through: their consumers stream (decode_range / chunk
            # regeneration) instead of reading a flat CSR
            self._graph = graph
        else:
            # heavy assertion level always validates, mirroring the
            # KASSERT(validate_graph(...), assert::heavy) call in
            # kaminpar-shm/kaminpar.cc:176
            if validate or heavy_assertions_enabled():
                validate_graph(graph)
            if self.ctx.compression.enabled:
                graph = compress_host_graph(graph)
            self._graph = graph
        return self

    def copy_graph(
        self,
        xadj: Sequence[int],
        adjncy: Sequence[int],
        vwgt: Optional[Sequence[int]] = None,
        adjwgt: Optional[Sequence[int]] = None,
    ) -> "KaMinPar":
        """CSR ingestion (KaMinPar::copy_graph signature)."""
        self._graph = HostGraph(
            xadj=np.asarray(xadj),
            adjncy=np.asarray(adjncy, dtype=np.int32),
            node_weights=None if vwgt is None else np.asarray(vwgt),
            edge_weights=None if adjwgt is None else np.asarray(adjwgt),
        )
        return self

    def set_output_level(self, level: OutputLevel) -> "KaMinPar":
        """Instance-scoped (kaminpar.h set_output_level): applied to the
        process-global logger only for the duration of compute_partition,
        so a QUIET instance does not mute the embedding process.  When
        never called, the global level is left untouched."""
        self.output_level = OutputLevel(level)
        self._explicit_level = self.output_level
        return self

    def graph(self) -> Optional[HostGraph]:
        return self._graph

    def set_initial_partition(
        self, partition, max_levels: Optional[int] = None
    ) -> "KaMinPar":
        """Warm-start the next ``compute_partition`` call (v-cycle
        scheme only): ``partition`` must be a valid full-k labeling of
        the current graph; the v-cycle driver refines it instead of
        running the initial deep multilevel pass.  ``max_levels`` bounds
        the warm cycle's restricted-coarsening depth (0 = refinement
        only).  One-shot: cleared when the call returns."""
        self._warm_part = (
            None if partition is None
            else np.asarray(partition, dtype=np.int32)
        )
        self._warm_levels = max_levels
        return self

    # -- main entry point (KaMinPar::compute_partition, kaminpar.cc:297) --
    def compute_partition(
        self,
        k: Optional[int] = None,
        epsilon: Optional[float] = None,
        max_block_weights: Optional[np.ndarray] = None,
        min_block_weights: Optional[np.ndarray] = None,
        seed: Optional[int] = None,
    ) -> np.ndarray:
        if self._graph is None:
            raise RuntimeError("no graph set; call set_graph() first")
        # one root profiler span per request: every timer scope below is
        # a span inside it (utils/timer.py); k as PartitionContext.setup
        # will resolve it
        if max_block_weights is not None:
            span_k = len(max_block_weights)
        else:
            span_k = self.ctx.partition.k if k is None else k
        with timer.request_span(
            k=int(span_k), n=int(self._graph.n), m=int(self._graph.m)
        ):
            return self._compute_partition(
                k, epsilon, max_block_weights, min_block_weights, seed
            )

    def _compute_partition(
        self, k, epsilon, max_block_weights, min_block_weights, seed
    ) -> np.ndarray:
        from .graphs.compressed import CompressedHostGraph

        graph = self._graph
        if isinstance(graph, CompressedHostGraph) and self._must_decode(
            graph
        ):
            graph = self._decode_cached(graph)
        # else: the graph STAYS compressed — the deep pipeline streams
        # the device upload chunk-by-chunk (TeraPart compute parity:
        # peak host memory is compressed + one chunk + O(n); see
        # graphs/csr.device_graph_from_compressed) and the RESULT
        # metrics stream the same way
        ctx = self.ctx
        if seed is not None:
            ctx.seed = int(seed)
        rng_mod.set_seed(ctx.seed)

        ctx.partition.setup(
            graph,
            k=k,
            epsilon=epsilon,
            max_block_weights=max_block_weights,
        )
        if min_block_weights is not None:
            ctx.partition.min_block_weights = np.asarray(
                min_block_weights, dtype=np.int64
            )
        self._validate_parameters()
        k = ctx.partition.k

        from . import telemetry
        from .utils import heap_profiler, statistics
        from .utils.heap_profiler import scoped_heap_profiler

        timer.GLOBAL_TIMER.reset()
        heap_profiler.reset()
        statistics.reset()
        # telemetry shares the timer's nesting caveat: when this run is
        # embedded in another pipeline (shm IP inside the dist driver),
        # the outer run owns the stream and its annotations
        owns_stream = timer.GLOBAL_TIMER.idle()
        if owns_stream:
            telemetry.reset()
            telemetry.annotate(
                preset=ctx.preset_name,
                seed=int(ctx.seed),
                k=int(k),
                epsilon=float(ctx.partition.epsilon),
                mode=ctx.partitioning.mode.value,
                graph={"n": int(graph.n), "m": int(graph.m)},
            )
        from .partitioning import debug
        from .utils.logger import output_level as global_output_level

        # preemption safety: the run that OWNS the stream (same idle-timer
        # guard as the telemetry annotations) may arm a deadline budget
        # and a checkpoint manager; nested IP runs inside the dist driver
        # never do — a checkpoint must not record an inner pipeline's
        # stage as the outer run's.
        from .resilience import checkpoint as ckpt_mod
        from .resilience import deadline as deadline_mod
        from .resilience import memory as mem_mod

        mgr = None
        res_ctx = ctx.resilience
        self.last_anytime = None  # stale verdicts must not survive a rerun
        # hard wall-clock watchdog (resilience/supervisor.py): the
        # cooperative budget above is checked BETWEEN launches and can
        # never interrupt a hung one; when a hard ceiling resolves
        # (env override, or factor x budget for budgeted runs) the
        # partitioning block below runs under an armed watchdog stage
        # that converts a wall-clock overrun into a structured,
        # breaker-relevant StageHang.  None = no ceiling = no-op guard.
        from .resilience import supervisor as sup_mod

        hard_ceiling_s = None
        if owns_stream:
            # self-heal leftover state from an exceptional unwind of a
            # previous run in this process (a stale manager or deadline
            # must not govern this run), arm the configured budget while
            # PRESERVING a preemption signal that arrived before the run
            # (deadline.begin_run), and build/validate the checkpoint
            # manager (create_manager: mismatch/corruption degrade to a
            # logged clean restart)
            ckpt_mod.deactivate()
            deadline_mod.begin_run(
                res_ctx.time_budget or None, res_ctx.budget_grace,
                getattr(res_ctx, "hard_deadline_factor", None),
            )
            mgr = ckpt_mod.create_manager(res_ctx, self._graph, ctx)
            if mgr is not None:
                ckpt_mod.activate(mgr)
            # memory governor: price this run against the declared
            # budget and pick the starting ladder rung (after
            # begin_run's fresh RunState — the governor state rides on
            # it); dormant without a budget, but the ladder below still
            # catches any DeviceOOM
            mem_mod.begin_run(graph, ctx)
            hard_ceiling_s = sup_mod.hard_ceiling(
                res_ctx.time_budget, res_ctx.budget_grace,
                getattr(res_ctx, "hard_deadline_factor", None),
            )
        if not owns_stream:
            # nested run (shm IP inside the dist driver): blind the
            # barrier hook for the duration — inner drivers must neither
            # rewrite the outer run's manifest with their own stage nor
            # consume its resume state (unsuspended in the finally below)
            ckpt_mod.suspend()

        debug.dump_toplevel_graph(ctx, graph)
        # the logger is process-global; apply this instance's level only
        # for the duration of the computation
        prior_level = global_output_level()
        try:
            set_output_level(getattr(self, "_explicit_level", prior_level))
            if self.output_level >= OutputLevel.APPLICATION:
                self._print_context_summary(graph, ctx)
            with sup_mod.stage_guard(
                "partition", hard_ceiling_s
            ), timer.scoped_timer("partitioning"), scoped_heap_profiler(
                "partitioning"
            ):
                # isolated-node preprocessing (kaminpar.cc:392-404)
                from .external.chunkstore import StreamedSpecGraph

                num_isolated = count_isolated_nodes(graph)
                still_compressed = isinstance(graph, CompressedHostGraph)
                # generator-spec wrappers keep isolated nodes in the
                # stream: extraction would materialize the adjacency,
                # and the external scheme's device phases (LP packing +
                # balancers) place edge-less nodes anyway
                streaming_src = isinstance(graph, StreamedSpecGraph)
                resumed_result = (
                    mgr.take_result_resume() if mgr is not None else None
                )
                if (
                    resumed_result is not None
                    and resumed_result.shape == (graph.n,)
                ):
                    # a run preempted AFTER its output gate left a final
                    # `result` snapshot: nothing to recompute
                    partition = resumed_result
                elif (
                    num_isolated
                    and graph.n > num_isolated
                    and still_compressed
                ):
                    # compressed twin of the decoded branch below: the
                    # core graph is extracted compressed-to-compressed
                    # (chunk-streamed re-encode, graphs/compressed.py)
                    # and isolated nodes refill blocks by headroom —
                    # skipping this cost 28% cut at k=128 (isolated
                    # weight distorts coarsening and balance)
                    from .graphs.compressed import extract_core_compressed
                    from .graphs.host import NodePermutation

                    core_cg, core_ids, iso_ids = extract_core_compressed(
                        graph
                    )
                    part_core = self._partition_core_governed(core_cg, ctx)
                    new_to_old = np.concatenate([core_ids, iso_ids])
                    old_to_new = np.empty(graph.n, dtype=np.int64)
                    old_to_new[new_to_old] = np.arange(graph.n)
                    partition = self._reintegrate_isolated(
                        graph, core_cg,
                        NodePermutation(old_to_new, new_to_old),
                        num_isolated, part_core,
                    )
                elif (
                    num_isolated
                    and graph.n > num_isolated
                    and not still_compressed
                    and not streaming_src
                ):
                    # host-only, before the first launch: the device
                    # idles meanwhile, so the span says what for
                    with timer.scoped_timer("isolated-nodes"):
                        core, perm, _ = remove_isolated_nodes(graph)
                    core_ctx = ctx  # weights already set up from the full graph
                    if self._warm_part is not None:
                        # warm seed follows the core permutation (the
                        # first core.n permuted slots are the connected
                        # nodes the core run partitions)
                        self._warm_part = self._warm_part[
                            perm.new_to_old[: core.n]
                        ]
                    part_core = self._partition_core_governed(core, core_ctx)
                    partition = self._reintegrate_isolated(
                        graph, core, perm, num_isolated, part_core
                    )
                elif num_isolated == graph.n and graph.n > 0:
                    partition = self._partition_only_isolated(graph)
                else:
                    partition = self._partition_core_governed(graph, ctx)
        finally:
            set_output_level(prior_level)
            # warm-start state is one-shot: a later call on this
            # instance (different graph, different k) must never
            # silently inherit it
            self._warm_part = None
            self._warm_levels = None
            if not owns_stream:
                ckpt_mod.unsuspend()

        # strict-balance output gate (resilience/gate.py): validate the
        # partition invariants host-side and repair balance violations,
        # so the postcondition below holds no matter which optional fast
        # paths degraded during the run.  Only a run that OWNS the
        # telemetry stream (idle timer — same guard as the annotations
        # above) may stamp its verdict into the report; nested IP runs
        # inside the dist driver still gate, but anonymously.
        from .resilience import gate as output_gate

        if output_gate.gate_enabled() and ctx.resilience.output_gate:
            with timer.scoped_timer("output-gate"):
                partition = output_gate.apply(
                    self, graph, partition, ctx, annotate=owns_stream
                )

        # final barrier: a `result` snapshot AFTER the gate, so a
        # preemption between here and the caller resumes instantly; then
        # stamp the anytime/checkpoint sections into the run report and
        # release the run-scoped preemption state
        if owns_stream:
            if mgr is not None and mgr.enabled:
                final_part = partition
                ckpt_mod.barrier(
                    "result", scheme="facade",
                    payload=lambda: {"state": {
                        "partition": np.asarray(final_part, dtype=np.int32)
                    }},
                )
            if deadline_mod.triggered():
                self.last_anytime = deadline_mod.state()
                telemetry.annotate(anytime=self.last_anytime)
                from .utils.logger import log_warning

                # .get(): a driverless path (e.g. the all-isolated-nodes
                # branch) crosses no barrier, so stage/reason may be absent
                log_warning(
                    "ANYTIME result: wound down at stage "
                    f"'{self.last_anytime.get('stage') or 'start'}' "
                    f"({self.last_anytime.get('reason')}); partition is "
                    "gate-validated but lower-effort"
                )
            else:
                self.last_anytime = None
            if mgr is not None:
                telemetry.annotate(checkpoint=mgr.summary())
            # memory-budget audit trail: annotate only when a budget was
            # declared or the ladder/pressure hook engaged — the report
            # builder fills the well-formed disabled default otherwise
            mem_summary = mem_mod.summary()
            if mem_summary.get("enabled"):
                telemetry.annotate(memory_budget=mem_summary)
            ckpt_mod.deactivate()

        debug.dump_toplevel_partition(ctx, partition)
        from .utils.assertions import AssertionLevel, kassert

        kassert(
            lambda: partition.shape == (graph.n,)
            and (partition >= 0).all()
            and (partition < k).all(),
            "partition labels out of range (validate_partition analog)",
            AssertionLevel.LIGHT,
        )
        # telemetry only needs the metrics when this run owns the stream
        # (idle-gated, like the annotation itself): nested IP runs inside
        # the dist driver would otherwise pay an O(n + m) pass per
        # candidate and discard the result
        if self.output_level >= OutputLevel.APPLICATION or (
            telemetry.enabled() and timer.GLOBAL_TIMER.idle()
        ):
            self._print_result(graph, partition)
        return partition

    def _decode_cached(self, cgraph):
        """Memoized full decode of a compressed input: repeated
        compute_partition calls (seed/k sweeps) and the compressed-stream
        degradation fallback shouldn't re-pay the O(m) decompression."""
        cached = getattr(self, "_decoded", None)
        if cached is None or cached[0] is not cgraph:
            self._decoded = (cgraph, cgraph.decode())
        return self._decoded[1]

    def _partition_core_governed(self, graph, ctx: Context) -> np.ndarray:
        """The core partition under the memory governor's OOM recovery
        ladder (resilience/memory.py): a classified DeviceOOM anywhere
        below retries at progressively more frugal rungs (tight pads,
        host-spilled hierarchy, semi-external streaming, host-only)
        instead of surfacing RESOURCE_EXHAUSTED.  A plain try-through
        when the governor is dormant and nothing OOMs."""
        from .resilience import integrity as integrity_mod
        from .resilience import memory as mem_mod

        # corruption-recovery ladder OUTSIDE the OOM ladder: a sentinel
        # violation (silent data corruption detected at a phase boundary)
        # re-executes once from the last clean checkpoint barrier; a
        # second violation is the `corrupt-result` verdict and propagates
        return integrity_mod.run_with_retry(
            lambda: mem_mod.run_ladder(
                lambda: self._partition_core_resilient(graph, ctx),
                graph, ctx, facade=self,
            ),
            where="partition-core",
        )

    def _partition_core_resilient(self, graph, ctx: Context) -> np.ndarray:
        """_partition_core under the compressed-stream degradation
        contract: when the chunk-streamed device upload of a compressed
        graph fails (device OOM, injected fault), decode to the plain
        host CSR and re-partition — TeraPart memory parity degrades to
        correctness-first instead of aborting the run."""
        from .graphs.compressed import CompressedHostGraph

        if not isinstance(graph, CompressedHostGraph):
            return self._partition_core(graph, ctx)
        from .resilience import with_fallback

        return with_fallback(
            lambda: self._partition_core(graph, ctx),
            lambda exc: self._partition_core(
                self._decode_cached(graph), ctx
            ),
            site="compressed-stream",
        )

    # -- scheme dispatch (factories.cc:40-57 create_partitioner) --
    def _partition_core(self, graph: HostGraph, ctx: Context) -> np.ndarray:
        mode = ctx.partitioning.mode
        if mode == PartitioningMode.KWAY:
            from .partitioning.kway import KWayMultilevelPartitioner

            return KWayMultilevelPartitioner(ctx).partition(graph)
        elif mode == PartitioningMode.DEEP:
            from .partitioning.deep import DeepMultilevelPartitioner

            return DeepMultilevelPartitioner(ctx).partition(graph)
        elif mode == PartitioningMode.RB:
            from .partitioning.rb_scheme import RBMultilevelPartitioner

            return RBMultilevelPartitioner(ctx).partition(graph)
        elif mode == PartitioningMode.VCYCLE:
            from .partitioning.vcycle import VcycleDeepMultilevelPartitioner

            return VcycleDeepMultilevelPartitioner(
                ctx,
                initial_partition=self._warm_part,
                max_levels=self._warm_levels,
            ).partition(graph)
        elif mode == PartitioningMode.EXTERNAL:
            from .external.driver import ExternalPartitioner

            return ExternalPartitioner(ctx).partition(graph)
        raise ValueError(f"unknown partitioning mode: {mode}")

    def _validate_parameters(self) -> None:
        """KaMinPar::validate_partition_parameters (kaminpar.cc:465)."""
        p = self.ctx.partition
        if p.k < 1:
            raise ValueError("k must be >= 1")
        if int(p.max_block_weights.sum()) < p.total_node_weight:
            raise ValueError(
                "infeasible: total max block weight "
                f"{int(p.max_block_weights.sum())} < total node weight "
                f"{p.total_node_weight}"
            )

    def _reintegrate_isolated(
        self, graph, core, perm, num_isolated, part_core
    ) -> np.ndarray:
        """kaminpar.cc:422-431: isolated nodes fill up underloaded blocks."""
        p = self.ctx.partition
        partition = np.zeros(graph.n, dtype=np.int32)
        core_n = core.n
        # nodes permuted: first core_n slots are connected nodes
        partition_permuted = np.zeros(graph.n, dtype=np.int32)
        partition_permuted[:core_n] = part_core

        node_w = graph.node_weight_array()[perm.new_to_old]
        bw = np.zeros(p.k, dtype=np.int64)
        np.add.at(bw, part_core, node_w[:core_n].astype(np.int64))
        partition_permuted[core_n:] = _fill_blocks_by_headroom(
            node_w[core_n:], bw, p.max_block_weights
        )
        partition[perm.new_to_old] = partition_permuted
        return partition

    def _partition_only_isolated(self, graph) -> np.ndarray:
        """Graph with no edges: fill blocks by headroom under the caps."""
        p = self.ctx.partition
        node_w = graph.node_weight_array()
        bw = np.zeros(p.k, dtype=np.int64)
        return _fill_blocks_by_headroom(node_w, bw, p.max_block_weights)

    def _print_context_summary(self, graph, ctx: Context) -> None:
        """Startup banner + compact context block (the analog of the
        reference's version banner and context printer,
        kaminpar-shm/context.cc / kaminpar-common console_io)."""
        from . import __version__

        p = ctx.partition
        log(f"kaminpar-tpu v{__version__} (preset '{ctx.preset_name}', "
            f"seed {ctx.seed})")
        log(f"  graph: n={graph.n} m={graph.m} "
            f"total_node_weight={graph.total_node_weight}")
        log(f"  partition: k={p.k} eps={p.epsilon} "
            f"mode={ctx.partitioning.mode.value}")
        log(f"  coarsening: {ctx.coarsening.algorithm.value} "
            f"(contraction limit {ctx.coarsening.contraction_limit}), "
            f"refinement: "
            f"{';'.join(a.value for a in ctx.refinement.algorithms)}")

    def _must_decode(self, cgraph) -> bool:
        """Whether a compressed input still needs the full host CSR.

        The streamed-compute path (deep multilevel; chunked device
        upload + chunked RESULT metrics) covers the TeraPart workload;
        host-CSR consumers force a decode: isolated-node pre/processing
        (kaminpar.cc:392-404 walks host rows), non-deep schemes, and
        debug graph dumps."""
        from .context import PartitioningMode

        d = self.ctx.debug
        if (
            d.dump_toplevel_graph
            or d.dump_toplevel_partition
            or d.dump_graph_hierarchy
        ):
            return True
        if self.ctx.partitioning.mode not in (
            PartitioningMode.DEEP,
            # the external scheme is BUILT on never materializing: the
            # chunk store decodes node ranges on demand
            PartitioningMode.EXTERNAL,
        ):
            return True
        # isolated nodes do NOT force a decode: the host-side isolated
        # extraction (kaminpar.cc:392-404) is skipped for compressed
        # inputs and the device pipeline places them instead (LP's
        # isolated-node packing + balancers) — they cut nothing either way
        return False

    def result_metrics(self, graph, partition) -> dict:
        """cut / imbalance / feasible of a computed partition (the RESULT
        line's numbers, also the run report's `result` section).

        Memoized by (graph, partition) identity: the output gate needs
        the driver-path cut for its cross-check and the RESULT printer
        needs the same numbers moments later — without the memo every
        gated call would pay the O(n + m) host sweep twice (and re-
        stream the whole compressed adjacency on TeraPart inputs)."""
        cached = getattr(self, "_metrics_memo", None)
        if (
            cached is not None
            and cached[0] is graph
            and cached[1] is partition
        ):
            return cached[2]
        from .external.chunkstore import (
            StreamedSpecGraph,
            streamed_partition_metrics,
        )
        from .graphs.compressed import (
            CompressedHostGraph,
            compressed_partition_metrics,
        )
        from .graphs.host import host_partition_metrics

        p = self.ctx.partition
        if isinstance(graph, CompressedHostGraph):
            m = compressed_partition_metrics(graph, partition, p.k)
        elif isinstance(graph, StreamedSpecGraph):
            m = streamed_partition_metrics(graph, partition, p.k)
        else:
            m = host_partition_metrics(graph, partition, p.k)
        result = {
            "cut": int(m["cut"]),
            "imbalance": float(m["imbalance"]),
            "feasible": bool(
                (m["block_weights"] <= p.max_block_weights).all()
            ),
        }
        self._metrics_memo = (graph, partition, result)
        return result

    def _print_result(self, graph, partition) -> None:
        """Parseable RESULT line (kaminpar-shm/kaminpar.cc:48) + the
        telemetry result annotation consumed by --report-json."""
        from . import telemetry

        m = self.result_metrics(graph, partition)
        if timer.GLOBAL_TIMER.idle():  # nested runs don't own the stream
            telemetry.annotate(result=m)
        if self.output_level >= OutputLevel.APPLICATION:
            log(
                f"RESULT cut={m['cut']} imbalance={m['imbalance']:.6f} "
                f"feasible={int(m['feasible'])} k={self.ctx.partition.k}"
            )


def _fill_blocks_by_headroom(
    node_w: np.ndarray, block_w: np.ndarray, max_block_weights: np.ndarray
) -> np.ndarray:
    """Assign edge-less (interchangeable) nodes to blocks without exceeding
    the caps: fill blocks in descending-headroom order with node prefixes by
    cumulative weight — O((n + k) log k) instead of a per-node argmax loop
    (kaminpar.cc:422-431 reintegration semantics)."""
    n = len(node_w)
    out = np.zeros(n, dtype=np.int32)
    if n == 0:
        return out
    headroom = (np.asarray(max_block_weights, dtype=np.int64) - block_w).clip(0)
    order = np.argsort(-headroom, kind="stable")
    cum = np.cumsum(node_w.astype(np.int64))
    start = 0
    assigned = 0
    for b in order:
        if start >= n:
            break
        end = int(np.searchsorted(cum, assigned + headroom[b], side="right"))
        out[start:end] = b
        if end > start:
            assigned = int(cum[end - 1])
        start = end
    if start < n:
        # caps cannot hold everything (validated earlier to be impossible
        # for feasible instances); spill into the biggest block
        out[start:] = int(order[0])
    return out


def context_from_preset(name: str) -> Context:
    return create_context_by_preset_name(name)
