"""Deep multilevel partitioner — the flagship scheme (ESA'21).

Analog of kaminpar-shm/partitioning/deep/deep_multilevel.cc: coarsen on
device until n <= 2 * contraction_limit (the sequential initial-partitioning
threshold, deep_multilevel.cc:170-183 — the host pool bipartitioner plays
the role of the reference's sequential mode), bipartition the coarsest graph
(initial_partition:185), then uncoarsen while *doubling k*: after each
projection, if the graph is large enough for more blocks
(compute_k_for_n, partition_utils.cc:94-101), extend the partition by
bipartitioning each block's induced subgraph (extend_partition,
helper.cc:220-349), then refine at the current k.

Block bookkeeping: each current block b spans the final blocks
[first(b), first(b)+count(b)); extension splits a block into ceil/floor
halves (split_k = math::split_integral), preserving block order, so when
current_k reaches the input k the block ids coincide with final ids.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import jax.numpy as jnp
import numpy as np

from ..context import Context
from ..graphs.csr import (
    DeviceGraph,
    WEIGHT_DTYPE,
    device_graph_from_host,
    host_graph_from_device,
)
from ..graphs.host import HostGraph, extract_block_subgraphs
from ..initial import InitialMultilevelBipartitioner
from ..utils import rng as rng_mod
from ..utils import timer
from ..utils.logger import log_progress
from .coarsener import Coarsener
from .refiner import RefinerPipeline
from ..dtypes import WMAX
from .rb import bipartition_max_block_weights, split_k


@dataclass
class _BlockSpan:
    first: int  # first final block
    count: int  # number of final blocks


# Below this many edge slots the old host extraction (small readback +
# numpy) wins over minting device extraction programs; above it the
# device path avoids a full-graph readback per k-doubling
# (subgraph_extractor.h:36-177 analog, ops/subgraphs.py).
DEVICE_EXTEND_MIN_EDGE_SLOTS = 1 << 22


def compute_k_for_n(n: int, ctx: Context) -> int:
    """partition_utils.cc:94-101."""
    C = ctx.coarsening.contraction_limit
    if n < 2 * C:
        return 2
    k_prime = 1 << max(1, (int(np.ceil(np.log2(max(n / C, 2.0))))))
    return int(np.clip(k_prime, 2, ctx.partition.k))


class DeepMultilevelPartitioner:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self._spans: List[_BlockSpan] = []

    def partition(self, graph: HostGraph) -> np.ndarray:
        from ..resilience import memory as memory_mod
        from ..telemetry import quality as quality_mod

        # pre-upload budget check: refuse the allocation BEFORE bytes
        # land on the device; the facade's recovery ladder catches the
        # structured DeviceOOM and retries at the next rung
        memory_mod.preflight(
            graph.n, graph.m, self.ctx.partition.k, where="deep"
        )
        # quality observatory (telemetry/quality.py): one hierarchy
        # recording scope per driver run — nesting-safe, so a nested IP
        # run inside the dist driver records its own tiny hierarchy
        # without corrupting the outer one; no-op while disabled
        qh = quality_mod.begin("deep")
        try:
            return self._partition_recorded(graph, qh)
        finally:
            quality_mod.end(qh)

    def _partition_recorded(self, graph: HostGraph, qh) -> np.ndarray:
        ctx = self.ctx
        input_k = ctx.partition.k
        rng = rng_mod.host_rng(ctx.seed ^ 0xDEE9)

        from . import debug
        from ..resilience import checkpoint as ckpt
        from ..telemetry import quality as quality_mod
        with timer.scoped_timer("device-upload"):
            from ..graphs.compressed import CompressedHostGraph

            # streamed inputs keep the host footprint at compressed +
            # O(n); the extend path must then avoid full-graph readbacks
            # (see _extend_partition)
            self._streamed_input = isinstance(graph, CompressedHostGraph)
            if isinstance(graph, CompressedHostGraph):
                # TeraPart compute parity: stream the decode chunk-by-
                # chunk to the device — the flat CSR never exists on the
                # host (graphs/csr.device_graph_from_compressed)
                from ..graphs.csr import device_graph_from_compressed

                dgraph = device_graph_from_compressed(graph)
            else:
                dgraph = device_graph_from_host(graph)

        # --- coarsen (deep_multilevel.cc:69-183) ---
        coarsener = Coarsener(ctx, dgraph, graph.n)
        threshold = max(2 * ctx.coarsening.contraction_limit, 2)
        from ..utils.heap_profiler import sample_device_memory

        # --- checkpoint resume: rebuild the recorded hierarchy/state and
        # re-enter at the recorded stage (no completed level re-runs) ---
        resume = ckpt.take_resume("deep")
        stage = None
        partition = None
        spans: List[_BlockSpan] = []
        current_k = 0
        num_levels = None
        if resume is not None:
            stage, partition, spans, current_k, num_levels, rng = (
                self._restore_from_checkpoint(resume, coarsener, dgraph, rng)
            )

        if stage is None or stage == "coarsen":
            with timer.scoped_timer("coarsening"):
                while coarsener.current_n > threshold:
                    if not coarsener.coarsen():
                        break
                    sample_device_memory()  # per-level live-HBM peak
                    log_progress(
                        f"deep coarsening level {coarsener.level}: "
                        f"n={coarsener.current_n}"
                    )
                    if ctx.debug.dump_graph_hierarchy:
                        debug.dump_graph_hierarchy(
                            ctx,
                            host_graph_from_device(coarsener.current),
                            coarsener.level,
                        )
                    if not ckpt.barrier(
                        "coarsen", level=coarsener.level, scheme="deep",
                        payload=lambda: self._ckpt_level_payload(coarsener),
                        keep=[
                            f"level-{j}" for j in range(coarsener.level - 1)
                        ],
                        meta=self._ckpt_meta(current_k, num_levels, rng),
                    ):
                        # deadline wind-down: stop deepening the
                        # hierarchy; IP + projection below stay mandatory
                        break

        if stage in (None, "coarsen"):
            # --- initial bipartition of the coarsest graph (:185) ---
            with timer.scoped_timer("initial-partitioning"):
                coarsest_host = host_graph_from_device(coarsener.current)
                debug.dump_coarsest_graph(ctx, coarsest_host)
                k0, k1 = split_k(input_k)
                spans = (
                    [_BlockSpan(0, k0), _BlockSpan(k0, k1)]
                    if input_k > 1
                    else [_BlockSpan(0, 1)]
                )
                if input_k == 1:
                    part_host = np.zeros(coarsest_host.n, dtype=np.int32)
                else:
                    max_w = bipartition_max_block_weights(
                        ctx, 0, input_k, coarsest_host.total_node_weight
                    )
                    part_host = (
                        InitialMultilevelBipartitioner(
                            ctx.initial_partitioning
                        )
                        .bipartition(coarsest_host, max_w, rng)
                        .astype(np.int32)
                    )
                current_k = len(spans)
                self._spans = spans
                debug.dump_coarsest_partition(ctx, part_host)
                padded = np.zeros(coarsener.current.n_pad, dtype=np.int32)
                padded[: coarsest_host.n] = part_host
                partition = jnp.asarray(padded)
                # quality: the coarsest level's entry cut (the cut the
                # initial partitioner handed uncoarsening)
                quality_mod.note_projected(
                    coarsener.level, coarsener.current, partition,
                    k=current_k,
                )
            num_levels = coarsener.level + 1
            ckpt.barrier(
                "initial", level=coarsener.level, scheme="deep",
                payload=lambda: self._ckpt_state_payload(
                    partition, coarsener.current_n, spans
                ),
                keep=[f"level-{j}" for j in range(coarsener.level)],
                meta=self._ckpt_meta(current_k, num_levels, rng),
            )

        # --- uncoarsen: refine / extend / repeat (:275-365) ---
        if num_levels is None:
            num_levels = coarsener.level + 1
        # debug hierarchy dumps are STAGED: a device copy of each level's
        # partition is collected during the span and pulled to host only
        # after it closes, so the uncoarsening span never carries the
        # readback (tpulint R1).  A copy, not a reference: the next
        # level's projection donates the partition's buffer
        # (coarsener._project_partition_donated).
        pending_dumps: List[Tuple[int, object, int]] = []
        with timer.scoped_timer("uncoarsening"):
            level = coarsener.level
            if stage != "uncoarsen":
                partition, spans, current_k = self._extend_and_refine(
                    coarsener.current,
                    coarsener.current_n,
                    partition,
                    spans,
                    current_k,
                    rng,
                    level,
                    num_levels,
                )
                quality_mod.note_refined(
                    level, coarsener.current, partition, k=current_k,
                    spans=spans, input_k=input_k,
                )
                ckpt.barrier(
                    "uncoarsen", level=level, scheme="deep",
                    payload=lambda: self._ckpt_state_payload(
                        partition, coarsener.current_n, spans
                    ),
                    keep=[f"level-{j}" for j in range(level)],
                    meta=self._ckpt_meta(current_k, num_levels, rng),
                )
            while not coarsener.empty():
                fine_graph, partition = coarsener.uncoarsen(partition)
                sample_device_memory()  # per-level live-HBM peak
                level -= 1
                quality_mod.note_projected(
                    level, fine_graph, partition, k=current_k
                )
                partition, spans, current_k = self._extend_and_refine(
                    fine_graph,
                    coarsener.current_n,
                    partition,
                    spans,
                    current_k,
                    rng,
                    level,
                    num_levels,
                )
                quality_mod.note_refined(
                    level, fine_graph, partition, k=current_k,
                    spans=spans, input_k=input_k,
                )
                if ctx.debug.dump_partition_hierarchy:
                    pending_dumps.append(
                        (level, jnp.copy(partition), coarsener.current_n)
                    )
                part_now = partition
                spans_now = spans
                ckpt.barrier(
                    "uncoarsen", level=level, scheme="deep",
                    payload=lambda: self._ckpt_state_payload(
                        part_now, coarsener.current_n, spans_now
                    ),
                    keep=[f"level-{j}" for j in range(level)],
                    meta=self._ckpt_meta(current_k, num_levels, rng),
                )
        for dump_level, dump_part, dump_n in pending_dumps:
            debug.dump_partition_hierarchy(
                ctx, np.asarray(dump_part)[:dump_n], dump_level
            )

        # final extensions to input_k if not there yet
        while current_k < input_k:
            partition, spans, current_k = self._extend_partition(
                coarsener.current, partition, spans, input_k, rng
            )
            partition = self._refine(
                coarsener.current, partition, current_k, 0, num_levels
            )

        refiner = RefinerPipeline(self.ctx, current_k)
        # readback scopes: the overload check is the first host read
        # after the last refiner, the download the last of the request
        with timer.scoped_timer("balance-check", sync=True):
            partition = refiner.enforce_balance_host(
                dgraph, partition,
                np.asarray(self.ctx.partition.max_block_weights),
                where="deep",
            )
        # quality: push the FINAL partition back up through the recorded
        # cluster maps — the coarsening floors + per-level attribution
        quality_mod.finalize_device(qh, dgraph, partition, graph.n)
        with timer.scoped_timer("partition-download", sync=True):
            return np.asarray(partition)[: graph.n]

    # -- checkpoint payloads / restore (resilience/checkpoint.py) -------

    def _ckpt_level_payload(self, coarsener: Coarsener) -> dict:
        """The just-contracted level as a named snapshot (the barrier
        defers this payload, so it costs nothing with checkpointing
        disabled)."""
        from .coarsener import newest_level_snapshot

        return {f"level-{coarsener.level - 1}": newest_level_snapshot(coarsener)}

    def _ckpt_state_payload(self, partition, n: int, spans) -> dict:
        return {
            "state": {
                "partition": np.asarray(partition)[:n].astype(np.int32),
                "spans": np.asarray(
                    [[s.first, s.count] for s in spans], dtype=np.int64
                ),
            }
        }

    def _ckpt_meta(self, current_k, num_levels, rng) -> dict:
        return {
            "current_k": int(current_k),
            "num_levels": None if num_levels is None else int(num_levels),
            "rng_state": rng.bit_generator.state,
        }

    def _restore_from_checkpoint(self, resume, coarsener, dgraph, rng):
        """Rebuild the coarsener hierarchy (coarsener.restore_levels) and
        the driver state recorded at the checkpointed barrier: partition,
        block spans, current_k, and the host RNG stream."""
        from .coarsener import restore_levels

        arrays = resume["arrays"]
        meta = resume.get("meta", {})
        stage = resume["stage"]
        num_restored = restore_levels(coarsener, dgraph, arrays)

        partition = None
        spans: List[_BlockSpan] = []
        current_k = 0
        if "state" in arrays:
            st = arrays["state"]
            part_host = np.asarray(st["partition"], dtype=np.int32)
            padded = np.zeros(coarsener.current.n_pad, dtype=np.int32)
            padded[: part_host.shape[0]] = part_host
            partition = jnp.asarray(padded)
            spans = [
                _BlockSpan(int(f), int(c))
                for f, c in np.asarray(st["spans"]).tolist()
            ]
            current_k = int(meta.get("current_k", len(spans)))
            self._spans = spans
        if meta.get("rng_state"):
            rng = np.random.default_rng(0)
            rng.bit_generator.state = meta["rng_state"]
        from .. import telemetry

        telemetry.event(
            "resume",
            scheme="deep",
            stage=stage,
            level=resume.get("level"),
            levels_restored=num_restored,
        )
        log_progress(
            f"resumed deep pipeline at {stage}"
            f"{'' if resume.get('level') is None else ':' + str(resume['level'])}"
            f" ({num_restored} hierarchy level(s) restored)"
        )
        return (
            stage, partition, spans, current_k,
            meta.get("num_levels"), rng,
        )

    # ------------------------------------------------------------------
    def _extend_and_refine(
        self,
        dgraph: DeviceGraph,
        n: int,
        partition,
        spans: List[_BlockSpan],
        current_k: int,
        rng,
        level: int,
        num_levels: int,
    ):
        ctx = self.ctx
        partition = self._refine(dgraph, partition, current_k, level, num_levels)
        desired_k = compute_k_for_n(n, ctx)
        target_k = min(desired_k, ctx.partition.k)
        while current_k < target_k:
            partition, spans, current_k = self._extend_partition(
                dgraph, partition, spans, min(2 * current_k, ctx.partition.k), rng
            )
            if ctx.partitioning.refine_after_extending_partition:
                # with light_intermediate_refinement, extensions that are
                # followed by another doubling get a single-round Jet —
                # the partition is refined again at the next doubling;
                # only the final extension's refine is the real polish
                partition = self._refine(
                    dgraph, partition, current_k, level, num_levels,
                    light=(
                        ctx.partitioning.light_intermediate_refinement
                        and current_k < target_k
                    ),
                )
        return partition, spans, current_k

    def _refine(self, dgraph, partition, k, level, num_levels, light=False):
        ctx = self.ctx
        # block weight caps for the *current* k: each current block's cap is
        # the sum of its final sub-blocks' caps (helper.cc block splitting)
        max_bw, min_bw = self._current_block_weights(k)
        refiner = RefinerPipeline(ctx, k, light=light)
        return refiner.refine(
            dgraph,
            partition,
            max_bw,
            min_bw,
            seed=ctx.seed + level,
            level=level,
            num_levels=num_levels,
        )

    # a real per-block device->host pull, by design: each extracted block
    # subgraph round-trips through the device bipartition pipeline and
    # comes back as a host int8 partition for stitching.  The extension
    # span that calls this IS the staged boundary — the pull is the
    # product, not an accidental sync.
    # tpulint: disable=R1
    def _device_bipartition(
        self, sub: HostGraph, max_block_weights: np.ndarray, rng
    ) -> np.ndarray:
        """Host-graph entry: upload, then run the device bipartition
        (passing `sub` down avoids a readback when coarsening converges
        immediately)."""
        dg = device_graph_from_host(sub)
        part = self._device_bipartition_dev(
            dg, sub.n, sub.m, max_block_weights, rng, host_sub=sub
        )
        return np.asarray(part)[: sub.n].astype(np.int8)

    def _device_bipartition_dev(
        self, dg: DeviceGraph, n: int, m: int,
        max_block_weights: np.ndarray, rng,
        host_sub: HostGraph | None = None,
    ):
        """Bipartition a large block subgraph through the device pipeline:
        LP coarsening + contraction on device until ~2000 nodes, host pool
        bipartition of the coarsest, then per-level 2-way LP refinement on
        device (the large-block replacement for the sequential
        InitialMultilevelBipartitioner inside extend_partition,
        helper.cc:220 — same structure, device-speed hot loops).  Takes
        and returns DEVICE arrays (i32[n_pad], 0/1) — the caller decides
        whether the result ever visits the host."""
        from ..ops.contraction import contract_clustering
        from ..ops.lp import lp_cluster, lp_refine
        from ..ops.subgraphs import host_graph_from_padded

        ctx = self.ctx
        ic = ctx.initial_partitioning.coarsening
        seed = int(rng.integers(0, 2**31 - 1))
        max_w = max_block_weights.astype(np.int64, copy=False)
        mcw = max(1, int(ic.cluster_weight_multiplier * max_w.max()))

        levels = []
        current, cur_n = dg, n
        # hand off to the sequential host pool at the same scale the main
        # pipeline does (deep coarsening threshold = 2 * contraction_limit)
        stop_n = max(2, 2 * ctx.coarsening.contraction_limit)
        while cur_n > stop_n:
            labels = lp_cluster(
                current,
                jnp.asarray(min(mcw, WMAX), dtype=WEIGHT_DTYPE),
                jnp.int32((seed + 31 * len(levels)) & 0x7FFFFFFF),
            )
            coarse, c_n, _ = contract_clustering(current, labels)
            if c_n >= (1.0 - ic.convergence_threshold) * cur_n:
                break
            levels.append((current, coarse))
            current, cur_n = coarse.graph, c_n

        if levels:
            coarsest_host = host_graph_from_device(current)
        elif host_sub is not None:
            coarsest_host = host_sub  # already in hand — no readback
        else:
            coarsest_host = host_graph_from_padded(dg, n, m)
        bp = InitialMultilevelBipartitioner(
            ctx.initial_partitioning
        ).bipartition(coarsest_host, max_w, rng)

        part = np.zeros(current.n_pad, dtype=np.int32)
        part[: coarsest_host.n] = bp
        part = jnp.asarray(part)
        caps = jnp.asarray(np.minimum(max_w, WMAX), dtype=WEIGHT_DTYPE)
        for lvl, (fine_graph, coarse) in enumerate(reversed(levels)):
            part = coarse.project_up(part)
            part = lp_refine(
                fine_graph, part, 2, caps,
                jnp.int32((seed ^ 0x5F3759) + 101 * lvl),
            )
        # Jet polish of the 2-way cut at the subgraph's finest level — the
        # device replacement for the host FM pass the sequential
        # bipartitioner would have run per level (initial_fm_refiner.h:68)
        from ..ops.jet import jet_refine

        return jet_refine(
            dg, part, 2, caps, jnp.int32(seed ^ 0x2545F491),
            ctx.refinement.jet,
        )

    def _current_block_weights(self, k: int):
        ctx = self.ctx
        spans = self._spans
        assert len(spans) == k, (len(spans), k)
        p = ctx.partition
        caps = np.array(
            [
                p.total_max_block_weights(s.first, s.first + s.count)
                for s in spans
            ],
            dtype=np.int64,
        )
        max_bw = jnp.asarray(np.minimum(caps, WMAX), dtype=WEIGHT_DTYPE)
        min_bw = None
        if p.min_block_weights is not None:
            mins = np.array(
                [
                    int(p.min_block_weights[s.first : s.first + s.count].sum())
                    for s in spans
                ],
                dtype=np.int64,
            )
            min_bw = jnp.asarray(np.minimum(mins, WMAX), dtype=WEIGHT_DTYPE)
        return max_bw, min_bw

    def _extend_partition(
        self, dgraph: DeviceGraph, partition, spans, next_k: int, rng
    ):
        """extend_partition (helper.cc:220,349): bipartition each block that
        still spans more than one final block, until current_k == next_k.

        Large levels run the DEVICE extraction (ops/subgraphs.py — no
        full-graph readback); small levels keep the host path, whose
        readback is cheap and whose numpy extraction needs no extra
        device programs.  So does the large-k regime: with hundreds of
        small blocks, per-block device programs would pay the ~87 ms
        launch floor per block — one readback + native bipartitions win.
        STREAMED (compressed) inputs raise the span limit to 128: the
        host readback would blow the compressed-mode memory contract
        (peak RSS tracked 8.4 GB at k=128 through this path), and the
        extra per-block launch floors are what TeraPart parity costs."""
        span_limit = 128 if getattr(self, "_streamed_input", False) else 64
        if (
            dgraph.m_pad >= DEVICE_EXTEND_MIN_EDGE_SLOTS
            and len(spans) <= span_limit
        ):
            return self._extend_partition_device(
                dgraph, partition, spans, next_k, rng
            )
        return self._extend_partition_host(
            dgraph, partition, spans, next_k, rng
        )

    def _extend_partition_device(
        self, dgraph: DeviceGraph, partition, spans, next_k: int, rng
    ):
        """Device-side extend_partition: block-major extraction on device,
        per-block bipartitions (device pipeline for big blocks, host pool
        for small ones — only the small blocks and coarsest sub-levels are
        ever downloaded), partition assembly on device."""
        from ..graphs.csr import shape_floors
        from ..ops.subgraphs import (
            assemble_extended_partition,
            extract_blocks_device,
            host_graph_from_padded,
            scatter_block_bipartition,
            slice_block,
        )

        ctx = self.ctx
        with timer.scoped_timer("extend-partition"):
            current_k = len(spans)
            ext = extract_blocks_device(dgraph, partition, current_k)
            n_floor, m_floor = shape_floors()
            bp_global = jnp.zeros(dgraph.n_pad, dtype=jnp.int32)
            bipartitioner = InitialMultilevelBipartitioner(
                ctx.initial_partitioning
            )
            new_spans: List[_BlockSpan] = []
            base_ids = np.zeros(current_k, dtype=np.int32)
            is_split = np.zeros(current_k, dtype=bool)
            next_id = 0
            for bidx, span in enumerate(spans):
                base_ids[bidx] = next_id
                if span.count <= 1:
                    new_spans.append(span)
                    next_id += 1
                    continue
                is_split[bidx] = True
                sub, n_b, m_b = slice_block(ext, bidx, n_floor, m_floor)
                max_w = bipartition_max_block_weights(
                    ctx, span.first, span.count,
                    int(ext.block_weights[bidx]),
                )
                if n_b >= ctx.partitioning.device_bipartition_threshold:
                    bp = self._device_bipartition_dev(
                        sub, n_b, m_b, max_w, rng
                    )
                else:
                    host_sub = host_graph_from_padded(sub, n_b, m_b)
                    bp_np = bipartitioner.bipartition(host_sub, max_w, rng)
                    padded = np.zeros(sub.n_pad, dtype=np.int32)
                    padded[:n_b] = bp_np
                    bp = jnp.asarray(padded)
                bp_global = scatter_block_bipartition(
                    bp_global, bp, ext.node_start[bidx], jnp.int32(n_b),
                    sub.n_pad,
                )
                k0, k1 = split_k(span.count)
                new_spans.append(_BlockSpan(span.first, k0))
                new_spans.append(_BlockSpan(span.first + k0, k1))
                next_id += 2
            new_part = assemble_extended_partition(
                ext.b, ext.new_id, ext.node_start, bp_global,
                jnp.asarray(base_ids), jnp.asarray(is_split), current_k,
            )
            self._spans = new_spans
            from .. import telemetry

            telemetry.event(
                "extend-partition", k=len(new_spans), extractor="device"
            )
            return new_part, new_spans, len(new_spans)

    def _extend_partition_host(
        self, dgraph: DeviceGraph, partition, spans, next_k: int, rng
    ):
        ctx = self.ctx
        # the host extraction IS the staged boundary: pull graph and
        # partition before opening the span so the timed extension work
        # starts from host arrays; the pull is a readback scope beside it
        with timer.scoped_timer("extend-pull", sync=True):
            host = host_graph_from_device(dgraph)
            part = np.asarray(partition)[: host.n].astype(np.int64)
        with timer.scoped_timer("extend-partition"):
            current_k = len(spans)
            ext = extract_block_subgraphs(host, part, current_k)

            new_spans: List[_BlockSpan] = []
            new_ids_base: List[Tuple[int, int]] = []  # (id0, id1 or -1)
            bipartitioner = InitialMultilevelBipartitioner(
                ctx.initial_partitioning
            )
            sub_parts: List = []
            next_id = 0
            pool_jobs: List[Tuple[int, HostGraph, np.ndarray, int]] = []
            workers = max(1, int(ctx.parallel.num_workers))
            for b, span in enumerate(spans):
                # split only while we have not reached next_k blocks overall
                if span.count > 1:
                    sub = ext.subgraphs[b]
                    max_w = bipartition_max_block_weights(
                        ctx, span.first, span.count, sub.total_node_weight
                    )
                    if sub.n >= ctx.partitioning.device_bipartition_threshold:
                        bp = self._device_bipartition(sub, max_w, rng)
                    elif workers > 1:
                        # per-block seeds are PRE-DRAWN so the result is
                        # identical for any worker-pool size (the
                        # reference's per-PE seed discipline,
                        # initial_bipartitioner_worker_pool.h:42)
                        pool_jobs.append(
                            (len(sub_parts), sub, max_w,
                             int(rng.integers(0, 2**31 - 1)))
                        )
                        bp = None
                    else:
                        # single worker: draw from the shared stream —
                        # bitwise-identical to the pre-pool code path
                        bp = bipartitioner.bipartition(sub, max_w, rng)
                    k0, k1 = split_k(span.count)
                    new_ids_base.append((next_id, next_id + 1))
                    new_spans.append(_BlockSpan(span.first, k0))
                    new_spans.append(_BlockSpan(span.first + k0, k1))
                    sub_parts.append(bp)
                    next_id += 2
                else:
                    new_ids_base.append((next_id, -1))
                    new_spans.append(span)
                    sub_parts.append(None)
                    next_id += 1

            # host-pool bipartitions: independent per block — run them on
            # a worker pool (the native bipartitioner releases the GIL
            # for the duration of the C call, so threads scale on real
            # multi-core hosts; this dev box has ONE logical CPU).
            # Each job gets its OWN bipartitioner (the pool's adaptive
            # per-algorithm stats are not thread-safe) and the global
            # timer is quiesced for the pool phase (its scope stack is
            # shared; the outer extend-partition scope still captures
            # the wall time).
            def run_job(job):
                idx, sub, max_w, s = job
                bip = InitialMultilevelBipartitioner(
                    ctx.initial_partitioning
                )
                return idx, bip.bipartition(
                    sub, max_w, np.random.default_rng(s)
                )

            if len(pool_jobs) > 1:
                from concurrent.futures import ThreadPoolExecutor

                was_enabled = timer.GLOBAL_TIMER.enabled
                timer.GLOBAL_TIMER.enabled = False
                try:
                    with ThreadPoolExecutor(max_workers=workers) as pool:
                        for idx, bp in pool.map(run_job, pool_jobs):
                            sub_parts[idx] = bp
                finally:
                    timer.GLOBAL_TIMER.enabled = was_enabled
            else:
                for job in pool_jobs:
                    idx, bp = run_job(job)
                    sub_parts[idx] = bp

            new_part = np.zeros(host.n, dtype=np.int32)
            for b, span in enumerate(spans):
                mask = part == b
                id0, id1 = new_ids_base[b]
                if id1 < 0:
                    new_part[mask] = id0
                else:
                    bp = sub_parts[b]
                    new_part[mask] = np.where(
                        bp[ext.node_mapping[mask]] == 0, id0, id1
                    )

            padded = np.zeros(dgraph.n_pad, dtype=np.int32)
            padded[: host.n] = new_part
            self._spans = new_spans
            from .. import telemetry

            telemetry.event(
                "extend-partition", k=len(new_spans), extractor="host"
            )
            return jnp.asarray(padded), new_spans, len(new_spans)
