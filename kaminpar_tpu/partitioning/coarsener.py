"""Device multilevel coarsener.

Analog of kaminpar-shm/coarsening/abstract_cluster_coarsener.cc (+
BasicClusterCoarsener): drives lp_cluster -> contract_clustering level by
level, keeps the hierarchy for projection, applies the max-cluster-weight
formula (max_cluster_weights.h) and the shrink/convergence checks
(abstract_cluster_coarsener.cc:98-147).
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from functools import partial

import jax

from ..context import Context
from ..graphs.csr import DeviceGraph, WEIGHT_DTYPE
from ..ops.contraction import CoarseGraph, contract_clustering
from ..ops.lp import LPConfig, lp_cluster
from ..utils import timer


# level-handoff projection with the coarse partition donated: when fine
# and coarse levels share a pad bucket (same n_pad), the projected fine
# partition aliases the dead coarse buffer instead of allocating a new
# one.  Only dispatched when shapes actually permit aliasing (the
# caller checks), so XLA never warns about unusable donations; the
# execution ledger's donation audit verifies it was honored.
@partial(jax.jit, donate_argnums=(0,))
def _project_partition_donated(partition, cmap):
    return partition[cmap]


@contextmanager
def _lp_clustering_scope(engine: str):
    """The `lp-clustering` timer scope with the level's resolved rating
    engine as a scope of its own directly under it (`rating-sort2`,
    `rating-scatter`, ...): telemetry is off in a measured run, and the
    scope, a span in a profiler trace, still says which engine a level
    ran and for how long."""
    with timer.scoped_timer("lp-clustering"):
        with timer.scoped_timer(f"rating-{engine}"):
            yield


@dataclass
class CoarseningLevel:
    """One hierarchy step.  ``fine_graph``/``coarse`` may be None while
    the level is host-spilled (``spilled`` then holds the coarse host
    CSR + cmap + pad bucket; resilience/memory.py rung 2) — the
    coarsener restores them on demand during uncoarsening."""

    fine_graph: Optional[DeviceGraph]
    coarse: Optional[CoarseGraph]
    fine_n: int
    coarse_n: int
    coarse_m: int
    spilled: Optional[dict] = None


class Coarsener:
    """Cluster coarsener with hierarchy (Coarsener interface,
    kaminpar-shm/coarsening/coarsener.h:20-88)."""

    def __init__(self, ctx: Context, graph: DeviceGraph, n: int):
        self.ctx = ctx
        self.levels: List[CoarseningLevel] = []
        self.current = graph
        self.current_n = n
        # the input level (level 0's fine graph) — uncoarsening falls
        # back to it when the hierarchy below has been host-spilled
        self._input_graph = graph
        # memory governor (resilience/memory.py): the active hierarchy
        # registers as the run's spill target so the barrier pressure
        # hook can shed cold levels; no-op while the governor is dormant
        from ..resilience import memory as memory_mod

        memory_mod.register_spiller(self)
        self.total_node_weight = int(ctx.partition.total_node_weight)
        lp_ctx = ctx.coarsening.clustering.lp
        from ..context import IsolatedNodesStrategy, TwoHopStrategy

        self._lp_cfg = LPConfig(
            num_iterations=lp_ctx.num_iterations,
            participation=lp_ctx.participation,
            allow_tie_moves=lp_ctx.allow_tie_moves,
            use_active_set=lp_ctx.use_active_set,
            two_hop=lp_ctx.two_hop_strategy != TwoHopStrategy.DISABLE,
            cluster_isolated=lp_ctx.isolated_nodes_strategy
            != IsolatedNodesStrategy.KEEP,
            rating=lp_ctx.rating,
            num_slots=lp_ctx.rating_slots,
        )

    def _level_lp_cfg(self, graph: DeviceGraph) -> LPConfig:
        """Per-level rating-engine selection from MEASURED density and
        degree skew (the 1402.3281 adaptivity rule, ops/rating.py).

        Host-side, between launches: n/m are level metadata the driver
        already holds, the max degree is one scalar readback off the
        degrees array the graph already carries.  The chosen engine is
        stamped into the level's LPConfig (trace-time static, so each
        shape bucket compiles the engine it will actually run) and
        exposed as a `rating-engine` telemetry event -> the run
        report's `rating` section."""
        from dataclasses import replace

        from ..ops.rating import select_engine

        # REAL sizes only — never padded shapes: the memory governor's
        # recovery ladder re-buckets the same graph into tighter pads,
        # and a pad-sensitive engine choice would make spilled/reloaded
        # runs diverge from unspilled ones (rung-2 cut-identity test)
        n = max(int(self.current_n), 1)
        m = int(graph.m) or int(graph.src.shape[0])
        avg_degree = m / n
        max_degree = int(jnp.max(graph.degrees))
        degree_skew = max_degree / max(avg_degree, 1e-9)
        engine, reason = select_engine(
            self._lp_cfg.rating, graph.n_pad, n, m,
            num_slots=self._lp_cfg.num_slots,
            avg_degree=avg_degree, degree_skew=degree_skew,
        )
        from .. import telemetry

        telemetry.event(
            "rating-engine",
            level=self.level,
            engine=engine,
            reason=reason,
            avg_degree=round(avg_degree, 2),
            degree_skew=round(degree_skew, 2),
            n=n,
            m=int(graph.m),
        )
        # the RESOLVED engine name is stamped (a handful of distinct
        # cfg values across the hierarchy), never the raw float stats —
        # LPConfig is a static jit argument and a per-level float would
        # force a retrace per level.  The slot budget steps with the
        # measured density (quantized to two values for the same
        # retrace reason): denser levels contest more slots, and a
        # doubled budget costs less than the fallback rounds it avoids
        # (measured on the 600k bench: S=64 at avg degree 18 is both
        # faster and coarsens further than S=32).
        slots = self._lp_cfg.num_slots
        if (
            engine == "scatter"
            and avg_degree > slots / 2
            and 4 * n * slots <= 12 * m  # doubled table stays in budget
        ):
            slots = 2 * slots
        return replace(self._lp_cfg, rating=engine, num_slots=slots)

    @property
    def level(self) -> int:
        return len(self.levels)

    def empty(self) -> bool:
        return not self.levels

    def coarsen(self) -> bool:
        """One coarsening step; returns False when converged (shrink factor
        below convergence_threshold, abstract_cluster_coarsener.cc:118-142)."""
        from ..telemetry import progress as progress_mod

        # label this level's LP progress series (the timer path alone
        # repeats across levels; PASCO-style coarsening-quality curves
        # need the level number)
        with progress_mod.tag(level=self.level):
            return self._coarsen_level()

    def _coarsen_level(self) -> bool:
        c_ctx = self.ctx.coarsening
        max_cluster_weight = max(
            1,
            c_ctx.max_cluster_weight(
                self.current_n, self.total_node_weight, self.ctx.partition
            ),
        )
        seed = jnp.int32(
            (self.ctx.seed * 7919 + self.level * 31337) & 0x7FFFFFFF
        )
        from ..context import CoarseningAlgorithm

        cluster_input = self.current
        if (
            c_ctx.algorithm == CoarseningAlgorithm.SPARSIFICATION_CLUSTERING
            and int(self.current.m) > (1 << 16)
        ):
            # linear-time MGP: cluster on a sparsified copy to bound LP
            # work, but contract the TRUE graph — the hierarchy must hold
            # unmutated graphs (the reference likewise never sparsifies the
            # input level, sparsification_cluster_coarsener.cc)
            from ..ops.sparsify import sparsify_edges

            with timer.scoped_timer("sparsification"):
                cluster_input = sparsify_edges(
                    self.current,
                    jnp.float32(c_ctx.sparsification_keep_ratio),
                    seed ^ jnp.int32(0x51A5),
                )
        mcw = jnp.asarray(
            min(max_cluster_weight, int(jnp.iinfo(WEIGHT_DTYPE).max)),
            dtype=WEIGHT_DTYPE,
        )
        # density-adaptive rating engine for THIS level, from the graph
        # actually being clustered (the sparsified copy when active)
        lp_cfg = self._level_lp_cfg(cluster_input)

        def cluster_once(cap, salt_off):
            if c_ctx.algorithm == CoarseningAlgorithm.OVERLAY_CLUSTERING:
                # OverlayClusterCoarsener (PASCO): intersect several
                # independent clusterings — nodes merge only when every
                # clustering agrees, which guards quality on hard instances
                from ..ops.segments import combine_labels

                labels = None
                for r in range(max(1, c_ctx.clustering.num_overlays)):
                    li = lp_cluster(
                        cluster_input, cap,
                        seed + jnp.int32(7 * r + 1 + salt_off),
                        lp_cfg,
                    )
                    labels = (
                        li if labels is None else combine_labels(labels, li)
                    )
                return labels
            return lp_cluster(
                cluster_input, cap, seed + jnp.int32(salt_off), lp_cfg
            )

        # dispatch is async and block_until_ready is unreliable over the
        # remote backend; a scalar readback inside the scope keeps the
        # LP/contraction attribution honest (otherwise the first host
        # sync in contract_clustering absorbs the whole LP runtime).
        # Only worth a host round-trip when the timer actually records.
        def drain(x):
            if timer.GLOBAL_TIMER.enabled:
                int(jnp.sum(x[:1]))

        with _lp_clustering_scope(lp_cfg.rating):
            labels = cluster_once(mcw, 0)
            drain(labels)
        with timer.scoped_timer("contraction"):
            coarse, c_n, c_m = contract_clustering(self.current, labels)

        # forced-shrink retries (abstract_cluster_coarsener.cc:118-142
        # shrink-factor logic): when clustering stalls but the graph is
        # still far above the contraction limit, relax the cluster weight
        # cap and re-cluster with the SAME configured clusterer — a
        # stalled hierarchy otherwise leaves a huge "coarsest" graph for
        # the sequential initial partitioner
        retries = 0
        while (
            c_n >= (1.0 - c_ctx.convergence_threshold) * self.current_n
            and self.current_n > 4 * c_ctx.contraction_limit
            and retries < 3
        ):
            retries += 1
            mcw = jnp.asarray(
                min(int(mcw) * 2, int(jnp.iinfo(WEIGHT_DTYPE).max)),
                dtype=WEIGHT_DTYPE,
            )
            with _lp_clustering_scope(lp_cfg.rating):
                labels = cluster_once(mcw, retries * 977)
                drain(labels)
            with timer.scoped_timer("contraction"):
                coarse, c_n, c_m = contract_clustering(self.current, labels)

        if (
            c_n >= (1.0 - c_ctx.convergence_threshold) * self.current_n
            and self.current_n > 4 * c_ctx.contraction_limit
        ):
            # last resort before declaring convergence: the hashed-slot
            # engine sees 32 candidate clusters per node where sort2's
            # top-K sees K — on dense near-cap coarse graphs that extra
            # visibility often finds the feasible merges that unstick a
            # limping hierarchy (each extra level costs a full refine
            # pass downstream)
            import dataclasses

            hash_cfg = dataclasses.replace(self._lp_cfg, rating="hash")
            with _lp_clustering_scope(hash_cfg.rating):
                labels = lp_cluster(
                    cluster_input, mcw, seed + jnp.int32(3989), hash_cfg
                )
                drain(labels)
            with timer.scoped_timer("contraction"):
                coarse, c_n, c_m = contract_clustering(self.current, labels)

        if c_n >= (1.0 - c_ctx.convergence_threshold) * self.current_n:
            # converged: drop this level (not enough shrinkage)
            return False
        if (
            c_n >= (1.0 - c_ctx.stall_threshold) * self.current_n
            and self.current_n <= 8 * c_ctx.contraction_limit
        ):
            # limping tail cutoff: near the contraction limit, dense
            # near-cap graphs shrink only ~6-8% per level while every
            # accepted level costs a full refine pass (Jet + LP + a
            # contraction + fresh executables) during uncoarsening —
            # profiled at the 10M bench as the dominant systemic cost.
            # The host initial-partitioning pool handles a 10-16k-node
            # coarsest graph directly, so declare convergence instead of
            # limping to the threshold.
            return False
        # integrity sentinels (resilience/integrity.py): corruption
        # chaos first — `bit-flip:contraction` genuinely mutates a
        # coarse edge weight in flight — then the conservation / range /
        # surjectivity / symmetry checks on the accepted level.  A
        # violation fires BEFORE this level's barrier, so the manifest
        # still points at the last clean one and the retry ladder
        # (integrity.run_with_retry) resumes there.  One separate small
        # jitted reduction, host compares; the LP/contraction jaxprs
        # above are untouched whether integrity is on or off.
        from ..resilience import integrity as integrity_mod

        coarse = integrity_mod.chaos_corrupt_contraction(coarse)
        integrity_mod.check_contraction(
            self.current, coarse.cmap, coarse.graph,
            level=self.level, fine_n=self.current_n, coarse_n=c_n,
        )
        self.levels.append(
            CoarseningLevel(
                fine_graph=self.current,
                coarse=coarse,
                fine_n=self.current_n,
                coarse_n=c_n,
                coarse_m=c_m,
            )
        )
        self.current = coarse.graph
        self.current_n = c_n
        from .. import telemetry

        # per-level resident-buffer accounting (perf.memory.levels):
        # padded shapes and total device-array bytes of the coarse CSR —
        # all host-side array metadata, never a device sync
        g = coarse.graph
        telemetry.event(
            "coarsening-level",
            level=self.level,
            n=int(c_n),
            m=int(c_m),
            retries=retries,
            n_pad=int(g.node_w.shape[0]),
            m_pad=int(g.dst.shape[0]),
            buffer_bytes=int(
                g.row_ptr.nbytes + g.src.nbytes + g.dst.nbytes
                + g.edge_w.nbytes + g.node_w.nbytes
                + coarse.cmap.nbytes
            ),
        )
        # quality observatory (telemetry/quality.py): per-level
        # coarsening-quality metrics — internalized edge weight, cluster
        # sizes vs the cap, weight skew.  A separate small reduction
        # pulled host-side between launches; no-op while disabled, and
        # the LP/contraction jaxprs above are untouched either way.
        from ..telemetry import quality as quality_mod

        quality_mod.note_contraction(
            level=self.level,
            fine_graph=self.levels[-1].fine_graph,
            coarse=coarse,
            fine_n=self.levels[-1].fine_n,
            coarse_n=c_n,
            coarse_m=c_m,
            max_cluster_weight=mcw,
            total_node_weight=self.total_node_weight,
        )
        return True

    def uncoarsen(self, partition: jnp.ndarray) -> Tuple[DeviceGraph, jnp.ndarray]:
        """Pop one level; project the coarse partition up
        (abstract_cluster_coarsener.cc:149-171).  Returns (fine graph,
        fine partition).

        Host-spilled levels are transparently restored: the level below
        (whose coarse graph IS this level's fine graph) is re-uploaded
        into its original pad bucket, and a spilled projection map is
        used straight from the host copy — the projection gather and the
        restored arrays are bitwise-identical to the unspilled run
        (deterministic buckets), so spill/reload is cut-neutral."""
        if len(self.levels) >= 2:
            # the popped level's fine graph lives in the level below
            self._restore_level(len(self.levels) - 2)
        level = self.levels.pop()
        if level.coarse is not None:
            cmap = level.coarse.cmap
        else:
            cmap = jnp.asarray(
                np.asarray(level.spilled["cmap"], dtype=np.int32)
            )
            from ..resilience import memory as memory_mod

            memory_mod.note_reload(int(cmap.nbytes))
        fine = level.fine_graph
        if fine is None:
            fine = (
                self.levels[-1].coarse.graph
                if self.levels else self._input_graph
            )
        # quality observatory: the popped contraction's projection map,
        # host-copied here where it is already in hand (spilled levels
        # are host-side already) — finalize composes these into the
        # coarsening floors.  No-op while disabled.
        from ..telemetry import quality as quality_mod

        quality_mod.note_cmap(
            level=len(self.levels) + 1, cmap=cmap, fine_n=level.fine_n
        )
        if (
            partition.shape == cmap.shape
            and not isinstance(partition, jax.core.Tracer)
        ):
            # same pad bucket: the dead coarse partition's buffer can
            # back the projected fine partition (donation audited)
            from ..telemetry import ledger

            tok = ledger.donation_begin((partition,),
                                        kind="level-handoff")
            fine_part = _project_partition_donated(partition, cmap)
            ledger.donation_end(tok)
        else:
            fine_part = partition[cmap]
        self.current = fine
        self.current_n = level.fine_n
        return fine, fine_part

    # -- host spill / reload (resilience/memory.py rung 2) --------------

    def _level_device_bytes(self, lvl: CoarseningLevel) -> int:
        g = lvl.coarse.graph
        return int(
            g.row_ptr.nbytes + g.src.nbytes + g.dst.nbytes
            + g.edge_w.nbytes + g.node_w.nbytes + lvl.coarse.cmap.nbytes
        )

    def spill_cold_levels(self, keep_last: int = 1) -> int:
        """Serialize every hierarchy level except the newest
        ``keep_last`` as host CSR + cmap and DROP their device arrays
        (the working graph and the checkpoint payload's newest level
        stay resident).  Returns the device bytes freed.  Called by the
        barrier pressure hook (proactively, under budget pressure) and
        unconditionally at rung >= 2."""
        freed = 0
        for i in range(len(self.levels) - max(0, keep_last)):
            lvl = self.levels[i]
            if lvl.coarse is None or lvl.spilled is not None:
                continue
            freed += self._spill_level(i)
        return freed

    def _spill_level(self, i: int) -> int:
        from ..graphs.csr import host_graph_from_device
        from ..resilience import memory as memory_mod

        lvl = self.levels[i]
        g = lvl.coarse.graph
        nbytes = self._level_device_bytes(lvl)
        hg = host_graph_from_device(g)
        lvl.spilled = {
            "xadj": hg.xadj,
            "adjncy": hg.adjncy,
            "node_w": hg.node_weight_array(),
            "edge_w": hg.edge_weight_array(),
            "cmap": np.asarray(lvl.coarse.cmap),
            "n_pad": int(g.n_pad),
            "m_pad": int(g.m_pad),
        }
        # drop the device arrays: this level's coarse graph is also the
        # next level's fine graph (same object) — both refs must go or
        # nothing is freed
        lvl.coarse = None
        if i + 1 < len(self.levels):
            self.levels[i + 1].fine_graph = None
        memory_mod.note_spill(nbytes)
        from .. import telemetry

        telemetry.event(
            "memory-spill", level=i, bytes=nbytes,
            n=lvl.coarse_n, m=lvl.coarse_m,
        )
        return nbytes

    def _restore_level(self, i: int) -> None:
        """Re-upload a spilled level into its ORIGINAL pad bucket (the
        explicit n_pad/m_pad recorded at spill time, so cmaps and
        partitions line up slot-for-slot whatever pad policy is active
        now)."""
        lvl = self.levels[i]
        if lvl.coarse is not None:
            return
        from ..graphs.csr import device_graph_from_host
        from ..graphs.host import HostGraph
        from ..resilience import memory as memory_mod

        sp = lvl.spilled
        edge_w = sp["edge_w"]
        hg = HostGraph(
            xadj=sp["xadj"],
            adjncy=sp["adjncy"],
            node_weights=sp["node_w"],
            edge_weights=edge_w if edge_w.size else None,
        )
        dg = device_graph_from_host(
            hg, n_pad=sp["n_pad"], m_pad=sp["m_pad"]
        )
        lvl.coarse = CoarseGraph(
            graph=dg,
            cmap=jnp.asarray(np.asarray(sp["cmap"], dtype=np.int32)),
        )
        lvl.spilled = None
        if i + 1 < len(self.levels):
            self.levels[i + 1].fine_graph = dg
        nbytes = self._level_device_bytes(lvl)
        memory_mod.note_reload(nbytes)
        from .. import telemetry

        telemetry.event(
            "memory-reload", level=i, bytes=nbytes,
            n=lvl.coarse_n, m=lvl.coarse_m,
        )


# ---------------------------------------------------------------------------
# hierarchy checkpointing (resilience/checkpoint.py): one coarsening level
# serialized as its coarse host CSR + projection map, and the inverse —
# shared by the deep and kway drivers
# ---------------------------------------------------------------------------


def newest_level_snapshot(coarsener: Coarsener) -> dict:
    """Serialize the just-contracted level: the coarse graph's host CSR
    plus the fine->coarse projection map — everything a resume needs to
    rebuild this hierarchy step without re-clustering/re-contracting.
    Pulls the level off device; call only with checkpointing enabled."""
    from ..graphs.csr import host_graph_from_device

    lvl = coarsener.levels[-1]
    hg = host_graph_from_device(lvl.coarse.graph)
    return {
        "xadj": hg.xadj,
        "adjncy": hg.adjncy,
        "node_w": hg.node_weight_array(),
        "edge_w": hg.edge_weight_array(),
        "cmap": np.asarray(lvl.coarse.cmap),
        "dims": np.asarray(
            [lvl.fine_n, lvl.coarse_n, lvl.coarse_m], dtype=np.int64
        ),
        # the pad bucket the saved cmap was sized for: a resume must
        # re-upload into exactly this bucket even when the recovery
        # ladder has switched the ambient pad policy (rung >= 1)
        "pads": np.asarray(
            [lvl.coarse.graph.n_pad, lvl.coarse.graph.m_pad],
            dtype=np.int64,
        ),
    }


def restore_levels(coarsener: Coarsener, dgraph: DeviceGraph, arrays: dict) -> int:
    """Rebuild the coarsener hierarchy from `level-<i>` snapshots:
    re-upload each saved coarse CSR and reattach the projection maps.
    Snapshots record their pad bucket (`pads`), so the rebuilt device
    graphs land in exactly the buckets the saved cmaps/partitions were
    sized for even when the memory governor's ladder has switched the
    ambient pad policy; pre-`pads` snapshots fall back to the
    deterministic default policy (graphs/csr.pad_size) that wrote them.
    Returns the number of levels restored."""
    from ..graphs.csr import device_graph_from_host
    from ..graphs.host import HostGraph
    from ..ops.contraction import CoarseGraph

    level_names = sorted(
        (nm for nm in arrays if nm.startswith("level-")),
        key=lambda s: int(s.split("-", 1)[1]),
    )
    graphs = [dgraph]
    for nm in level_names:
        a = arrays[nm]
        fine_n, coarse_n, coarse_m = (int(x) for x in a["dims"])
        hg = HostGraph(
            xadj=a["xadj"],
            adjncy=a["adjncy"],
            node_weights=a["node_w"],
            edge_weights=a["edge_w"] if a["edge_w"].size else None,
        )
        if "pads" in a:
            n_pad, m_pad = (int(x) for x in a["pads"])
            dg = device_graph_from_host(hg, n_pad=n_pad, m_pad=m_pad)
        else:
            dg = device_graph_from_host(hg)
        coarse = CoarseGraph(
            graph=dg,
            cmap=jnp.asarray(np.asarray(a["cmap"], dtype=np.int32)),
        )
        coarsener.levels.append(
            CoarseningLevel(
                fine_graph=graphs[-1],
                coarse=coarse,
                fine_n=fine_n,
                coarse_n=coarse_n,
                coarse_m=coarse_m,
            )
        )
        graphs.append(dg)
    if coarsener.levels:
        coarsener.current = graphs[-1]
        coarsener.current_n = coarsener.levels[-1].coarse_n
    return len(level_names)
