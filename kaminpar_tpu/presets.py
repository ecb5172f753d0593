"""Named presets (analog of kaminpar-shm/presets.cc:18-100).

Each preset builds a fully-populated Context; values mirror the reference's
defaults (presets.cc:102-301) where the corresponding knob exists in the TPU
design.  Reference-only knobs that have no TPU analog (e.g. per-thread
rating-map implementation choices) are intentionally absent — the TPU
equivalents are the bulk-sync LP knobs on LabelPropagationContext.
"""

from __future__ import annotations

import os
from typing import Dict, Set

from .context import (
    ClusterWeightLimit,
    Context,
    PartitioningMode,
    RefinementAlgorithm,
    TwoHopStrategy,
)


def create_default_context() -> Context:
    """presets.cc:102-301 (deep multilevel, LP coarsening) — with two
    TPU-first deviations from the reference's default, both measured on
    RMAT workloads against the reference binary:

      * Jet instead of LP as the default refiner.  The reference's LP
        refiner is asynchronous (moves see the latest labels); the
        bulk-synchronous port needs Jet's afterburner-filtered move
        selection to avoid adjacent-move conflicts, and Jet IS that
        algorithm (jet_refiner.cc:1-8 makes the same argument for GPUs).
        Balancer+LP stays available via the explicit algorithm list.

      * refine_after_extending_partition defaults ON: k-doubling
        extensions otherwise land unrefined on the finest levels, which
        measurably dominates the final cut (together these two flips take
        the RMAT bench cut from ~1.28x of the reference binary to ~0.84x
        — better than the reference)."""
    ctx = Context(preset_name="default")
    # Jet then an afterburned-LP polish pass; two Jet rounds on the
    # finest level.  Measured on the medium RMAT bench (both seeds):
    # ~0.8% lower cut than Jet-only at marginal extra device time.
    ctx.refinement.algorithms = [
        RefinementAlgorithm.OVERLOAD_BALANCER,
        RefinementAlgorithm.UNDERLOAD_BALANCER,
        RefinementAlgorithm.JET,
        RefinementAlgorithm.LABEL_PROPAGATION,
    ]
    ctx.refinement.jet.num_rounds_on_fine_level = 2
    ctx.partitioning.refine_after_extending_partition = True
    return ctx


def create_fast_context() -> Context:
    """presets.cc:301-309: single LP iteration, single IP repetition."""
    ctx = create_default_context()
    ctx.preset_name = "fast"
    ctx.coarsening.clustering.lp.num_iterations = 1
    ctx.initial_partitioning.pool.min_num_repetitions = 1
    ctx.initial_partitioning.pool.min_num_non_adaptive_repetitions = 1
    ctx.initial_partitioning.pool.max_num_repetitions = 1
    ctx.partitioning.light_intermediate_refinement = True
    return ctx


def create_strong_context() -> Context:
    """presets.cc:311-324: adds k-way FM between refinement and final
    balancing (Jet plays the reference's LP slot, see default).  The
    localized batch FM (native/fm.cpp) runs on the finest levels,
    ALTERNATED with Jet — FM escapes Jet's bulk-move local optimum, Jet
    then re-polishes the FM result.  Measured variants on the medium
    bench (docs/performance.md): jet-fm-jet-fm with 3 FM passes and
    light intermediate refinement cuts 2.0% below default (single
    jet+fm: 1.7%; 6 passes or FM on intermediate extensions buy nothing
    further; a doubled Jet budget instead buys nothing at all)."""
    ctx = create_default_context()
    ctx.preset_name = "strong"
    ctx.refinement.algorithms = [
        RefinementAlgorithm.OVERLOAD_BALANCER,
        RefinementAlgorithm.UNDERLOAD_BALANCER,
        RefinementAlgorithm.JET,
        RefinementAlgorithm.GREEDY_FM,
        RefinementAlgorithm.JET,
        RefinementAlgorithm.GREEDY_FM,
        RefinementAlgorithm.OVERLOAD_BALANCER,
        RefinementAlgorithm.UNDERLOAD_BALANCER,
    ]
    # intermediate extensions get single-round Jet and skip FM; the
    # final extension's refine at each level is the real polish
    ctx.partitioning.light_intermediate_refinement = True
    return ctx


def host_worker_count() -> int:
    """The host cores this process may run on, less one for the thread
    that dispatches to the device; at least 1."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # no affinity mask on this platform
        cores = os.cpu_count() or 1
    return max(1, cores - 1)


def create_strong_parallel_context() -> Context:
    """`strong` on every host core, as the reference runs `-P strong -t
    <cores>`: the host k-way FM's worker pool (native/fm.cpp) at every
    level and k, and the host extend's bipartition pool
    (partitioning/deep.py), get `host_worker_count()` threads.  Neither
    pool's answer depends on its size or timing, so a replay returns the
    same partition; it is another partition than `strong`'s (FM grows a
    round's regions against one state, the extend draws per-block
    seeds).  Two callers: the benchmark's `delaunay-n17-strong-parallel`
    configuration, and any user who passes the name."""
    ctx = create_strong_context()
    ctx.preset_name = "strong-parallel"
    ctx.parallel.num_workers = host_worker_count()
    return ctx


def create_largek_context() -> Context:
    """presets.cc:326-334: fewer IP repetitions for huge k.  Refinement
    avoids every dense (n, k) structure — Jet's connection table cannot
    exist at the reference's k=30,000 claim (README.MD:17); LP refinement
    rates through the sort engine and the balancers switch to edge
    aggregation above ops/balancer.BALANCER_DENSE_MAX_K."""
    ctx = create_default_context()
    ctx.preset_name = "largek"
    ctx.initial_partitioning.pool.min_num_repetitions = 4
    ctx.initial_partitioning.pool.min_num_non_adaptive_repetitions = 2
    ctx.initial_partitioning.pool.max_num_repetitions = 4
    ctx.refinement.algorithms = [
        RefinementAlgorithm.OVERLOAD_BALANCER,
        RefinementAlgorithm.UNDERLOAD_BALANCER,
        RefinementAlgorithm.LABEL_PROPAGATION,
    ]
    return ctx


def create_largek_fast_context() -> Context:
    ctx = create_largek_context()
    ctx.preset_name = "largek-fast"
    pool = ctx.initial_partitioning.pool
    pool.min_num_repetitions = 2
    pool.min_num_non_adaptive_repetitions = 1
    pool.max_num_repetitions = 2
    pool.enable_ggg_bipartitioner = False
    pool.refinement.disabled = True
    pool.refinement.num_iterations = 1
    return ctx


def create_largek_strong_context() -> Context:
    ctx = create_largek_context()
    ctx.preset_name = "largek-strong"
    ctx.refinement.algorithms = [
        RefinementAlgorithm.OVERLOAD_BALANCER,
        RefinementAlgorithm.UNDERLOAD_BALANCER,
        RefinementAlgorithm.LABEL_PROPAGATION,
        RefinementAlgorithm.GREEDY_FM,
        RefinementAlgorithm.OVERLOAD_BALANCER,
        RefinementAlgorithm.UNDERLOAD_BALANCER,
    ]
    return ctx


def create_jet_context(rounds: int = 1) -> Context:
    """presets.cc:372-391: Jet instead of LP refinement — the preset most
    aligned with the TPU execution model."""
    ctx = create_default_context()
    ctx.preset_name = "jet" if rounds == 1 else f"{rounds}xjet"
    ctx.refinement.algorithms = [
        RefinementAlgorithm.OVERLOAD_BALANCER,
        RefinementAlgorithm.UNDERLOAD_BALANCER,
        RefinementAlgorithm.JET,
    ]
    if rounds > 1:
        jet = ctx.refinement.jet
        jet.num_rounds_on_coarse_level = rounds
        jet.num_rounds_on_fine_level = rounds
        jet.initial_gain_temp_on_coarse_level = 0.75
        jet.initial_gain_temp_on_fine_level = 0.75
        jet.final_gain_temp_on_coarse_level = 0.25
        jet.final_gain_temp_on_fine_level = 0.25
    return ctx


def create_noref_context() -> Context:
    ctx = create_default_context()
    ctx.preset_name = "noref"
    ctx.refinement.algorithms = []
    return ctx


def create_vcycle_context(restrict_refinement: bool = False) -> Context:
    """presets.cc:422-436."""
    ctx = create_default_context()
    ctx.preset_name = "restricted-vcycle" if restrict_refinement else "vcycle"
    ctx.partitioning.mode = PartitioningMode.VCYCLE
    if restrict_refinement:
        ctx.partitioning.restrict_vcycle_refinement = True
        ctx.refinement.algorithms = [RefinementAlgorithm.LABEL_PROPAGATION]
    return ctx


def _terapartify(ctx: Context) -> Context:
    """presets.cc terapartify_context: enable compressed-graph mode."""
    ctx.compression.enabled = True
    ctx.preset_name = "terapart"
    return ctx


def create_terapart_context() -> Context:
    return _terapartify(create_default_context())


def create_terapart_strong_context() -> Context:
    ctx = _terapartify(create_strong_context())
    ctx.preset_name = "terapart-strong"
    return ctx


def create_terapart_largek_context() -> Context:
    ctx = _terapartify(create_largek_context())
    ctx.preset_name = "terapart-largek"
    ctx.coarsening.clustering.forced_kc_level = True
    return ctx


def create_esa21_smallk_context() -> Context:
    """presets.cc create_esa21_smallk_context: the ESA'21 configuration.
    The reference switches to BUFFERED contraction + single-phase LP; the
    TPU kernels have one contraction and one LP implementation, so this is
    the default pipeline under the historical name."""
    ctx = create_default_context()
    ctx.preset_name = "esa21-smallk"
    return ctx


def create_esa21_largek_context() -> Context:
    ctx = create_esa21_smallk_context()
    ctx.preset_name = "esa21-largek"
    ctx.initial_partitioning.pool.min_num_repetitions = 4
    ctx.initial_partitioning.pool.min_num_non_adaptive_repetitions = 2
    ctx.initial_partitioning.pool.max_num_repetitions = 4
    return ctx


def create_esa21_largek_fast_context() -> Context:
    ctx = create_esa21_largek_context()
    ctx.preset_name = "esa21-largek-fast"
    pool = ctx.initial_partitioning.pool
    pool.min_num_repetitions = 2
    pool.min_num_non_adaptive_repetitions = 1
    pool.max_num_repetitions = 2
    return ctx


def create_esa21_strong_context() -> Context:
    ctx = create_esa21_smallk_context()
    ctx.preset_name = "esa21-strong"
    ctx.refinement.algorithms = [
        RefinementAlgorithm.OVERLOAD_BALANCER,
        RefinementAlgorithm.UNDERLOAD_BALANCER,
        RefinementAlgorithm.LABEL_PROPAGATION,
        RefinementAlgorithm.GREEDY_FM,
        RefinementAlgorithm.OVERLOAD_BALANCER,
        RefinementAlgorithm.UNDERLOAD_BALANCER,
    ]
    return ctx


def create_linear_time_kway_context() -> Context:
    """presets.cc create_linear_time_kway_context: mtkahypar-kway with
    sparsification clustering (linear-time MGP, arXiv 2504.17615)."""
    from .context import CoarseningAlgorithm

    ctx = create_mtkahypar_kway_context()
    ctx.preset_name = "linear-time-kway"
    ctx.coarsening.algorithm = CoarseningAlgorithm.SPARSIFICATION_CLUSTERING
    return ctx


def create_mtkahypar_kway_context() -> Context:
    """presets.cc:488-499: Mt-KaHyPar-style coarsening + direct k-way."""
    ctx = create_default_context()
    ctx.preset_name = "mtkahypar-kway"
    cl = ctx.coarsening.clustering
    cl.lp.num_iterations = 1
    cl.cluster_weight_limit = ClusterWeightLimit.BLOCK_WEIGHT
    cl.cluster_weight_multiplier = 1.0 / 160.0
    cl.shrink_factor = 2.5
    cl.lp.two_hop_strategy = TwoHopStrategy.CLUSTER
    ctx.coarsening.contraction_limit = 160
    ctx.partitioning.mode = PartitioningMode.KWAY
    return ctx


_PRESETS = {
    "default": create_default_context,
    "fast": create_fast_context,
    "strong": create_strong_context,
    "strong-parallel": create_strong_parallel_context,
    "fm": create_strong_context,
    "largek": create_largek_context,
    "largek-fast": create_largek_fast_context,
    "largek-strong": create_largek_strong_context,
    "terapart": create_terapart_context,
    "terapart-strong": create_terapart_strong_context,
    "terapart-largek": create_terapart_largek_context,
    "jet": create_jet_context,
    "4xjet": lambda: create_jet_context(4),
    "noref": create_noref_context,
    "vcycle": lambda: create_vcycle_context(False),
    "restricted-vcycle": lambda: create_vcycle_context(True),
    "esa21": create_esa21_smallk_context,
    "esa21-smallk": create_esa21_smallk_context,
    "esa21-largek": create_esa21_largek_context,
    "esa21-largek-fast": create_esa21_largek_fast_context,
    "esa21-strong": create_esa21_strong_context,
    "diss": create_esa21_smallk_context,
    "diss-smallk": create_esa21_smallk_context,
    "diss-largek": create_esa21_largek_context,
    "diss-largek-fast": create_esa21_largek_fast_context,
    "diss-strong": create_esa21_strong_context,
    "mtkahypar-kway": create_mtkahypar_kway_context,
    "linear-time-kway": create_linear_time_kway_context,
}


def create_context_by_preset_name(name: str) -> Context:
    """presets.cc:18-73."""
    if name not in _PRESETS:
        raise ValueError(
            f"invalid preset name: {name!r} (available: {sorted(_PRESETS)})"
        )
    return _PRESETS[name]()


def get_preset_names() -> Set[str]:
    """presets.cc:76-99."""
    return set(_PRESETS)
