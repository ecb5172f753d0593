"""Rating-engine tests (ops/rating.py): scatter-add slot-table
exactness, engine equivalence vs the sort engine under the shared
tie-break hash, the collision-safe fallback, density-adaptive
selection, the fused-round jaxpr pin, and bench-path dormancy.

The equivalence contract (ISSUE 9): the scatter-add and sort rating
engines pick IDENTICAL clusters given the same tie-break hash — either
because every row is fully rated (slot budget covers the graph) or
because the per-round guard fell back to the sort engine.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaminpar_tpu.graphs import device_graph_from_host, factories
from kaminpar_tpu.ops.lp import LPConfig, lp_cluster
from kaminpar_tpu.ops.rating import (
    best_from_slots,
    best_from_slots_pallas,
    scatter_slot_ratings,
    select_engine,
)


def _slot_bruteforce_ref(dg, labels):
    """Per-(node, label) exact connection sums from the raw edge list."""
    src, dst, ew = (np.asarray(dg.src), np.asarray(dg.dst),
                    np.asarray(dg.edge_w))
    nb = labels[dst]
    ref = {}
    for s, lab, w in zip(src, nb, ew):
        if w:
            ref[(int(s), int(lab))] = ref.get((int(s), int(lab)), 0) + int(w)
    return ref


def test_scatter_slot_ratings_exact_and_flagged():
    """Every rated slot carries the EXACT connection sum, and a
    fully_rated row's slots enumerate every adjacent label."""
    g = factories.make_rmat(256, 2048, seed=9)
    dg = device_graph_from_host(g)
    rng = np.random.default_rng(4)
    labels = np.arange(dg.n_pad, dtype=np.int32)
    labels[: g.n] = rng.integers(0, g.n, g.n)
    nb = jnp.asarray(labels)[dg.dst]
    ref = _slot_bruteforce_ref(dg, labels)
    per_node = {}
    for (u, lab) in ref:
        per_node.setdefault(u, set()).add(lab)
    for S in (8, 64):
        sl, sw, fr = (
            np.asarray(x)
            for x in scatter_slot_ratings(
                dg.src, nb, dg.edge_w, dg.n_pad, S, 17
            )
        )
        for u in range(g.n):
            rated = {}
            for lab, w in zip(sl[u], sw[u]):
                if lab >= 0 and w > 0:
                    # exactness: a rated label's sum is the true sum
                    assert ref[(u, int(lab))] == int(w), (S, u, lab)
                    rated[int(lab)] = int(w)
            if fr[u] and u in per_node:
                # completeness: fully-rated rows rated every label
                assert per_node[u] <= set(rated), (S, u)
        # more slots must not rate fewer rows
    assert fr[: g.n].mean() > 0.5


@pytest.mark.parametrize(
    "make",
    [
        lambda: factories.make_rmat(512, 4096, seed=11),  # degree-skewed
        lambda: factories.make_star(32),                  # hub row
        lambda: factories.make_path(64),                  # unit weights
    ],
    ids=["rmat-skewed", "star", "path-unit"],
)
def test_engine_equivalence_scatter_vs_sort(make):
    """Fully-rated scatter rounds pick the SAME clusters as the sort
    engine (shared tie-break hash), bitwise across the whole
    clustering: rounds, post-passes, convergence."""
    g = make()
    dg = device_graph_from_host(g)
    cap = jnp.int32(max(4, int(g.node_weight_array().sum()) // 12))
    l_sort = np.asarray(
        lp_cluster(dg, cap, jnp.int32(5), LPConfig(rating="sort"))
    )
    l_scat = np.asarray(
        lp_cluster(
            dg, cap, jnp.int32(5),
            LPConfig(rating="scatter", num_slots=256, scatter_fallback=0.0),
        )
    )
    np.testing.assert_array_equal(l_sort, l_scat)


def test_scatter_collision_fallback_is_exact():
    """With a starved slot budget and a zero fallback threshold every
    contested round must take the sort branch — end-to-end output
    bitwise equal to the sort engine's."""
    g = factories.make_rmat(512, 4096, seed=11)
    dg = device_graph_from_host(g)
    l_sort = np.asarray(
        lp_cluster(dg, jnp.int32(40), jnp.int32(5), LPConfig(rating="sort"))
    )
    l_fb = np.asarray(
        lp_cluster(
            dg, jnp.int32(40), jnp.int32(5),
            LPConfig(rating="scatter", num_slots=2, scatter_fallback=0.0),
        )
    )
    np.testing.assert_array_equal(l_sort, l_fb)


def test_scatter_default_quality_and_caps():
    """Default scatter settings: caps respected, graph actually
    coarsens, and the cut-relevant cluster count stays within 2x of the
    exact sort engine's (the hash-engine quality contract, tightened)."""
    g = factories.make_rmat(512, 4096, seed=11)
    dg = device_graph_from_host(g)
    cap = 40
    counts = {}
    for name in ("sort", "scatter"):
        lab = np.asarray(
            lp_cluster(dg, jnp.int32(cap), jnp.int32(5),
                       LPConfig(rating=name))
        )[: g.n]
        w = np.zeros(dg.n_pad, np.int64)
        np.add.at(w, lab, g.node_weight_array())
        assert w.max() <= cap, name
        counts[name] = len(np.unique(lab))
    assert counts["scatter"] <= max(2 * counts["sort"],
                                    counts["sort"] + 64)


def test_scatter_global_label_space():
    """The owner-sharded dist layout rates GLOBAL cluster ids from
    n_loc-row tables: labels beyond the row count must be rated
    verbatim, never clipped into the row domain (which would silently
    merge every remote label into one)."""
    n_rows, label_space = 4, 64
    owner = jnp.array([0, 0, 1, 1, 2], dtype=jnp.int32)
    nb = jnp.array([37, 59, 59, 5, 37], dtype=jnp.int32)
    w = jnp.array([3, 4, 5, 6, 7], dtype=jnp.int32)
    sl, sw, fr = (
        np.asarray(x)
        for x in scatter_slot_ratings(
            owner, nb, w, n_rows, 16, 11, label_space=label_space
        )
    )
    assert fr.all()
    rated = {
        (u, int(lab)): int(wt)
        for u in range(n_rows)
        for lab, wt in zip(sl[u], sw[u])
        if lab >= 0 and wt > 0
    }
    assert rated == {(0, 37): 3, (0, 59): 4, (1, 59): 5, (1, 5): 6,
                     (2, 37): 7}


def test_select_engine_density_rule():
    """The 1402.3281 adaptivity rule: dense for refinement-sized label
    spaces, scatter inside the slot budget, sort2 beyond it (sort when
    the layout has no row spans); forced names pass through."""
    assert select_engine("auto", 16, 1 << 20, 1 << 24)[0] == "dense"
    assert select_engine(
        "auto", 1 << 20, 1 << 20, 1 << 24, num_slots=32,
        degree_skew=400.0,
    )[0] == "scatter"  # avg degree 16, RMAT-class skew
    assert select_engine(
        "auto", 1 << 20, 1 << 14, 1 << 24, num_slots=32,
        degree_skew=400.0,
    )[0] == "sort2"  # avg degree 1024
    assert select_engine(
        "auto", 1 << 20, 1 << 14, 1 << 24, num_slots=32,
        degree_skew=400.0, row_spans=False,
    )[0] == "sort"
    assert select_engine("hash", 16, 1 << 20, 1 << 24)[0] == "hash"
    # low-skew (uniform/geometric) graphs keep sort2: barred tie
    # chains measurably derail their coarsening (see select_engine)
    assert select_engine(
        "auto", 1 << 20, 1 << 20, 1 << 24, num_slots=32,
        avg_degree=8.0, degree_skew=2.5,
    )[0] == "sort2"
    # unmeasured skew defaults conservative (no scatter on the static
    # shape-only path; the coarsener measures and re-resolves)
    assert select_engine(
        "auto", 1 << 20, 1 << 20, 1 << 24, num_slots=32
    )[0] == "sort2"
    # measured stats override the padded-shape approximation
    assert select_engine(
        "auto", 1 << 20, 1 << 20, 1 << 24, num_slots=32,
        avg_degree=500.0, degree_skew=2.0,
    )[0] == "sort2"


def test_fused_round_jaxpr_identical_with_telemetry_idle():
    """The jaxpr pin (ISSUE 9 satellite): the fused scatter round must
    stay BITWISE-identical with progress/perf telemetry off — enabling
    the telemetry layer without capture must not touch the traced
    computation (the PR-4 zero-overhead contract extended to the new
    engine)."""
    import kaminpar_tpu.ops.lp as lp_mod
    from kaminpar_tpu import telemetry

    g = factories.make_rmat(256, 2048, seed=3)
    dg = device_graph_from_host(g)
    cfg = LPConfig(rating="scatter")

    def trace():
        return str(
            jax.make_jaxpr(
                lambda mcw, seed: lp_mod._lp_cluster_fused_rounds(
                    dg, mcw, seed, None, cfg, 4
                )
            )(jnp.int32(40), jnp.int32(1))
        )

    was_enabled = telemetry.enabled()
    try:
        telemetry.disable()
        j_off = trace()
        telemetry.enable()
        j_on = trace()
    finally:
        telemetry.disable() if not was_enabled else telemetry.enable()
    assert j_on == j_off


def test_bench_path_dormancy_wall_bounded():
    """Pin the r05-regression diagnosis (ISSUE 9 satellite): with
    telemetry ON (bench.py's configuration) a clustering emits NO
    per-round host events — only per-call progress series — and the
    perf observatory / memory governor add no per-round host work.  The
    wall bound is deliberately generous: it exists to catch a
    reintroduced per-round host sync (which multiplies wall by the
    round count), not scheduler jitter."""
    from kaminpar_tpu import telemetry
    from kaminpar_tpu.resilience import memory as memory_mod

    g = factories.make_rmat(1 << 11, 20_000, seed=1)
    dg = device_graph_from_host(g)
    cfg = LPConfig(rating="scatter")
    # warm: compile outside the timed region (bench measures min-over-
    # seeds for the same reason)
    jax.block_until_ready(lp_cluster(dg, jnp.int32(64), jnp.int32(1), cfg))
    was_enabled = telemetry.enabled()
    spills = []
    orig_note = memory_mod.note_spill
    memory_mod.note_spill = lambda b: spills.append(b)
    try:
        telemetry.enable()
        telemetry.reset()
        t0 = time.perf_counter()
        jax.block_until_ready(
            lp_cluster(dg, jnp.int32(64), jnp.int32(2), cfg)
        )
        wall = time.perf_counter() - t0
        events = telemetry.events()
        series = telemetry.progress_series()
    finally:
        memory_mod.note_spill = orig_note
        telemetry.enable() if was_enabled else telemetry.disable()
    # one progress series per clustering call (and the execution
    # ledger's one `ledger-transfer` event for pulling it), NO per-round
    # events, no governor work while dormant
    per_call = [e for e in events if e.name == "ledger-transfer"]
    assert len(per_call) <= 1
    assert len(events) == len(per_call), [e.name for e in events]
    assert len(series) <= 1
    assert not spills
    assert wall < 30.0, f"bench-path clustering took {wall:.1f}s"


def test_best_from_slots_pallas_interpret_matches_lax():
    """The optional Pallas rate+argmax core (platform-gated, lax path
    default) computes the same unconstrained best/own values in
    interpret mode."""
    g = factories.make_rmat(128, 1024, seed=7)
    dg = device_graph_from_host(g)
    rng = np.random.default_rng(2)
    labels = np.arange(dg.n_pad, dtype=np.int32)
    labels[: g.n] = rng.integers(0, g.n, g.n)
    lab_j = jnp.asarray(labels)
    nb = lab_j[dg.dst]
    slot_label, slot_w, _ = scatter_slot_ratings(
        dg.src, nb, dg.edge_w, dg.n_pad, 32, 13
    )
    # unconstrained reference via the lax path
    b_ref, w_ref, own_ref = best_from_slots(
        slot_label, slot_w, lab_j,
        jnp.zeros((dg.n_pad,), slot_w.dtype), dg.node_w,
        jnp.zeros((dg.n_pad,), slot_w.dtype), 13, require_fit=False,
    )
    b_pl, w_pl, own_pl = best_from_slots_pallas(
        slot_label, slot_w, lab_j, 13, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(b_ref), np.asarray(b_pl))
    np.testing.assert_array_equal(np.asarray(w_ref), np.asarray(w_pl))
    np.testing.assert_array_equal(np.asarray(own_ref), np.asarray(own_pl))


def test_dist_scatter_engine_valid_and_capped():
    """The scatter engine through shard_map (engine flag threaded via
    the static cfg): valid, cap-respecting clustering on the virtual
    mesh, identical across 1 and 4 devices."""
    from kaminpar_tpu.parallel import (
        dist_graph_from_host,
        dist_lp_cluster,
        make_mesh,
    )

    graph = factories.make_grid_graph(16, 16)
    cfg = LPConfig(rating="scatter")
    outs = []
    for nd in (1, 4):
        mesh = make_mesh(nd)
        dg = dist_graph_from_host(graph, mesh)
        labels = np.asarray(dist_lp_cluster(dg, 40, seed=1, cfg=cfg))
        lab = labels[: graph.n]
        w = np.zeros(labels.shape[0], dtype=np.int64)
        np.add.at(w, lab, graph.node_weight_array()[: graph.n])
        assert w.max() <= 40
        assert len(np.unique(lab)) < graph.n
        outs.append(labels)
