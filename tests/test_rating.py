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

import dataclasses
import hashlib

from kaminpar_tpu.graphs import device_graph_from_host, factories
from kaminpar_tpu.ops import rating
from kaminpar_tpu.ops.lp import LPConfig, lp_cluster, lp_round
from kaminpar_tpu.ops.rating import (
    best_from_slots,
    scatter_slot_ratings,
    select_engine,
)
from kaminpar_tpu.ops.segments import (
    ACC_DTYPE,
    INT32_MIN,
    expand_active_rows,
    hash_u32,
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


# ---------------------------------------------------------------------------
# feasibility at the edges (PR 27) against the table-side plain reference
# ---------------------------------------------------------------------------


def _table_side_best(slot_label, slot_w, labels, cluster_weights, node_w,
                     cap, tie_salt, communities=None, label_range=None):
    """The plain reference: best_from_slots as it stood before PR 27,
    which asked the FINISHED table whether each slot's cluster has room
    (two gathers of n_pad * 2 * num_slots indices, three with
    communities) where scatter_slot_ratings' `joinable` now decides it
    per edge.  Kept verbatim; takes a table built WITHOUT `joinable`."""
    n_pad = slot_label.shape[0]
    C = cluster_weights.shape[0]
    lab_c = jnp.clip(slot_label, 0, C - 1)
    own = labels[:, None]
    w_own = jnp.max(jnp.where(slot_label == own, slot_w, 0), axis=1)
    feas = (slot_label >= 0) & (slot_label != own)
    if label_range is not None:
        lo, hi = label_range
        feas = feas & (slot_label >= lo) & (slot_label < hi)
    cap_b = jnp.broadcast_to(cap, (C,))
    feas = feas & (
        cluster_weights[lab_c].astype(ACC_DTYPE)
        + node_w[:, None].astype(ACC_DTYPE)
        <= cap_b[lab_c]
    )
    if communities is not None:
        lab_n = jnp.clip(slot_label, 0, n_pad - 1)
        feas = feas & (communities[lab_n] == communities[:, None])
    score = jnp.where(feas, slot_w, INT32_MIN)
    best_w = jnp.max(score, axis=1)
    has = best_w > INT32_MIN
    is_best = feas & (score == best_w[:, None])
    tb = hash_u32(slot_label, tie_salt)
    best_tb = jnp.max(jnp.where(is_best, tb, -1), axis=1)
    winner = is_best & (tb == best_tb[:, None])
    best = jnp.max(jnp.where(winner, slot_label, -1), axis=1)
    return (
        jnp.where(has, best, -1),
        jnp.where(has, best_w, INT32_MIN),
        w_own,
    )


#: name -> what the mid-clustering state is made of
FEASIBILITY_CASES = {
    "unit-S32": dict(slots=32),
    "unit-S64": dict(slots=64),
    "heavy-node-weights": dict(slots=32, heavy=True),
    "clusters-at-cap": dict(slots=32, cap_quantile=70),
    "zero-weight-edges": dict(slots=32, zero_edges=True, heavy=True),
    "contested-both-passes": dict(slots=32, hubs=True),
    "communities": dict(slots=64, communities=True),
    "delta-rows": dict(slots=32, delta=True, heavy=True),
}


def _mid_clustering_state(slots=32, heavy=False, cap_quantile=80,
                          zero_edges=False, hubs=False, communities=False,
                          delta=False):
    """A state as a clustering round meets it: clusters of several
    nodes with their true weights, some with room, some at the cap,
    some over it (`cap_quantile` of the cluster weights is the cap)."""
    rng = np.random.default_rng(len(repr((slots, heavy, cap_quantile,
                                          zero_edges, hubs, communities,
                                          delta))))
    if hubs:
        # 1,024 nodes, 24k edges: the hub rows see far more than 2 x 32
        # distinct clusters, so labels lose both passes
        g = factories.make_rmat(1024, 24_000, seed=5)
    else:
        g = factories.make_rmat(512, 4096, seed=11)
    dg = device_graph_from_host(g)
    n_pad = dg.n_pad
    node_w = np.zeros(n_pad, np.int32)
    node_w[: g.n] = rng.integers(1, 40, g.n) if heavy else 1
    edge_w = np.asarray(dg.edge_w).copy()
    if zero_edges:
        edge_w[rng.random(edge_w.shape[0]) < 0.3] = 0
    dg = dataclasses.replace(dg, node_w=jnp.asarray(node_w),
                             edge_w=jnp.asarray(edge_w))
    # every node joins a random node of a small pool or stays alone
    labels = np.arange(n_pad, dtype=np.int32)
    pool = rng.choice(g.n, size=g.n // (2 if hubs else 6), replace=False)
    joins = rng.random(g.n) < (0.5 if hubs else 0.8)
    labels[: g.n] = np.where(joins, rng.choice(pool, g.n), labels[: g.n])
    labels[pool] = pool
    cluster_w = np.zeros(n_pad, np.int64)
    np.add.at(cluster_w, labels, node_w)
    cap = int(np.percentile(cluster_w[cluster_w > 0], cap_quantile))
    active = np.zeros(n_pad, bool)
    active[: g.n] = rng.random(g.n) < (0.25 if delta else 0.9)
    comm = None
    if communities:
        comm = jnp.asarray(rng.integers(0, 3, n_pad).astype(np.int32))
    rows = None
    if delta:
        # a buffer the active rows do not fill: the tail is invalid
        rows = expand_active_rows(dg.row_ptr, dg.degrees,
                                  jnp.asarray(active), dg.m_pad // 2)
        assert not bool(jnp.all(rows[3])) and bool(jnp.any(rows[3]))
    cfg = LPConfig(rating="scatter", num_slots=slots, scatter_fallback=1.0)
    return dict(graph=dg, labels=jnp.asarray(labels),
                cluster_weights=jnp.asarray(cluster_w.astype(np.int32)),
                cap=jnp.int32(cap), active=jnp.asarray(active),
                salt=jnp.int32(0x2F6B1D), cfg=cfg, communities=comm,
                rows=rows)


def _run_round(state):
    return lp_round(
        state["graph"], state["labels"], state["cluster_weights"],
        state["cap"], state["active"], state["salt"], state["cfg"],
        communities=state["communities"], rows=state["rows"],
    )


@pytest.mark.parametrize("name", list(FEASIBILITY_CASES))
def test_edge_side_feasibility_table_is_bitwise_table_side(name, monkeypatch):
    """The table lp_round builds (`joinable` decided per edge) rates
    every row as the table-side reference rates the plain table: same
    best, best_w, w_own and barred rows, and the two tables differ only
    in the weight of the slots the reference finds infeasible."""
    state = _mid_clustering_state(**FEASIBILITY_CASES[name])
    calls = []
    build = rating.scatter_slot_ratings
    monkeypatch.setattr(
        rating, "scatter_slot_ratings",
        lambda *a, **k: calls.append((a, k)) or build(*a, **k),
    )
    _run_round(state)
    ((args, kwargs),) = calls
    assert kwargs["joinable"] is not None
    sl, sw, fully = build(*args, **kwargs)
    plain = {k: v for k, v in kwargs.items() if k != "joinable"}
    sl_ref, sw_ref, fully_ref = build(*args, **plain)
    dg = state["graph"]
    got = best_from_slots(sl, sw, state["labels"], state["salt"])
    want = _table_side_best(
        sl_ref, sw_ref, state["labels"], state["cluster_weights"],
        dg.node_w, state["cap"], state["salt"],
        communities=state["communities"],
    )
    for g_, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))
    np.testing.assert_array_equal(np.asarray(fully), np.asarray(fully_ref))
    np.testing.assert_array_equal(np.asarray(sl), np.asarray(sl_ref))
    sw, sw_ref = np.asarray(sw), np.asarray(sw_ref)
    assert (sw_ref >= 0).all()
    np.testing.assert_array_equal(sw[sw >= 0], sw_ref[sw >= 0])
    # the case does exercise what its name says
    best = np.asarray(got[0])
    assert (sw < 0).any() and (best >= 0).any()
    S = state["cfg"].num_slots
    assert (np.asarray(sl)[:, S:] >= 0).any()  # pass 2 rated something
    if name == "contested-both-passes":
        assert not np.asarray(fully)[: int(dg.n)].all()
    if name == "zero-weight-edges":
        # a joinable slot of total weight 0 is not an infeasible one
        assert ((np.asarray(sl) >= 0) & (sw == 0)).any()
    if name == "clusters-at-cap":
        room = int(state["cap"]) - np.asarray(state["cluster_weights"])
        assert (room[np.asarray(state["labels"])] == 0).any()


@pytest.mark.parametrize("name", list(FEASIBILITY_CASES))
def test_edge_side_feasibility_round_is_bitwise_table_side(name, monkeypatch):
    """lp_round's four outputs against the same round with the rating
    swapped for the table-side reference (plain table + gathers)."""
    state = _mid_clustering_state(**FEASIBILITY_CASES[name])
    got = [np.asarray(x) for x in _run_round(state)]
    build = rating.scatter_slot_ratings
    monkeypatch.setattr(
        rating, "scatter_slot_ratings",
        lambda *a, joinable=None, **k: build(*a, **k),
    )
    monkeypatch.setattr(
        rating, "best_from_slots",
        lambda sl, sw, labels, salt: _table_side_best(
            sl, sw, labels, state["cluster_weights"],
            state["graph"].node_w, state["cap"], salt,
            communities=state["communities"],
        ),
    )
    want = [np.asarray(x) for x in _run_round(state)]
    for g_, w_ in zip(got, want):
        np.testing.assert_array_equal(g_, w_)
    assert (got[0] != np.asarray(state["labels"])).any()  # nodes moved


def test_edge_side_feasibility_global_label_space():
    """The dist caller's form (parallel/dist_lp.py): n_loc rows rate
    GLOBAL cluster ids, weights and cap are C-wide, the sharded COO has
    no row spans, some edges belong to no owned row."""
    rng = np.random.default_rng(8)
    n_loc, C, m, S = 64, 512, 3000, 32
    owner = jnp.asarray(rng.integers(0, n_loc, m).astype(np.int32))
    in_range = jnp.asarray(rng.random(m) < 0.9)
    nb = jnp.asarray(rng.integers(0, C, m).astype(np.int32))
    ew = jnp.asarray(rng.integers(0, 9, m).astype(np.int32))
    nw = jnp.asarray(rng.integers(1, 20, n_loc).astype(np.int32))
    labels_l = jnp.asarray(rng.integers(0, C, n_loc).astype(np.int32))
    weights = jnp.asarray(rng.integers(0, 60, C).astype(np.int32))
    cap = jnp.full((C,), 50, jnp.int32)
    joinable = (nw[owner] <= (cap - weights)[nb]) | (nb == labels_l[owner])
    kwargs = dict(valid=in_range, label_space=C)
    sl, sw, fully = scatter_slot_ratings(
        owner, nb, ew, n_loc, S, 21, joinable=joinable, **kwargs)
    sl_ref, sw_ref, fully_ref = scatter_slot_ratings(
        owner, nb, ew, n_loc, S, 21, **kwargs)
    label_range = (jnp.int32(128), jnp.int32(384))
    for lr in (None, label_range):
        got = best_from_slots(sl, sw, labels_l, 21, label_range=lr)
        want = _table_side_best(sl_ref, sw_ref, labels_l, weights, nw, cap,
                                21, label_range=lr)
        for g_, w_ in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))
    np.testing.assert_array_equal(np.asarray(fully), np.asarray(fully_ref))
    assert (np.asarray(sw) < 0).any() and (np.asarray(got[0]) >= 0).any()


#: sha256 of lp_cluster's int32 labels at commit f52751b (PR 26), the
#: parent of PR 27, for the level-0 clustering the coarsener asks for:
#: (rmat n, m, graph seed, k) -> digest
PARENT_LABELS = {
    (4096, 60_000, 3, 4):
        "fa1a69899eb16964a97a0bff681a1a817675f6ec4c8ea2be8b6ff2821f1d455e",
    (2048, 24_000, 5, 8):
        "ece5ee7e73961f46e3e106757d9474f9dfc05972c3650e44dfdc6ecd2307ac48",
}


@pytest.mark.parametrize("spec", list(PARENT_LABELS))
def test_scatter_level_returns_the_parents_labels(spec):
    """A skewed graph whose level 0 the coarsener rates with `scatter`
    at doubled slots (the R-MAT cells' level 0 in small) clusters to
    the labels the parent commit returned, bit for bit."""
    from kaminpar_tpu import telemetry
    from kaminpar_tpu.partitioning.coarsener import Coarsener
    from kaminpar_tpu.presets import create_context_by_preset_name

    n, m, graph_seed, k = spec
    g = factories.make_rmat(n, m, seed=graph_seed)
    dg = device_graph_from_host(g)
    ctx = create_context_by_preset_name("default")
    ctx.partition.setup(g, k=k, epsilon=0.03)
    coarsener = Coarsener(ctx, dg, g.n)
    was_enabled = telemetry.enabled()
    try:
        telemetry.enable()
        cfg = coarsener._level_lp_cfg(dg)
        event = telemetry.events("rating-engine")[-1].attrs
    finally:
        telemetry.enable() if was_enabled else telemetry.disable()
    assert event["engine"] == "scatter" and cfg.num_slots == 64
    cap = ctx.coarsening.max_cluster_weight(
        g.n, int(ctx.partition.total_node_weight), ctx.partition)
    labels = np.asarray(lp_cluster(dg, jnp.int32(cap), jnp.int32(11), cfg))
    assert len(np.unique(labels[: g.n])) < g.n // 4
    digest = hashlib.sha256(labels.astype(np.int32).tobytes()).hexdigest()
    assert digest == PARENT_LABELS[spec]


def _irregular_ops(jaxpr, skip_branch=None):
    """(primitive, number of indices) of every gather and scatter of a
    jaxpr and all it calls; `skip_branch` leaves that branch of every
    `cond` out (0 is lax.cond's false branch)."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            found.append((name, int(np.prod(eqn.invars[1].aval.shape[:-1]))))
        for key, sub in eqn.params.items():
            subs = sub if isinstance(sub, (tuple, list)) else (sub,)
            for i, item in enumerate(subs):
                if name == "cond" and key == "branches" and i == skip_branch:
                    continue
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    found += _irregular_ops(inner, skip_branch)
    return found


def test_scatter_round_has_no_table_wide_irregular_pass():
    """Structure of one `scatter` round: nothing irregular runs at the
    width of the slot table (n_pad * 2 * num_slots: four times the edge
    list on the R-MAT cells' level 0), best_from_slots is element-wise,
    and the edge-wide irregular passes are counted, so that the next
    writer sees one come back: 2 gathers for the neighbour (labels[dst]
    and the room of its cluster), 3 a pass of the table build
    (segment_max, winner gather, segment_sum), 1 in neighbor_any_true."""
    state = _mid_clustering_state(slots=64)
    dg = state["graph"]
    n_pad, m_pad, S = dg.n_pad, dg.m_pad, state["cfg"].num_slots
    table = n_pad * 2 * S
    assert n_pad + 1 < m_pad < table
    jaxpr = jax.make_jaxpr(
        lambda labels, weights, active: lp_round(
            dg, labels, weights, state["cap"], active, state["salt"],
            state["cfg"])
    )(state["labels"], state["cluster_weights"], state["active"]).jaxpr
    everything = _irregular_ops(jaxpr)
    assert everything and max(count for _, count in everything) < table
    # the round as it runs while the barred rows are few: without the
    # sort engine's branch of the fallback cond
    taken = [op for op in _irregular_ops(jaxpr, skip_branch=0)
             if op[1] == m_pad]
    gathers = [op for op in taken if op[0] == "gather"]
    scatters = [op for op in taken if op[0] != "gather"]
    assert (len(gathers), len(scatters)) == (2 + 2 + 1, 2 * 2), taken
    slot_label = jnp.zeros((n_pad, 2 * S), jnp.int32)
    rate = jax.make_jaxpr(
        lambda sl, sw, lab: best_from_slots(sl, sw, lab, 7)
    )(slot_label, slot_label, state["labels"]).jaxpr
    assert _irregular_ops(rate) == []


# ---------------------------------------------------------------------------
# one gather path (PR 28): every irregular read has one signature
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "module", ["lp", "jet", "contraction", "segments", "rating"]
)
def test_ops_have_one_gather_signature(module):
    """No function of the kernel modules takes a routing plan or the
    routed branch's per-slot own weight: a second gather path would have
    to be kept bit for bit by every change to these functions."""
    import importlib
    import inspect

    mod = importlib.import_module(f"kaminpar_tpu.ops.{module}")
    for name, fn in vars(mod).items():
        fn = getattr(fn, "__wrapped__", fn)  # through jax.jit
        if inspect.isfunction(fn) and fn.__module__ == mod.__name__:
            params = inspect.signature(fn).parameters
            assert not {"plans", "w_own"} & set(params), name
