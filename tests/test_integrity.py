"""Integrity sentinels (resilience/integrity.py): silent-data-corruption
defense.

Covers the four legs of the integrity contract (docs/robustness.md):
invariant sentinels at the phase boundaries, checksummed exchange,
sampled re-execution audits, and corruption chaos — plus the bounded
retry-from-last-good-barrier ladder, the `all`-plan exclusion of
corruption sites, the KAMINPAR_TPU_INTEGRITY=0 kill switch, the jaxpr
dormancy pin, and the schema-v14 `integrity` report section.
"""

import dataclasses
import importlib.util
import json
import os

import numpy as np
import pytest

from kaminpar_tpu import resilience, telemetry
from kaminpar_tpu.graphs import factories
from kaminpar_tpu.resilience import faults, integrity, with_fallback
from kaminpar_tpu.resilience.errors import IntegrityViolation

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_integrity(monkeypatch):
    """Every test starts with zero fault counters, no plan, integrity
    enabled at default knobs, and a fresh telemetry stream."""
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    monkeypatch.delenv(integrity.ENV_INTEGRITY, raising=False)
    monkeypatch.delenv(integrity.ENV_AUDIT_FRACTION, raising=False)
    resilience.reset()
    telemetry.reset()
    telemetry.enable()
    yield
    resilience.reset()
    telemetry.disable()
    telemetry.reset()


def _contracted(rows=16, cols=16, seed=1):
    """One real contraction of a grid graph: (fine device graph,
    CoarseGraph, coarse n)."""
    import jax.numpy as jnp

    from kaminpar_tpu.graphs.csr import device_graph_from_host
    from kaminpar_tpu.ops.contraction import contract_clustering
    from kaminpar_tpu.ops.lp import LPConfig, lp_cluster

    dg = device_graph_from_host(factories.make_grid_graph(rows, cols))
    labels = lp_cluster(
        dg, jnp.asarray(64, dtype=dg.node_w.dtype), jnp.int32(seed),
        LPConfig(num_iterations=2),
    )
    coarse, c_n, _ = contract_clustering(dg, labels)
    return dg, coarse, c_n


# ---------------------------------------------------------------------------
# invariant sentinels: contraction boundary
# ---------------------------------------------------------------------------


def test_contraction_sentinels_pass_clean():
    dg, coarse, c_n = _contracted()
    integrity.check_contraction(
        dg, coarse.cmap, coarse.graph, level=0, fine_n=int(dg.n),
        coarse_n=c_n,
    )
    s = integrity.summary()
    assert s["enabled"] and s["checks"] >= 5
    assert s["violations"] == [] and s["verdict"] == "clean"
    assert s["wall_s"] >= 0.0


def test_sentinel_catches_corrupted_coarse_edge_weight():
    import jax.numpy as jnp

    dg, coarse, c_n = _contracted()
    ew = np.array(np.asarray(coarse.graph.edge_w), copy=True)
    ew.reshape(-1)[0] ^= ew.dtype.type(1 << 5)
    bad = dataclasses.replace(coarse.graph, edge_w=jnp.asarray(ew))
    with pytest.raises(IntegrityViolation) as exc:
        integrity.check_contraction(
            dg, coarse.cmap, bad, level=3, fine_n=int(dg.n),
            coarse_n=c_n,
        )
    assert exc.value.invariant in (
        "edge-weight-conservation", "coarse-csr-symmetry",
    )
    assert exc.value.level == 3
    row = integrity.summary()["violations"][0]
    assert row["invariant"] == exc.value.invariant
    assert row["level"] == 3 and row["scope"] == "coarsen:3"
    # the violation is also a telemetry event
    ev = [e for e in telemetry.events("integrity")
          if e.attrs.get("action") == "violation"]
    assert ev and ev[0].attrs["invariant"] == exc.value.invariant


def test_sentinel_catches_corrupted_cmap():
    import jax.numpy as jnp

    dg, coarse, c_n = _contracted()
    cm = np.array(np.asarray(coarse.cmap), copy=True)
    cm[0] = c_n + 1000  # far out of the coarse id range
    with pytest.raises(IntegrityViolation) as exc:
        integrity.check_contraction(
            dg, jnp.asarray(cm), coarse.graph, level=0,
            fine_n=int(dg.n), coarse_n=c_n,
        )
    # any named invariant is a detection; the range check names it best
    assert exc.value.invariant in (
        "cmap-range", "edge-weight-conservation",
    )


def test_sentinel_catches_corrupted_node_weight():
    import jax.numpy as jnp

    dg, coarse, c_n = _contracted()
    nw = np.array(np.asarray(coarse.graph.node_w), copy=True)
    nw[0] += nw.dtype.type(7)
    bad = dataclasses.replace(coarse.graph, node_w=jnp.asarray(nw))
    with pytest.raises(IntegrityViolation) as exc:
        integrity.check_contraction(
            dg, coarse.cmap, bad, level=0, fine_n=int(dg.n),
            coarse_n=c_n,
        )
    assert exc.value.invariant == "node-weight-conservation"


# ---------------------------------------------------------------------------
# invariant sentinels: refinement boundary (pure host tuples)
# ---------------------------------------------------------------------------


def test_refinement_cut_regression_detected():
    with pytest.raises(IntegrityViolation) as exc:
        integrity.check_refinement(
            (10, True, 0, 3), (12, True, 0, 3), k=4, level=1,
        )
    assert exc.value.invariant == "cut-non-increase"
    assert exc.value.level == 1


def test_refinement_partition_range_detected():
    with pytest.raises(IntegrityViolation) as exc:
        integrity.check_refinement(
            (10, True, 0, 3), (8, True, 0, 7), k=4, level=0,
        )
    assert exc.value.invariant == "partition-range"


def test_refinement_balancer_tradeoff_is_not_corruption():
    # an infeasible input legitimately trades cut for balance
    integrity.check_refinement(
        (10, False, 0, 3), (14, True, 0, 3), k=4, level=0,
    )
    # feasible -> infeasible never triggers the cut check either
    integrity.check_refinement(
        (10, True, 0, 3), (14, False, 0, 3), k=4, level=0,
    )
    assert integrity.summary()["violations"] == []


def test_refinement_none_probes_are_noops():
    integrity.check_refinement(None, (1, True, 0, 0), k=4, level=0)
    integrity.check_refinement((1, True, 0, 0), None, k=4, level=0)
    assert integrity.summary()["checks"] == 0


# ---------------------------------------------------------------------------
# kill switch
# ---------------------------------------------------------------------------


def test_kill_switch_disables_every_leg(monkeypatch):
    import jax.numpy as jnp

    dg, coarse, c_n = _contracted()
    monkeypatch.setenv(integrity.ENV_INTEGRITY, "0")
    assert not integrity.enabled()
    # a grossly corrupted contraction sails through: sentinels dormant
    nw = np.array(np.asarray(coarse.graph.node_w), copy=True)
    nw[0] += nw.dtype.type(99)
    bad = dataclasses.replace(coarse.graph, node_w=jnp.asarray(nw))
    integrity.check_contraction(
        dg, coarse.cmap, bad, level=0, fine_n=int(dg.n), coarse_n=c_n,
    )
    # probes return None, digest verification is vacuous
    assert integrity.refine_probe(dg, coarse.cmap, None, None) is None
    integrity.verify_digest("feedface", np.arange(4), what="x")
    assert integrity.summary() == {"enabled": False}


# ---------------------------------------------------------------------------
# checksummed exchange
# ---------------------------------------------------------------------------


def test_content_digest_roundtrip_and_mismatch():
    a = np.arange(64, dtype=np.int32)
    d = integrity.content_digest(a)
    integrity.verify_digest(d, a, what="unit", site="cache-poison")
    b = a.copy()
    b[0] ^= 1 << 7
    with pytest.raises(IntegrityViolation) as exc:
        integrity.verify_digest(d, b, what="unit", site="cache-poison")
    assert exc.value.invariant == "exchange-digest"
    s = integrity.summary()["digests"]
    assert s["verified"] == 2 and s["mismatched"] == 1
    # a missing expected digest verifies vacuously (pre-upgrade data)
    integrity.verify_digest("", b, what="unit")


def test_digest_distinguishes_dtype_reinterpretation():
    a = np.arange(8, dtype=np.int32)
    assert integrity.content_digest(a) != integrity.content_digest(
        a.view(np.uint32)
    )


def test_snapshot_sha_verified_on_read(tmp_path):
    from kaminpar_tpu.io.snapshot import (
        SnapshotError,
        read_snapshot,
        write_snapshot,
    )

    path = str(tmp_path / "x.npz")
    arrays = {"adjncy": np.arange(100, dtype=np.int32)}
    _, sha = write_snapshot(path, arrays)
    out = read_snapshot(path, sha)
    assert np.array_equal(out["adjncy"], arrays["adjncy"])
    # flip one at-rest byte: the sha check must fire BEFORE np.load
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0x40]))
    with pytest.raises(SnapshotError):
        read_snapshot(path, sha)


# ---------------------------------------------------------------------------
# corruption chaos helpers
# ---------------------------------------------------------------------------


def test_chaos_flip_array_fires_once(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "cache-poison:nth=1")
    resilience.reset()
    a = np.arange(16, dtype=np.int32)
    out = integrity.chaos_flip_array("cache-poison", a)
    assert out[0] == a[0] ^ (1 << 7) and not np.array_equal(out, a)
    assert np.array_equal(a, np.arange(16, dtype=np.int32))  # copy, not in place
    # nth=1 consumed: the second call is a no-op passthrough
    again = integrity.chaos_flip_array("cache-poison", a)
    assert again is a
    assert {"site": "cache-poison", "call": 1} in faults.injected_log()


def test_chaos_flip_file_mutates_at_rest_bytes(tmp_path, monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "spill-corrupt:nth=1")
    resilience.reset()
    path = str(tmp_path / "chunk.bin")
    with open(path, "wb") as f:
        f.write(bytes(range(64)))
    before = open(path, "rb").read()
    assert integrity.chaos_flip_file("spill-corrupt", path) is True
    after = open(path, "rb").read()
    assert before != after and len(before) == len(after)
    # one flipped bit in exactly one byte
    diff = [i for i in range(64) if before[i] != after[i]]
    assert len(diff) == 1
    # consumed: no second mutation
    assert integrity.chaos_flip_file("spill-corrupt", path) is False


def test_all_plan_excludes_corruption_sites(monkeypatch):
    """`all` covers degradation-contract sites only: corruption chaos
    (IntegrityViolation-typed sites) is opt-in by name — two corruption
    injections in one run would exhaust the retry budget by
    construction."""
    monkeypatch.setenv(faults.ENV_VAR, "all:nth=1")
    resilience.reset()
    # corruption sites skip the `all` rule entirely
    faults.maybe_inject("bit-flip:contraction")
    faults.maybe_inject("spill-corrupt")
    # a degradation-contract site still fires
    with pytest.raises(faults.SITES["refiner"].exc):
        faults.maybe_inject("refiner")


def test_colon_site_plan_parsing():
    rules = faults.parse_plan(
        "bit-flip:contraction:nth=1,spill-corrupt:0.5,bit-flip:partition"
    )
    assert [r.site for r in rules] == [
        "bit-flip:contraction", "spill-corrupt", "bit-flip:partition",
    ]
    assert rules[0].nth == 1
    assert rules[1].prob == 0.5
    assert rules[2].nth is None and rules[2].prob is None


# ---------------------------------------------------------------------------
# the retry ladder + the with_fallback carve-out
# ---------------------------------------------------------------------------


def test_with_fallback_never_absorbs_integrity_violation():
    def primary():
        raise integrity.violation("cut-non-increase", "unit", scope="t")

    with pytest.raises(IntegrityViolation):
        with_fallback(primary, lambda: "swallowed", site="refiner")


def test_run_with_retry_recovers_once():
    calls = {"n": 0}

    def body():
        calls["n"] += 1
        if calls["n"] == 1:
            raise integrity.violation(
                "edge-weight-conservation", "unit", level=0, scope="t",
            )
        return "ok"

    assert integrity.run_with_retry(body, where="unit") == "ok"
    s = integrity.summary()
    assert s["retries"] == 1 and s["recovered"] == 1
    assert s["verdict"] == "recovered"
    actions = [e.attrs.get("action")
               for e in telemetry.events("integrity")]
    assert "retry" in actions and "recovered" in actions


def test_run_with_retry_bounded_corrupt_result():
    def body():
        raise integrity.violation(
            "cmap-surjective", "unit", level=2, scope="t",
        )

    with pytest.raises(IntegrityViolation):
        integrity.run_with_retry(body, where="unit")
    s = integrity.summary()
    assert s["retries"] == integrity.MAX_RETRIES
    assert s["recovered"] == 0 and s["verdict"] == "corrupt-result"


# ---------------------------------------------------------------------------
# sampled re-execution audits
# ---------------------------------------------------------------------------


def test_audit_fraction_one_audits_every_contraction(monkeypatch):
    monkeypatch.setenv(integrity.ENV_AUDIT_FRACTION, "1.0")
    dg, coarse, c_n = _contracted()
    integrity.check_contraction(
        dg, coarse.cmap, coarse.graph, level=0, fine_n=int(dg.n),
        coarse_n=c_n,
    )
    s = integrity.summary()
    assert s["audit_fraction"] == 1.0
    ent = s["audits"]["contraction-weights"]
    assert ent == {"audited": 1, "mismatched": 0}


def test_audit_mismatch_is_a_violation():
    with pytest.raises(IntegrityViolation) as exc:
        integrity.record_audit("unit-scope", mismatched=True, level=1)
    assert exc.value.invariant == "audit:unit-scope"
    ent = integrity.summary()["audits"]["unit-scope"]
    assert ent == {"audited": 1, "mismatched": 1}


def test_audit_sampling_is_deterministic(monkeypatch):
    monkeypatch.setenv(integrity.ENV_AUDIT_FRACTION, "0.5")
    first = [integrity.should_audit("scope-a") for _ in range(32)]
    integrity.reset()  # clears the per-scope call counters
    second = [integrity.should_audit("scope-a") for _ in range(32)]
    assert first == second
    assert any(first) and not all(first)  # 0.5 actually samples


def test_audit_off_by_default():
    assert integrity.audit_fraction() == 0.0
    assert not integrity.should_audit("anything")


# ---------------------------------------------------------------------------
# end-to-end: chaos proof + dormancy + schema
# ---------------------------------------------------------------------------


def _partition(k=4, seed=1):
    from kaminpar_tpu.graphs.factories import make_rgg2d
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.presets import create_context_by_preset_name
    from kaminpar_tpu.utils import rng

    rng.set_seed(0)
    ctx = create_context_by_preset_name("default")
    # force real coarsening levels at n=400 so the contraction chaos
    # site has a first call to hit
    ctx.coarsening.contraction_limit = 50
    g = make_rgg2d(400, avg_degree=8, seed=3)
    solver = KaMinPar(ctx)
    solver.set_graph(g)
    part = solver.compute_partition(k=k, epsilon=0.03, seed=seed)
    return np.asarray(part)


def test_bitflip_chaos_detect_retry_recover_cut_identical(monkeypatch):
    """The chaos proof: an injected contraction bit-flip is detected by
    a named invariant, recovered in one retry, and the final partition
    is IDENTICAL to the uninjected run (recovery is lossless).  With
    detection kill-switched the same injection yields a measurably
    different (silently corrupt) result.  Seed 3: with eight native
    bipartition attempts a call (PR 26) the flipped weight no longer
    reaches the final partition at seeds 1 and 2 (k=4), where one
    attempt let it through; at seed 3 it still does."""
    baseline = _partition(seed=3)

    resilience.reset()
    telemetry.reset()
    monkeypatch.setenv(faults.ENV_VAR, "bit-flip:contraction:nth=1")
    injected = _partition(seed=3)
    s = integrity.summary()
    assert s["verdict"] == "recovered", s
    assert s["retries"] == 1 and s["recovered"] == 1
    invariants = {v["invariant"] for v in s["violations"]}
    assert invariants & {
        "edge-weight-conservation", "coarse-csr-symmetry",
    }, invariants
    assert all(v["level"] is not None for v in s["violations"])
    assert {"site": "bit-flip:contraction",
            "call": 1} in faults.injected_log()
    assert np.array_equal(injected, baseline)

    # A/B: same injection, detection off -> silently different result
    resilience.reset()
    telemetry.reset()
    monkeypatch.setenv(integrity.ENV_INTEGRITY, "0")
    corrupt = _partition(seed=3)
    assert integrity.summary() == {"enabled": False}
    assert not np.array_equal(corrupt, baseline)


def test_jaxpr_dormancy_lp_jet_contraction(monkeypatch):
    """The acceptance pin: the LP / Jet / contraction programs trace to
    bitwise-identical jaxprs whether integrity is on, off, or the
    sentinels have already compiled — every gate is a SEPARATE jitted
    reduction, never a branch inside the pipeline jaxprs."""
    import jax
    import jax.numpy as jnp

    from kaminpar_tpu.graphs.csr import device_graph_from_host
    from kaminpar_tpu.ops import jet as jet_mod
    from kaminpar_tpu.ops import lp as lp_mod
    from kaminpar_tpu.ops.contraction import _contract_part1

    g = factories.make_grid_graph(8, 8)
    dg = device_graph_from_host(g)
    part0 = jnp.asarray((np.arange(dg.n_pad) % 4).astype(np.int32))

    # progress capture off so only the INTEGRITY toggle varies
    monkeypatch.setenv("KAMINPAR_TPU_PROGRESS", "0")

    def traces():
        cluster = str(jax.make_jaxpr(
            lambda s: lp_mod.lp_cluster(
                dg, jnp.asarray(64, dtype=dg.node_w.dtype), s,
                lp_mod.LPConfig(num_iterations=2),
            )
        )(jnp.int32(3)))
        jet = str(jax.make_jaxpr(
            lambda p: jet_mod._jet_build_conn(dg, p, 4)
        )(part0))
        contraction = str(jax.make_jaxpr(
            lambda lab: _contract_part1(dg, lab)
        )(part0))
        return cluster, jet, contraction

    assert integrity.enabled()
    j_on = traces()
    # warm the sentinel jits too: compiled sentinels must not leak in
    dg2, coarse, c_n = _contracted(8, 8)
    integrity.check_contraction(
        dg2, coarse.cmap, coarse.graph, level=0, fine_n=int(dg2.n),
        coarse_n=c_n,
    )
    j_warm = traces()
    monkeypatch.setenv(integrity.ENV_INTEGRITY, "0")
    j_off = traces()
    assert j_on == j_warm == j_off


def test_report_schema_v14_integrity_section():
    from kaminpar_tpu.telemetry.report import (
        SCHEMA_PATH,
        SCHEMA_VERSION,
        build_run_report,
    )

    assert SCHEMA_VERSION == 14
    _partition(k=2)
    report = build_run_report()
    assert report["schema_version"] == 14
    integ = report["integrity"]
    assert integ["enabled"] is True
    assert integ["checks"] > 0 and integ["verdict"] == "clean"
    assert integ["digests"]["mismatched"] == 0

    spec = importlib.util.spec_from_file_location(
        "check_report_schema",
        os.path.join(_REPO, "scripts", "check_report_schema.py"),
    )
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    schema = json.loads(open(SCHEMA_PATH).read())
    assert checker.validate_instance(report, schema) == []
    assert checker.version_checks(report) == []


def test_overhead_pct_metering():
    integrity.reset()
    assert integrity.overhead_pct(0.0) == 0.0
    dg, coarse, c_n = _contracted(8, 8)
    integrity.check_contraction(
        dg, coarse.cmap, coarse.graph, level=0, fine_n=int(dg.n),
        coarse_n=c_n,
    )
    wall = integrity.summary()["wall_s"]
    assert wall > 0.0
    assert integrity.overhead_pct(wall * 100) == pytest.approx(
        1.0, rel=0.2
    )
