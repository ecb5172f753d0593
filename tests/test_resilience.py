"""Resilience layer tests: degradation contract, fault-injection chaos
suite, and the strict-balance output gate (docs/robustness.md).

The chaos suite is the acceptance check of ISSUE 3: for every registered
fault site, single-site injection must still yield a partition that
passes the strict-balance output gate, with a `degraded` telemetry event
naming the site and its fallback.
"""

import os

import numpy as np
import pytest

from kaminpar_tpu import resilience, telemetry
from kaminpar_tpu.resilience import (
    CollectiveTimeout,
    DegradationError,
    DeviceOOM,
    NativeUnavailable,
    RefinerRefused,
    faults,
    gate,
    policy,
    with_fallback,
)


@pytest.fixture(autouse=True)
def _clean_resilience(monkeypatch):
    """Every test starts with closed breakers, zero fault counters, no
    plan, and a fresh telemetry stream."""
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    resilience.reset()
    telemetry.reset()
    telemetry.enable()
    yield
    resilience.reset()
    telemetry.disable()
    telemetry.reset()


def degraded_sites():
    return [e.attrs["site"] for e in telemetry.events("degraded")]


# ---------------------------------------------------------------------------
# fault-plan parsing
# ---------------------------------------------------------------------------


def test_parse_plan_specs():
    rules = faults.parse_plan(
        "native-fm,refiner:nth=3,device-balancer:0.25,all"
    )
    assert [r.site for r in rules] == [
        "native-fm", "refiner", "device-balancer", "all",
    ]
    assert rules[1].nth == 3
    assert rules[2].prob == 0.25
    assert rules[0].nth is None and rules[0].prob is None


@pytest.mark.parametrize(
    "bad",
    ["nosuchsite", "native-fm:maybe", "refiner:nth=0", "refiner:2.0",
     "refiner:nth=x"],
)
def test_parse_plan_rejects(bad):
    with pytest.raises(faults.FaultPlanError):
        faults.parse_plan(bad)


def test_injection_nth_fires_exactly_once(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "refiner:nth=2")
    faults.maybe_inject("refiner")  # call 1: no fire
    with pytest.raises(DeviceOOM) as ei:
        faults.maybe_inject("refiner")  # call 2: fires
    assert ei.value.injected and ei.value.site == "refiner"
    faults.maybe_inject("refiner")  # call 3: no fire
    assert faults.injected_log() == [{"site": "refiner", "call": 2}]


def test_injection_prob_deterministic_by_seed(monkeypatch):
    from kaminpar_tpu.utils import rng

    monkeypatch.setenv(faults.ENV_VAR, "refiner:0.5")

    def draw_pattern():
        resilience.reset()
        fired = []
        for _ in range(32):
            try:
                faults.maybe_inject("refiner")
                fired.append(False)
            except DeviceOOM:
                fired.append(True)
        return fired

    rng.set_seed(7)
    a = draw_pattern()
    rng.set_seed(7)
    b = draw_pattern()
    rng.set_seed(8)
    c = draw_pattern()
    assert a == b  # same seed -> identical injection pattern
    assert any(a) and not all(a)
    assert a != c  # different seed -> (overwhelmingly likely) different


def test_unregistered_site_is_a_programming_error():
    with pytest.raises(KeyError):
        with_fallback(lambda: 1, lambda exc: 2, site="no-such-site")


# ---------------------------------------------------------------------------
# with_fallback policy
# ---------------------------------------------------------------------------


def test_with_fallback_success_no_events():
    assert with_fallback(lambda: 41, lambda exc: -1, site="refiner") == 41
    assert telemetry.events("degraded") == []


def test_with_fallback_degrades_with_event():
    def boom():
        raise DeviceOOM("synthetic")

    out = with_fallback(boom, lambda exc: "fb", site="device-balancer")
    assert out == "fb"
    (ev,) = telemetry.events("degraded")
    assert ev.attrs["site"] == "device-balancer"
    assert ev.attrs["error"] == "DeviceOOM"
    assert "host balancer" in ev.attrs["fallback"]


def test_with_fallback_classifies_oom_strings():
    class FakeXlaError(RuntimeError):
        pass

    def boom():
        raise FakeXlaError("RESOURCE_EXHAUSTED: out of HBM")

    out = with_fallback(boom, lambda exc: exc, site="device-balancer")
    assert isinstance(out, DeviceOOM)


def test_with_fallback_propagates_unclassified():
    def bug():
        raise ZeroDivisionError("a bug, not a degradation")

    with pytest.raises(ZeroDivisionError):
        with_fallback(bug, lambda exc: "fb", site="refiner")
    assert telemetry.events("degraded") == []


def test_with_fallback_none_fallback_raises_structured():
    def boom():
        raise CollectiveTimeout("down")

    with pytest.raises(CollectiveTimeout):
        with_fallback(boom, None, site="collective")
    assert degraded_sites() == ["collective"]


def test_with_fallback_retry_recovers_and_reports():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise DeviceOOM("transient")
        return "ok"

    out = with_fallback(flaky, lambda exc: "fb", site="refiner", retries=1)
    assert out == "ok"
    (ev,) = telemetry.events("degraded")
    assert ev.attrs["recovered"] is True
    assert ev.attrs["fallback"] == "retry(primary)"
    assert policy.breaker_state("refiner")["consecutive_failures"] == 0


def test_breaker_opens_and_skips_primary():
    ran = {"n": 0}

    def boom():
        ran["n"] += 1
        raise NativeUnavailable("gone")

    for _ in range(policy.BREAKER_THRESHOLD):
        with_fallback(boom, lambda exc: None, site="native-fm")
    assert policy.breaker_state("native-fm")["open"]
    ran_before = ran["n"]
    with_fallback(boom, lambda exc: None, site="native-fm")
    assert ran["n"] == ran_before  # breaker open: primary skipped
    last = telemetry.events("degraded")[-1]
    assert last.attrs["error"] == "circuit-open"


def test_refusals_do_not_latch_breaker():
    for _ in range(policy.BREAKER_THRESHOLD + 2):
        with_fallback(
            lambda: (_ for _ in ()).throw(RefinerRefused("refused")),
            lambda exc: None,
            site="native-fm",
        )
    assert not policy.breaker_state("native-fm")["open"]


# ---------------------------------------------------------------------------
# strict-balance output gate
# ---------------------------------------------------------------------------


def _unit_graph_and_ctx(n=64, k=4):
    from kaminpar_tpu.context import PartitionContext
    from kaminpar_tpu.graphs.factories import make_grid_graph

    rows = int(np.sqrt(n))
    g = make_grid_graph(rows, n // rows)
    p_ctx = PartitionContext()
    p_ctx.setup(g, k=k, epsilon=0.03)
    return g, p_ctx


def test_gate_passes_a_valid_partition():
    g, p_ctx = _unit_graph_and_ctx()
    part = np.arange(g.n, dtype=np.int32) % p_ctx.k
    fixed, verdict = gate.check_and_repair(g, part, p_ctx)
    assert verdict["valid"] and not verdict["repaired"]
    assert verdict["cap_basis"] == "strict-unit"
    assert np.array_equal(fixed, part)


def test_gate_repairs_deliberate_imbalance():
    g, p_ctx = _unit_graph_and_ctx()
    part = np.zeros(g.n, dtype=np.int32)  # everything in block 0
    fixed, verdict = gate.check_and_repair(g, part, p_ctx)
    assert verdict["repaired"] and verdict["valid"]
    assert any(v.startswith("balance") for v in verdict["violations"])
    bw = np.bincount(fixed, minlength=p_ctx.k)
    cap = int(np.ceil((1 + 0.03) * np.ceil(g.n / p_ctx.k)))
    assert bw.max() <= cap
    # strict unit-weight contract: (1+eps) * ceil(n/k)
    assert bw.max() <= p_ctx.unrelaxed_max_block_weights.max()


def test_gate_repairs_out_of_range_labels():
    g, p_ctx = _unit_graph_and_ctx()
    part = np.arange(g.n, dtype=np.int32) % p_ctx.k
    part[3] = -7
    part[11] = p_ctx.k + 100
    fixed, verdict = gate.check_and_repair(g, part, p_ctx)
    assert verdict["repaired"] and verdict["valid"]
    assert any(v.startswith("assignment") for v in verdict["violations"])
    assert fixed.min() >= 0 and fixed.max() < p_ctx.k


def test_gate_no_repair_reports_only():
    g, p_ctx = _unit_graph_and_ctx()
    part = np.zeros(g.n, dtype=np.int32)
    fixed, verdict = gate.check_and_repair(g, part, p_ctx, repair=False)
    assert not verdict["repaired"] and not verdict["valid"]
    assert verdict["max_overload"] > 0
    assert np.array_equal(fixed, part)  # untouched


def test_gate_no_repair_never_touches_the_partition():
    """--no-repair contract: even out-of-range labels come back
    untouched, and `valid` reports the honest unclipped state."""
    g, p_ctx = _unit_graph_and_ctx()
    part = np.arange(g.n, dtype=np.int32) % p_ctx.k
    part[5] = -3  # out of range
    fixed, verdict = gate.check_and_repair(g, part, p_ctx, repair=False)
    assert fixed is part  # the very same object, not a clipped copy
    assert not verdict["valid"] and not verdict["repaired"]
    assert any(v.startswith("assignment") for v in verdict["violations"])


def test_gate_cut_crosscheck_survives_repair():
    """The cut cross-check compares PRE-repair values: a run whose gate
    repairs balance must not report a spurious cut-mismatch."""
    g, p_ctx = _unit_graph_and_ctx()
    part = np.zeros(g.n, dtype=np.int32)  # imbalanced -> repair fires
    reported, _ = gate.recompute_metrics(g, part, p_ctx.k)
    fixed, verdict = gate.check_and_repair(
        g, part, p_ctx, reported_cut=reported
    )
    assert verdict["repaired"]
    assert verdict["cut_match"] is True
    assert not any("cut-mismatch" in v for v in verdict["violations"])
    # cut_recomputed describes the RETURNED (repaired) partition
    cut_final, _ = gate.recompute_metrics(g, fixed, p_ctx.k)
    assert verdict["cut_recomputed"] == cut_final


def test_gate_cut_crosscheck():
    g, p_ctx = _unit_graph_and_ctx()
    part = np.arange(g.n, dtype=np.int32) % p_ctx.k
    cut, _ = gate.recompute_metrics(g, part, p_ctx.k)
    _, ok = gate.check_and_repair(g, part, p_ctx, reported_cut=cut)
    assert ok["cut_match"] is True
    _, bad = gate.check_and_repair(g, part, p_ctx, reported_cut=cut + 5)
    assert bad["cut_match"] is False
    assert any("cut-mismatch" in v for v in bad["violations"])


def test_gate_recompute_matches_host_metrics():
    from kaminpar_tpu.graphs.factories import make_rgg2d
    from kaminpar_tpu.graphs.host import host_partition_metrics

    g = make_rgg2d(256, avg_degree=6, seed=2)
    part = (np.arange(g.n) * 7 % 5).astype(np.int32)
    cut, bw = gate.recompute_metrics(g, part, 5)
    ref = host_partition_metrics(g, part, 5)
    assert cut == ref["cut"]
    assert np.array_equal(bw, ref["block_weights"])


def test_gate_streams_compressed_graphs():
    from kaminpar_tpu.graphs.compressed import compress_host_graph
    from kaminpar_tpu.graphs.factories import make_rgg2d

    g = make_rgg2d(256, avg_degree=6, seed=4)
    cg = compress_host_graph(g)
    part = (np.arange(g.n) % 3).astype(np.int32)
    cut_c, bw_c = gate.recompute_metrics(cg, part, 3)
    cut_h, bw_h = gate.recompute_metrics(g, part, 3)
    assert cut_c == cut_h and np.array_equal(bw_c, bw_h)


# ---------------------------------------------------------------------------
# FM refusal regression: fm_refine -> None / FM_REFUSED route through
# with_fallback, never "treated as zero gain"
# ---------------------------------------------------------------------------


def _fm_setup():
    import jax.numpy as jnp

    from kaminpar_tpu.context import FMRefinementContext
    from kaminpar_tpu.graphs.csr import device_graph_from_host
    from kaminpar_tpu.graphs.factories import make_grid_graph

    g = make_grid_graph(8, 8)
    dg = device_graph_from_host(g)
    part = jnp.asarray(
        np.pad((np.arange(g.n) % 4).astype(np.int32),
               (0, dg.n_pad - g.n))
    )
    caps = np.full(4, g.n, dtype=np.int64)
    return dg, part, caps, FMRefinementContext()


def test_fm_unavailable_routes_to_numpy_fallback(monkeypatch):
    from kaminpar_tpu import native
    from kaminpar_tpu.refinement.fm import fm_refine_host

    monkeypatch.setattr(native, "fm_refine", lambda *a, **kw: None)
    dg, part, caps, fm_ctx = _fm_setup()
    out = fm_refine_host(dg, part, 4, caps, fm_ctx, seed=0)
    assert out.shape[0] == dg.n_pad
    (ev,) = telemetry.events("degraded")
    assert ev.attrs["site"] == "native-fm"
    assert ev.attrs["error"] == "NativeUnavailable"


def test_fm_refusal_returns_partition_unchanged(monkeypatch):
    from kaminpar_tpu import native
    from kaminpar_tpu.refinement.fm import fm_refine_host

    monkeypatch.setattr(
        native, "fm_refine", lambda *a, **kw: native.FM_REFUSED
    )
    dg, part, caps, fm_ctx = _fm_setup()
    out = fm_refine_host(dg, part, 4, caps, fm_ctx, seed=0)
    assert np.array_equal(np.asarray(out), np.asarray(part))
    (ev,) = telemetry.events("degraded")
    assert ev.attrs["site"] == "native-fm"
    assert ev.attrs["error"] == "RefinerRefused"
    # the refusal must not disable native FM for later (feasible) calls
    assert not policy.breaker_state("native-fm")["open"]


# ---------------------------------------------------------------------------
# chaos suite: single-site injection through the full pipeline
# ---------------------------------------------------------------------------


def _run_partition(monkeypatch, fault_plan, *, compression=False,
                   with_fm=False, n=400, k=4):
    """One pipeline run under a fault plan; returns (graph, partition,
    gate verdicts seen, degraded sites seen)."""
    from kaminpar_tpu.context import RefinementAlgorithm
    from kaminpar_tpu.graphs.factories import make_rgg2d
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.presets import create_context_by_preset_name

    monkeypatch.setenv(faults.ENV_VAR, fault_plan)
    ctx = create_context_by_preset_name("default")
    ctx.compression.enabled = compression
    if with_fm:
        ctx.refinement.algorithms = list(ctx.refinement.algorithms) + [
            RefinementAlgorithm.GREEDY_FM
        ]
    g = make_rgg2d(n, avg_degree=8, seed=3)
    solver = KaMinPar(ctx)
    solver.set_graph(g)
    part = solver.compute_partition(k=k, epsilon=0.03, seed=1)
    gates = [e.attrs for e in telemetry.events("output-gate")]
    return g, part, gates, degraded_sites()


CHAOS_CASES = [
    # (site plan, pipeline config kwargs)
    ("native-build:nth=1", {}),
    ("native-ip:nth=1", {}),
    ("native-fm:nth=1", {"with_fm": True}),
    ("refiner:nth=1", {}),
    ("device-balancer:nth=1", {}),
    ("compressed-stream:nth=1", {"compression": True}),
    # allocator-shaped OOM at the device upload: absorbed by the memory
    # governor's recovery ladder (retry at rung 1, tight pads) — the
    # run must still end gate-valid with the degraded event naming the
    # ladder as its fallback
    ("device-oom:nth=1", {}),
]


@pytest.mark.parametrize("plan,cfg", CHAOS_CASES,
                         ids=[p.split(":")[0] for p, _ in CHAOS_CASES])
def test_chaos_single_site(monkeypatch, plan, cfg):
    site = plan.split(":")[0]
    if site in ("native-build", "native-ip", "native-fm"):
        from kaminpar_tpu import native

        if site == "native-build":
            # get_lib caches per process: re-arm it so the injection has
            # a first call to hit
            monkeypatch.setattr(native, "_lib", None)
            monkeypatch.setattr(native, "_tried", False)
        elif not native.available():
            pytest.skip("native library unavailable; site unreachable")
    g, part, gates, degraded = _run_partition(monkeypatch, plan, **cfg)
    # the postcondition: a complete, gate-valid partition
    assert part.shape == (g.n,)
    assert gates and gates[-1]["valid"], gates
    assert gates[-1]["cut_match"] is True
    # the injected site degraded visibly, naming its fallback
    assert site in degraded, (site, degraded)
    ev = [e for e in telemetry.events("degraded")
          if e.attrs["site"] == site][0]
    assert ev.attrs["injected"] is True
    assert ev.attrs["fallback"] == faults.SITES[site].fallback
    # and the fault was logged by the harness
    assert {"site": site, "call": 1} in faults.injected_log()


def test_chaos_collective_site(monkeypatch):
    from kaminpar_tpu.telemetry.report import build_run_report

    monkeypatch.setenv(faults.ENV_VAR, "collective:nth=1")
    report = build_run_report()
    assert "timers_aggregated" not in report  # degraded to local-only
    assert "collective" in [d["attrs"]["site"] for d in report["degraded"]]
    # the fault-plan echo names the active plan
    assert report["faults"]["plan"] == "collective:nth=1"
    assert report["faults"]["injected"]


def test_chaos_multi_site_sampled(monkeypatch):
    """Sampled multi-site plan: probabilistic faults at several sites at
    once; the pipeline must still meet the gate postcondition."""
    from kaminpar_tpu.utils import rng

    rng.set_seed(13)
    g, part, gates, _ = _run_partition(
        monkeypatch,
        "refiner:0.5,device-balancer:0.5,native-ip:0.5,native-fm:0.5",
        with_fm=True,
    )
    assert part.shape == (g.n,)
    assert gates and gates[-1]["valid"]
    assert gates[-1]["cut_match"] is True


def test_no_repair_keeps_check(monkeypatch):
    """--no-repair plumbing: the gate still checks (and reports) but
    leaves the partition alone."""
    from kaminpar_tpu.cli import build_parser, make_context

    args = build_parser().parse_args(["g.metis", "-k", "4", "--no-repair"])
    ctx = make_context(args)
    assert ctx.resilience.repair is False
    assert ctx.resilience.output_gate is True


# ---------------------------------------------------------------------------
# native build: timeout config + poisoned-cache clean rebuild
# ---------------------------------------------------------------------------


def test_native_build_timeout_env(monkeypatch):
    from kaminpar_tpu import native

    monkeypatch.setenv(native.BUILD_TIMEOUT_ENV, "123.5")
    assert native.build_timeout() == 123.5
    monkeypatch.setenv(native.BUILD_TIMEOUT_ENV, "junk")
    assert native.build_timeout() == native.DEFAULT_BUILD_TIMEOUT_S


def test_native_unusable_cache_dir_degrades(monkeypatch):
    """An unusable cache dir is a degradation (ctypes-free mode), not a
    FileNotFoundError crash from inside _build."""
    from kaminpar_tpu import native

    monkeypatch.setenv(
        native.CACHE_DIR_ENV, "/proc/definitely/not/writable"
    )
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    assert native.get_lib() is None
    (ev,) = telemetry.events("degraded")
    assert ev.attrs["site"] == "native-build"


def test_cli_rejects_bad_fault_plan_at_startup(monkeypatch, capsys):
    from kaminpar_tpu.cli import main

    monkeypatch.setenv(faults.ENV_VAR, "refner:nth=1")  # typo'd site
    rc = main(["gen:grid2d;rows=4;cols=4", "-k", "2"])
    assert rc == 1
    err = capsys.readouterr().err
    assert "refner" in err and faults.ENV_VAR in err


def test_native_poisoned_cache_clean_rebuild(monkeypatch, tmp_path):
    """A corrupted cached .so must trigger one clean rebuild, not a
    permanent silent fall back to ctypes-free mode."""
    import glob
    import shutil

    from kaminpar_tpu import native

    if not shutil.which("g++"):
        pytest.skip("no C++ toolchain")
    # reuse the package cache's artifact NAME (tag = sources + flags)
    built = glob.glob(os.path.join(native._DIR, "libkmpnative-*.so"))
    if not built:
        built = [native._build()]
    poisoned = tmp_path / os.path.basename(built[0])
    poisoned.write_bytes(b"\x7fELF this is not a shared object")
    monkeypatch.setenv(native.CACHE_DIR_ENV, str(tmp_path))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    lib = native.get_lib()
    assert lib is not None  # clean rebuild succeeded
    assert telemetry.events("degraded") == []
    # the poisoned artifact was replaced by a working one
    rebuilt = tmp_path / os.path.basename(built[0])
    assert rebuilt.exists() and rebuilt.stat().st_size > 1000
