"""Performance observatory (telemetry/perf.py): zero-jaxpr-impact pin,
histogram bucket-edge semantics, compile-cost capture + scope
attribution, pad-waste accounting, memory sampling at barriers, the
`telemetry.top` triage CLI, and the serving-aware report diff."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaminpar_tpu import telemetry
from kaminpar_tpu.telemetry import perf
from kaminpar_tpu.telemetry.perf import Histogram
from kaminpar_tpu.utils import timer


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    telemetry.disable()
    yield
    telemetry.reset()
    telemetry.disable()


# ---------------------------------------------------------------------------
# zero device-code impact
# ---------------------------------------------------------------------------


def test_perf_layer_has_zero_jaxpr_impact(monkeypatch):
    """The observatory must be invisible to tracing: the SAME jaxpr
    whether perf is enabled, disabled via KAMINPAR_TPU_PERF=0, or
    telemetry is off entirely — cost capture lives at the compile
    boundary and barriers, never inside jitted code."""
    from kaminpar_tpu.ops.lp import lp_cluster
    from kaminpar_tpu.graphs.csr import device_graph_from_host
    from kaminpar_tpu.graphs import factories

    g = device_graph_from_host(factories.make_grid_graph(8, 8))

    def jaxpr_of_refine():
        def probe(node_w):
            return jnp.cumsum(node_w) + jnp.sum(g.edge_w)

        return str(jax.make_jaxpr(probe)(g.node_w))

    # progress capture off so only the PERF toggle varies between runs
    monkeypatch.setenv("KAMINPAR_TPU_PROGRESS", "0")
    telemetry.disable()
    j_off = jaxpr_of_refine()

    telemetry.enable()
    monkeypatch.setenv("KAMINPAR_TPU_PERF", "0")
    assert not perf.enabled()
    j_perf_off = jaxpr_of_refine()

    monkeypatch.delenv("KAMINPAR_TPU_PERF")
    assert perf.enabled()
    j_perf_on = jaxpr_of_refine()

    assert j_off == j_perf_off == j_perf_on
    # the real pipeline entry is pinned too: lp_cluster's traced shape
    # cannot depend on the perf toggle (it threads no perf state)
    assert lp_cluster is not None


def test_enabled_gates_on_telemetry_and_env(monkeypatch):
    telemetry.disable()
    assert not perf.enabled()
    telemetry.enable()
    assert perf.enabled()
    monkeypatch.setenv("KAMINPAR_TPU_PERF", "0")
    assert not perf.enabled()


# ---------------------------------------------------------------------------
# histogram semantics
# ---------------------------------------------------------------------------


def test_histogram_empty_quantiles_are_none():
    h = Histogram()
    assert h.quantile(0.5) is None
    snap = h.snapshot()
    assert snap["count"] == 0
    assert snap["p50_ms"] is None
    assert snap["p95_ms"] is None
    assert snap["p99_ms"] is None
    assert snap["mean_ms"] is None
    assert snap["buckets"] == []


def test_histogram_boundary_values_land_in_their_bucket():
    h = Histogram()
    edge = Histogram.EDGES[10]
    h.record(edge)  # exactly on a bucket edge
    assert h.counts[10] == 1
    # the quantile is the bucket's upper edge clamped to the observed
    # max — exact for a boundary value
    assert h.quantile(0.5) == pytest.approx(edge)
    # just below the edge lands one bucket down
    h2 = Histogram()
    h2.record(edge * 0.999)
    assert h2.counts[9] == 1


def test_histogram_under_and_overflow_are_clamped():
    h = Histogram()
    h.record(0.0)  # below the first edge
    h.record(1e9)  # beyond the last edge
    assert h.counts[0] == 1
    assert h.counts[-1] == 1
    assert h.count == 2
    assert h.quantile(0.99) == pytest.approx(1e9)  # clamped to max


def test_histogram_percentile_ordering_and_reset():
    h = Histogram()
    for ms in (1, 1, 1, 2, 2, 5, 10, 50, 200, 900):
        h.record(ms / 1000.0)
    snap = h.snapshot()
    assert snap["count"] == 10
    assert snap["p50_ms"] <= snap["p95_ms"] <= snap["p99_ms"]
    assert snap["max_ms"] == pytest.approx(900.0)
    h.reset()
    assert h.count == 0 and h.quantile(0.5) is None


# ---------------------------------------------------------------------------
# compile-cost capture and scope attribution
# ---------------------------------------------------------------------------


def test_compile_capture_attributes_to_open_scope():
    telemetry.enable()
    perf.install()
    perf.reset()
    # a distinctive shape so the in-process jit cache cannot absorb it
    x = jnp.arange(3333, dtype=jnp.float32)

    with timer.scoped_timer("perf-test-scope"):
        y = jax.jit(lambda v: (v * 3.0 + 1.0).sum())(x)
        float(y)

    snap = perf.snapshot()
    assert snap["enabled"] is True
    roof = snap["roofline"]
    assert "perf-test-scope" in roof, sorted(roof)
    row = roof["perf-test-scope"]
    assert row["compiles"] >= 1
    assert row["bytes"] > 0
    # wall joined from the timer tree -> achieved rates + utilization
    assert row["wall_s"] > 0
    assert "hbm_util" in row and row["hbm_util"] >= 0
    assert "deficit_s" in row
    assert snap["totals"]["bytes"] >= row["bytes"]


def test_deficit_uses_exclusive_wall():
    # cost attributed to a non-leaf scope ran in that scope's OWN time;
    # the deficit ranking must not re-count the children's wall
    telemetry.enable()
    perf.reset()
    with timer.scoped_timer("deficit-parent"):
        time.sleep(0.01)
        with timer.scoped_timer("child"):
            time.sleep(0.05)
    with perf._lock:
        perf._scopes["deficit-parent"] = {
            "flops": 1.0, "bytes": 1.0, "output_bytes": 0,
            "temp_bytes": 0, "arg_bytes": 0, "compiles": 1,
            "executables": [],
        }
    row = perf.snapshot()["roofline"]["deficit-parent"]
    assert row["self_s"] < row["wall_s"]
    # utilization is ~0 here, so deficit ~= the exclusive wall — well
    # below the inclusive wall that contains the 50ms child
    assert row["deficit_s"] <= row["self_s"] + 1e-9
    assert row["deficit_s"] < 0.05


def test_peaks_env_override(monkeypatch):
    monkeypatch.setenv("KAMINPAR_TPU_PEAK_GBPS", "123.5")
    monkeypatch.setenv("KAMINPAR_TPU_PEAK_GFLOPS", "456")
    p = perf.peaks()
    assert p["gbps"] == 123.5
    assert p["gflops"] == 456.0
    assert p["source"] == "env"
    monkeypatch.delenv("KAMINPAR_TPU_PEAK_GBPS")
    monkeypatch.delenv("KAMINPAR_TPU_PEAK_GFLOPS")
    p = perf.peaks()
    assert p["source"].startswith("default:")
    assert p["gbps"] > 0 and p["gflops"] > 0


def test_peaks_come_from_the_device_kind_table():
    """One table keyed by device_kind; an unlisted device is an error,
    never a default."""
    assert perf.device_peaks("TPU v5 lite") == (819.0, 197_000.0)
    with pytest.raises(perf.UnknownDeviceError, match="TPU v9"):
        perf.device_peaks("TPU v9")


# ---------------------------------------------------------------------------
# pad-waste attribution
# ---------------------------------------------------------------------------


def test_record_padding_aggregates_per_scope_and_bucket():
    telemetry.enable()
    perf.reset()
    with timer.scoped_timer("pad-scope"):
        perf.record_padding(n=100, n_pad=256, m=300, m_pad=512)
        perf.record_padding(n=120, n_pad=256, m=310, m_pad=512)
        perf.record_padding(k=3, k_pad=4)
    rows = perf.snapshot()["pad_waste"]
    by_bucket = {(r["scope"], r["bucket"]): r for r in rows}
    nm = by_bucket[("pad-scope", "256/512/-")]
    assert nm["launches"] == 2
    assert nm["n_real"] == 220 and nm["n_pad"] == 512
    assert nm["n_waste"] == pytest.approx(1 - 220 / 512, abs=1e-4)
    assert nm["m_waste"] == pytest.approx(1 - 610 / 1024, abs=1e-4)
    kk = by_bucket[("pad-scope", "-/-/4")]
    assert kk["k_real"] == 3 and kk["k_pad"] == 4
    assert kk["k_waste"] == pytest.approx(0.25)
    # per-axis totals: k waste must not be masked by the much larger
    # n/m element counts that dominate the cross-axis headline
    axes = perf.snapshot()["totals"]["pad_waste_axes"]
    assert axes["k"] == pytest.approx(0.25)
    assert axes["n"] == pytest.approx(1 - 220 / 512, abs=1e-4)
    assert axes["m"] == pytest.approx(1 - 610 / 1024, abs=1e-4)


def test_device_upload_records_padding():
    from kaminpar_tpu.graphs import factories
    from kaminpar_tpu.graphs.csr import device_graph_from_host

    telemetry.enable()
    perf.reset()
    g = factories.make_grid_graph(10, 10)
    device_graph_from_host(g)
    rows = perf.snapshot()["pad_waste"]
    assert rows, "upload recorded no pad row"
    row = rows[0]
    assert row["n_pad"] >= g.n + 1
    assert row["m_pad"] >= g.m
    assert 0.0 <= row["n_waste"] <= 1.0


def test_record_padding_disabled_is_noop(monkeypatch):
    telemetry.enable()
    perf.reset()
    monkeypatch.setenv("KAMINPAR_TPU_PERF", "0")
    from kaminpar_tpu.caching import record_padding

    record_padding(n=10, n_pad=256)
    monkeypatch.delenv("KAMINPAR_TPU_PERF")
    assert perf.snapshot()["pad_waste"] == []


# ---------------------------------------------------------------------------
# memory watermarks
# ---------------------------------------------------------------------------


def test_sample_memory_records_event_and_snapshot():
    telemetry.enable()
    perf.reset()
    sample = perf.sample_memory("unit-test-stage", level=3)
    assert sample is not None
    assert sample["live_bytes"] >= 0
    assert sample["level"] == 3
    evs = telemetry.events("perf-memory")
    assert evs and evs[-1].attrs["stage"] == "unit-test-stage"
    mem = perf.snapshot()["memory"]
    assert mem["samples"]
    assert mem["peak_live_bytes"] >= 0


def test_sample_memory_disabled_returns_none():
    telemetry.disable()
    assert perf.sample_memory("nope") is None


def test_barriers_sample_memory_during_a_run():
    """End-to-end: a partition run crosses the PR-5 barriers, so the
    report must carry per-stage samples without any checkpoint dir."""
    import kaminpar_tpu as ktp
    from kaminpar_tpu.graphs import factories
    from kaminpar_tpu.telemetry.report import build_run_report
    from kaminpar_tpu.utils.logger import OutputLevel

    telemetry.enable()
    g = factories.make_grid_graph(24, 24)
    p = ktp.KaMinPar("default")
    p.set_output_level(OutputLevel.QUIET)
    p.set_graph(g).compute_partition(k=2, epsilon=0.05, seed=1)
    report = build_run_report()
    mem = report["perf"]["memory"]
    assert mem["samples"], "no barrier samples in a full run"
    stages = {s["stage"] for s in mem["samples"]}
    assert any(st.startswith("initial") or st.startswith("result")
               for st in stages), stages


def test_chrome_trace_emits_memory_counter_track(tmp_path):
    from kaminpar_tpu.telemetry.chrome_trace import chrome_trace

    telemetry.enable()
    perf.reset()
    perf.sample_memory("trace-stage")
    trace = chrome_trace()
    counters = [
        e for e in trace["traceEvents"]
        if e["ph"] == "C" and e["name"] == "memory"
    ]
    assert counters, "perf-memory event produced no counter track"
    assert "live_bytes" in counters[0]["args"]


# ---------------------------------------------------------------------------
# telemetry.top triage CLI
# ---------------------------------------------------------------------------


def _fake_report(with_perf: bool = True) -> dict:
    report = {
        "schema_version": 5 if with_perf else 4,
        "scope_tree": {
            "partitioning": {
                "elapsed_s": 2.0, "count": 1,
                "children": {
                    "coarsening": {"elapsed_s": 1.5, "count": 1,
                                   "children": {}},
                },
            },
        },
        "serving": {"enabled": False},
    }
    if with_perf:
        report["perf"] = {
            "enabled": True,
            "peaks": {"gbps": 100.0, "gflops": 1000.0, "source": "env"},
            "totals": {"flops": 5e6, "bytes": 4e7, "compiles": 3,
                       "wall_s": 2.0, "hbm_util": 0.0002,
                       "pad_waste": 0.25},
            "roofline": {
                "partitioning.coarsening": {
                    "flops": 5e6, "bytes": 4e7, "compiles": 3,
                    "wall_s": 1.5, "calls": 1, "achieved_gbps": 0.027,
                    "achieved_gflops": 0.003, "hbm_util": 0.0003,
                    "flops_util": 0.0, "deficit_s": 1.4995,
                    "output_bytes": 10, "temp_bytes": 0,
                    "executables": [],
                },
            },
            "memory": {
                "peak_live_bytes": 123456,
                "samples": [{"t": 0.5, "stage": "coarsen:1",
                             "live_bytes": 123456}],
                "levels": [{"level": 1, "n": 100, "m": 400,
                            "n_pad": 256, "m_pad": 512,
                            "buffer_bytes": 9000}],
            },
            "pad_waste": [
                {"scope": "partitioning.device-upload",
                 "bucket": "256/512/-", "launches": 1,
                 "n_real": 101, "n_pad": 256, "n_waste": 0.6055,
                 "m_real": 400, "m_pad": 512, "m_waste": 0.2188},
            ],
        }
    return report


def test_top_renders_and_exits_zero(tmp_path, capsys):
    from kaminpar_tpu.telemetry import top

    path = tmp_path / "r.json"
    path.write_text(json.dumps(_fake_report()))
    assert top.main([str(path), "--require-roofline"]) == 0
    out = capsys.readouterr().out
    assert "utilization deficit" in out
    assert "partitioning.coarsening" in out
    assert "pad-waste" in out
    assert "peak live" in out


def test_top_requires_roofline_flag_fails_without_rows(tmp_path, capsys):
    from kaminpar_tpu.telemetry import top

    path = tmp_path / "r.json"
    path.write_text(json.dumps(_fake_report(with_perf=False)))
    assert top.main([str(path)]) == 0  # renders, informational
    assert top.main([str(path), "--require-roofline"]) == 1


def test_top_diff_mode_aligns_scopes(tmp_path, capsys):
    from kaminpar_tpu.telemetry import top

    base = _fake_report()
    cand = _fake_report()
    cand["scope_tree"]["partitioning"]["children"]["coarsening"][
        "elapsed_s"] = 3.0
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(cand))
    assert top.main([str(b), "--diff", str(a)]) == 0
    out = capsys.readouterr().out
    assert "scope deltas" in out
    assert "1.500->3.000" in out


def test_top_bad_input_is_usage_error(tmp_path):
    from kaminpar_tpu.telemetry import top

    missing = tmp_path / "missing.json"
    assert top.main([str(missing)]) == 2


# ---------------------------------------------------------------------------
# serving-aware diff (satellite: v4 serving sections)
# ---------------------------------------------------------------------------


def _serving_section(served=3, failed=0, hit_rate=0.5, verdicts=None):
    verdicts = verdicts or {}
    requests = []
    for i in range(served):
        rid = f"r{i}"
        requests.append({
            "request_id": rid, "verdict": verdicts.get(rid, "served"),
            "k": 4, "cut": 10, "feasible": True,
        })
    return {
        "enabled": True,
        "requests": requests,
        "counts": {"served": sum(
            1 for r in requests if r["verdict"] == "served"
        ), "anytime": 0, "degraded": 0, "rejected": 0,
            "failed": failed + sum(
                1 for r in requests if r["verdict"] == "failed"
            )},
        "cache": {"hit_rate": hit_rate},
        "drained": False,
    }


def test_diff_gates_serving_served_count_and_hit_rate(tmp_path):
    from kaminpar_tpu.telemetry import diff as diff_mod

    base = {"schema_version": 4, "serving": _serving_section()}
    same = {"schema_version": 4, "serving": _serving_section()}
    lines, failures = diff_mod.diff_reports(base, same)
    assert failures == []

    worse = {
        "schema_version": 4,
        "serving": _serving_section(
            verdicts={"r2": "failed"}, hit_rate=0.1
        ),
    }
    lines, failures = diff_mod.diff_reports(base, worse)
    assert any("served rate regressed" in f for f in failures)
    assert any("hit rate regressed" in f for f in failures)
    assert any("r2: served -> failed" in ln for ln in lines)


def test_diff_serving_rate_not_absolute_count():
    # a smaller candidate batch that served 100% is no regression
    # against a larger base batch that also served 100%
    from kaminpar_tpu.telemetry import diff as diff_mod

    base = {"schema_version": 4, "serving": _serving_section(served=16)}
    cand = {"schema_version": 4, "serving": _serving_section(served=12)}
    _, failures = diff_mod.diff_reports(base, cand)
    assert failures == []


def test_diff_serving_one_sided_is_informational():
    from kaminpar_tpu.telemetry import diff as diff_mod

    base = {"schema_version": 3}
    cand = {"schema_version": 4, "serving": _serving_section()}
    lines, failures = diff_mod.diff_reports(base, cand)
    assert failures == []
    assert any("serve mode" in ln for ln in lines)


def test_diff_hit_rate_threshold_configurable():
    from kaminpar_tpu.telemetry import diff as diff_mod

    base = {"schema_version": 4, "serving": _serving_section(hit_rate=0.5)}
    cand = {"schema_version": 4, "serving": _serving_section(hit_rate=0.42)}
    _, failures = diff_mod.diff_reports(base, cand)
    assert failures == []  # within the default 0.10 absolute drop
    _, failures = diff_mod.diff_reports(
        base, cand, hit_rate_threshold=0.05
    )
    assert any("hit rate regressed" in f for f in failures)


# ---------------------------------------------------------------------------
# windowed cache/bucket stats (satellite: reset_records windowing)
# ---------------------------------------------------------------------------


def test_bounded_cache_window_counters():
    from kaminpar_tpu.caching import BoundedCache

    c = BoundedCache(max_entries=4, max_bytes=1 << 20)
    c.put("a", 1, 8)
    assert c.get("a") == 1
    assert c.get("b") is None
    s = c.stats()
    assert s["hits"] == 1 and s["misses"] == 1
    assert s["window"]["hits"] == 1 and s["window"]["misses"] == 1
    c.begin_window()
    assert c.get("a") == 1
    s = c.stats()
    # lifetime keeps accruing; the window restarted
    assert s["hits"] == 2 and s["window"]["hits"] == 1
    assert s["window"]["misses"] == 0
    assert s["window"]["hit_rate"] == 1.0


def test_bucket_tracker_window_and_per_bucket():
    from kaminpar_tpu.caching import BucketTracker

    t = BucketTracker()
    t.observe(100, 400, 4)
    t.observe(100, 400, 4)
    t.observe(5000, 20000, 8)
    assert t.stats()["hits"] == 1
    pb = t.per_bucket()
    assert sum(pb.values()) == 3 and len(pb) == 2
    t.begin_window()
    t.observe(100, 400, 4)
    s = t.stats()
    assert s["hits"] == 2  # lifetime
    assert s["window"]["hits"] == 1 and s["window"]["misses"] == 0
