"""The compile account with telemetry off (telemetry/compile_account.py):
records per executable, requests on the program's clock, the package's
import seconds, and the benchmark's four readers of them
(perfbench/layer_metrics/_setup_account.py)."""

import importlib.util
import os

import jax
import numpy as np
import pytest

import kaminpar_tpu as ktp
from kaminpar_tpu import telemetry
from kaminpar_tpu.telemetry import compile_account
from kaminpar_tpu.utils import timer
from kaminpar_tpu.utils.logger import OutputLevel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("trace_lower_s", "first_request_s", "setup_unattributed_s",
           "package_import_s")


@pytest.fixture
def account():
    """The process's account, emptied, with telemetry off."""
    was_on = telemetry.enabled()
    telemetry.disable()
    compile_account.reset()
    compile_account.forget()
    yield compile_account
    compile_account.forget()
    if was_on:
        telemetry.enable()


def _reader(name):
    path = os.path.join(REPO, "perfbench", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"_reader_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _partition(graph, seed=1):
    solver = ktp.KaMinPar("default")
    solver.set_output_level(OutputLevel.QUIET)
    return solver.set_graph(graph).compute_partition(
        k=4, epsilon=0.03, seed=seed)


def test_fresh_jit_is_one_record_under_the_open_scope(account):
    assert not telemetry.enabled()
    x = np.arange(8, dtype=np.int32)  # no executable of its own

    def compile_probe_fn(v):
        return v * 2 + 1

    with timer.GLOBAL_TIMER.scope("compile-probe"):
        jax.jit(compile_probe_fn)(x).block_until_ready()
    (record,) = account.records()
    assert record["fun_name"] == "compile_probe_fn"
    assert record["scope"] == "compile-probe"
    assert record["trace_s"] > 0 and record["lower_s"] > 0
    assert record["backend_s"] > 0
    assert record["request"] == 0 and record["nested"] is False
    assert record["cache_hit"] is False  # the suite keeps no compile cache
    assert record["end"] > 0
    totals = account.summary()["totals"]
    assert totals["records"] == totals["closed"] == 1
    assert totals["unplaced_events"] == 0
    # the per-scope sums see the same seconds, telemetry off
    phase = account.snapshot()["phases"]["compile-probe"]
    assert phase["compiles"] == 1
    assert phase["compile_s"] == pytest.approx(record["backend_s"], abs=1e-5)


def test_inlined_jitted_helpers_are_counted_once(account):
    @jax.jit
    def helper(v):
        return v + 3

    def outer(v):
        return helper(v) * helper(v + 1)

    jax.jit(outer)(np.arange(8, dtype=np.int32)).block_until_ready()
    (record,) = account.records()
    assert record["fun_name"] == "outer"
    assert record["inlined_traces"] >= 1  # helper, and jnp's own helpers
    summary = account.summary()
    assert summary["totals"]["inlined_traces"] == record["inlined_traces"]
    # the helper's tracing lies inside outer's and is not added again
    phase = account.snapshot()["phases"][compile_account.OUTSIDE]
    assert phase["trace_s"] == pytest.approx(record["trace_s"], abs=1e-5)


def test_trace_without_a_backend_event_stays_an_open_record(account):
    def only_traced(v):
        return v - 1

    jax.eval_shape(jax.jit(only_traced), np.arange(4, dtype=np.int32))
    (record,) = account.records()
    assert record["fun_name"] == "only_traced"
    assert record["trace_s"] > 0 and record["backend_s"] is None
    assert record["cache_hit"] is None
    totals = account.summary()["totals"]
    assert totals["records"] == 1 and totals["closed"] == 0
    assert account.summary()["top_backend"] == []


def test_two_requests_have_ordinals_and_walls(account, rgg2d):
    first = _partition(rgg2d)
    after_first = account.records()
    second = _partition(rgg2d)
    assert np.array_equal(first, second)
    summary = account.summary()
    requests = summary["requests"]
    assert requests["count"] == 2 and requests["later"] == 1
    assert requests["first"]["ordinal"] == 1
    assert after_first and {r["request"] for r in after_first} == {1}
    assert all(r["request"] == 2
               for r in account.records()[len(after_first):])
    booked = (requests["first"]["trace_s"] + requests["first"]["lower_s"]
              + requests["first"]["backend_s"])
    assert 0 < booked <= requests["first"]["wall_s"]
    assert requests["first"]["records"] == len(after_first)
    assert requests["later_median_wall_s"] < requests["first"]["wall_s"]
    assert summary["through_first_request"]["records"] == len(after_first)
    assert set(summary["layers"]) <= {"coarsening", "refinement", "extend",
                                      "initial partitioning", "driver"}
    assert summary["layers"]["refinement"]["closed"] > 0
    text = account.render()
    assert "by layer:" in text and "request 1: wall" in text


def test_a_request_inside_a_request_is_the_outer_one(account):
    with account.request() as outer:
        with account.request() as inner:
            assert inner == outer == 1
    with account.request() as third:
        assert third == 2
    requests = account.summary()["requests"]
    assert requests["count"] == 2 and requests["later"] == 1


def test_requests_keep_the_first_and_the_last_64(account):
    for _ in range(70):
        with account.request():
            pass
    requests = account.summary()["requests"]
    assert requests["count"] == 70 and requests["later"] == 64
    assert requests["first"]["ordinal"] == 1


def test_reset_clears_the_sums_and_keeps_the_records(account):
    jax.jit(lambda v: v * 5)(np.arange(4, dtype=np.int32))
    assert account.snapshot()["totals"]["compiles"] == 1
    account.reset()
    assert account.snapshot()["totals"]["compiles"] == 0
    assert len(account.records()) == 1
    account.forget()
    assert account.records() == []


def test_snapshot_keeps_exactly_its_keys(account):
    """The run report's `compile` section (schema 14) must not move."""
    jax.jit(lambda v: v * 7)(np.arange(4, dtype=np.int32))
    snap = account.snapshot()
    assert set(snap) == {"caveat", "totals", "phases"}
    assert set(snap["totals"]) == {
        "trace_s", "lower_s", "compile_s", "compiles", "cache_requests",
        "persistent_cache_hits", "persistent_cache_misses"}
    (phase,) = snap["phases"].values()
    assert set(phase) == {"trace_s", "lower_s", "compile_s", "compiles"}


def test_package_import_is_stamped():
    seconds = compile_account.summary()["package_import_s"]
    assert seconds is not None and 0 < seconds < 600


def test_layer_roll_up_takes_the_innermost_scope():
    layer_of = compile_account.layer_of
    assert layer_of("partitioning.coarsening.lp-clustering") == "coarsening"
    assert layer_of("partitioning.uncoarsening.jet.jet-edges") == "refinement"
    assert layer_of("partitioning.extend-partition.jet") == "refinement"
    assert layer_of("partitioning.extend-partition.extend-pull") == "extend"
    assert layer_of("partitioning.initial-partitioning.graph-download") == (
        "initial partitioning")
    assert layer_of("partitioning.device-upload") == "driver"
    assert layer_of(compile_account.OUTSIDE) == "driver"


# a summary whose answers are known by construction: request 1 took 10 s,
# the later ones 2 s; it traced 1.5 s, lowered 2.5 s and loaded for 3 s,
# and half a second of tracing happened before it
RECORDED = {
    "package_import_s": 0.75,
    "through_first_request": {"records": 4, "closed": 3, "cache_hits": 3,
                              "trace_s": 2.0, "lower_s": 2.5,
                              "backend_s": 3.0},
    "requests": {"count": 5, "later": 4, "later_median_wall_s": 2.0,
                 "first": {"ordinal": 1, "start": 100.0, "end": 110.0,
                           "wall_s": 10.0, "records": 3, "closed": 3,
                           "cache_hits": 3, "trace_s": 1.5, "lower_s": 2.5,
                           "backend_s": 3.0}},
}
EXPECTED = {"trace_lower_s": 4.5, "first_request_s": 10.0,
            "setup_unattributed_s": 1.0, "package_import_s": 0.75}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_recorded_summary(name, monkeypatch):
    monkeypatch.setattr(compile_account, "summary", lambda: RECORDED)
    assert _reader(name).read({}) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_the_accessor(name, monkeypatch):
    """The parent commit's program: a compile account with no summary."""
    monkeypatch.delattr(compile_account, "summary")
    assert _reader(name).read({}) is None


def test_readers_give_none_before_any_request(account):
    empty = account.summary()
    assert empty["requests"]["first"] is None
    for name in ("trace_lower_s", "first_request_s", "setup_unattributed_s"):
        assert _reader(name).read({}) is None
