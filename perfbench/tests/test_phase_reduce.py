"""Device and idle seconds per program phase: on a synthetic profile whose
answers are known by construction, on the chip trace without program
spans (``small.xplane.pb``: the ``run_id`` join), and on the annotated
chip trace ``record_phase_trace.py`` recorded (run by hand)."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from perfbench.harness import phase_reduce as pr
from perfbench.harness.registry import Registry

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

TEN = ("coarsening_device_s", "refinement_device_s", "jet_device_s",
       "extend_device_s", "coarsening_idle_s", "refinement_idle_s",
       "extend_idle_s", "driver_idle_s", "phase_attributed_share",
       "tracing_overhead")


def _ev(name, start_us, end_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3,
              duration_ns=(end_us - start_us) * 1e3,
              stats=list(stats.items()))


def _span(path, start_us, end_us, **stats):
    return _ev(pr.SPAN_PREFIX + path, start_us, end_us, **stats)


def _profile():
    """One request of 1000 us.  Nine launches: A under ``coarsening``; B
    enqueued under ``lp-refinement`` and executed during ``jet`` (the
    asynchronous-dispatch case); C under ``jet``; D without a ``run_id``,
    found through its ``PjitFunction`` call under ``extend-pull``; E under
    a ``jet`` below ``extend-partition``; F under ``partition-download``;
    G with no join at all; H enqueued after the request (the benchmark's
    own checking); I in the request's own time before ``partitioning``."""
    up = "partitioning.uncoarsening"
    python = NS(name="python3", events=[
        _span("request", 0, 1000, k=16, n=64, m=512),
        _span("partitioning", 10, 990),
        _span("partitioning.coarsening", 20, 200),
        _span(up, 200, 900),
        _span(up + ".lp-refinement", 210, 220),
        _span(up + ".jet", 220, 500),
        _span(up + ".extend-pull", 500, 560),
        _span(up + ".extend-partition", 560, 800),
        _span(up + ".extend-partition.jet", 700, 780),
        _span("partitioning.partition-download", 900, 980),
        _ev("PjitFunction(slice)", 510, 515),
        _ev("PjitFunction(slice)", 510.5, 514.5),
        _ev("np.asarray(jax.Array)", 535, 600),
        _span("late", 1200, 1300),  # after the request: not its span
    ])
    runtime = NS(name="main/7", events=[
        _ev(pr.ENQUEUE_EVENT, at, at + 2, run_id=run_id)
        for run_id, at in ((9, 5), (1, 30), (2, 212), (3, 225), (5, 710),
                           (6, 905), (8, 1100))])
    modules = [_ev("jit_first(11)", 6, 8, run_id=9),
               _ev("jit_cluster(12)", 40, 140, run_id=1),
               _ev("jit_lp(13)", 230, 330, run_id=2),
               _ev("jit_jet(14)", 330, 400, run_id=3),
               _ev("jit_slice(15)", 520, 530),
               _ev("jit_jet(14)", 720, 760, run_id=5),
               _ev("jit_pull(16)", 910, 920, run_id=6),
               _ev("jit_mystery(17)", 950, 955),
               _ev("jit_check(18)", 1110, 1150, run_id=8)]
    ops = [_ev("fusion.1", 6, 8),
           _ev("while.2", 40, 100), _ev("sort.3", 50, 90),  # nested
           _ev("fusion.4", 110, 140),                       # a 10 us hole
           _ev("fusion.5", 230, 330), _ev("fusion.6", 330, 400),
           _ev("slice.7", 520, 530), _ev("fusion.6", 720, 760),
           _ev("copy.8", 910, 920), _ev("fusion.9", 950, 955),
           _ev("reduce.10", 1110, 1150)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=ops)])
    later = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Modules", events=[_ev("jit_other(1)", 0, 999)])])
    host = NS(name="/host:CPU", lines=[runtime, python])
    return NS(planes=[later, host, device])


US = 1e-6


def test_launches_go_to_the_span_that_enqueued_them():
    out = pr.reduce_profile(_profile())
    spans = out["spans"]
    up = "partitioning.uncoarsening"
    assert list(spans)[:3] == ["request", "partitioning",
                               "partitioning.coarsening"]
    assert "late" not in spans
    assert spans["partitioning.coarsening"]["launches"] == 1
    assert spans["partitioning.coarsening"]["device_s"] == pytest.approx(
        90 * US)  # the union of its operations, not the module's 100
    # enqueued under lp-refinement, executed while jet was open
    assert spans[up + ".lp-refinement"]["launches"] == 1
    assert spans[up + ".lp-refinement"]["device_s"] == pytest.approx(100 * US)
    assert spans[up + ".jet"]["device_s"] == pytest.approx(70 * US)
    # no run_id: the outer one of the two PjitFunction events
    assert spans[up + ".extend-pull"]["launches"] == 1
    assert out["joined"] == {"run_id": 6, "pjit": 1}
    assert out["unowned"] == {"launches": 1,
                              "device_s": pytest.approx(5 * US)}
    assert out["outside_request"] == {"launches": 1,
                                      "device_s": pytest.approx(40 * US)}
    assert spans["request"]["launches"] == 1
    assert out["request"]["args"] == {"k": "16", "n": "64", "m": "512"}
    assert out["launch_lag_s"] == pytest.approx(1 * US)


def test_layers_roll_up_innermost_match_first():
    out = pr.reduce_profile(_profile())
    device = {name: layer["device_s"] for name, layer in out["layers"].items()}
    idle = {name: layer["idle_s"] for name, layer in out["layers"].items()}
    # the jet under extend-partition counts for refinement, not extend
    assert device == {"coarsening": pytest.approx(90 * US),
                      "refinement": pytest.approx(210 * US),
                      "extend": pytest.approx(10 * US),
                      "driver": pytest.approx(12 * US)}
    assert out["jet"]["device_s"] == pytest.approx(110 * US)
    assert idle == {"coarsening": pytest.approx(90 * US),
                    "refinement": pytest.approx(160 * US),
                    "extend": pytest.approx(210 * US),
                    "driver": pytest.approx(213 * US)}
    assert pr.layer_of("partitioning.extend-partition.coarsening.jet") == (
        "refinement", "jet")
    assert pr.layer_of("partitioning.extend-partition.coarsening") == (
        "coarsening", "coarsening")
    assert pr.layer_of("partitioning.initial-partitioning.graph-download") \
        == ("driver", "")


def test_both_sum_identities():
    out = pr.reduce_profile(_profile())
    assert out["request_device_s"] == pytest.approx(327 * US)
    assert out["busy_in_request_s"] == pytest.approx(327 * US)
    assert (sum(layer["device_s"] for layer in out["layers"].values())
            + out["unowned"]["device_s"]) == pytest.approx(
                out["request_device_s"], rel=pr.TOLERANCE)
    assert sum(layer["idle_s"] for layer in out["layers"].values()) \
        == pytest.approx(out["request"]["seconds"]
                         - out["busy_in_request_s"], rel=pr.TOLERANCE)
    assert out["idle_in_request_s"] == pytest.approx(673 * US)
    # the launches in the request's own time and without a join are not
    # attributed
    assert out["attributed_share"] == pytest.approx(100 * 320 / 327)
    broken = dict(out, request_device_s=2 * out["request_device_s"])
    with pytest.raises(ValueError, match="device seconds"):
        pr.check(broken)
    broken = dict(out, idle_in_request_s=0.5 * out["idle_in_request_s"])
    with pytest.raises(ValueError, match="idle seconds"):
        pr.check(broken)


def test_longest_gaps_name_a_jax_event_and_a_program_span():
    out = pr.reduce_profile(_profile(), top=2)
    up = "partitioning.uncoarsening"
    assert out["gaps"] == [
        [pytest.approx(190 * US), "np.asarray(jax.Array)",
         up + ".extend-partition"],
        [pytest.approx(150 * US), "host", up]]
    table = pr.render(pr.reduce_profile(_profile()))
    assert "partitioning.uncoarsening.extend-partition.jet" in table
    assert "layer refinement" in table and "k=16" in table


def test_without_spans_or_without_a_device_there_is_nothing():
    profile = _profile()
    for line in profile.planes[1].lines:
        line.events = [e for e in line.events
                       if not e.name.startswith(pr.SPAN_PREFIX)]
    assert pr.reduce_profile(profile) is None
    profile = _profile()
    profile.planes = profile.planes[1:2]
    assert pr.reduce_profile(profile) is None


def _reader(name):
    return Registry(REPO, BENCH).layer_reader(name)


def test_the_ten_readers(capsys):
    untraced = [{"wall_s": 2.0}, {"wall_s": 4.0}, {"wall_s": 3.0}]
    run = {"samples": [{"traced": True, "wall_s": 3.3}] + untraced,
           "traced_wall_s": 3.3, "phases": pr.reduce_profile(_profile())}
    got = {name: _reader(name).read(run) for name in TEN}
    assert got == {
        "coarsening_device_s": pytest.approx(90 * US),
        "refinement_device_s": pytest.approx(210 * US),
        "jet_device_s": pytest.approx(110 * US),
        "extend_device_s": pytest.approx(10 * US),
        "coarsening_idle_s": pytest.approx(90 * US),
        "refinement_idle_s": pytest.approx(160 * US),
        "extend_idle_s": pytest.approx(210 * US),
        "driver_idle_s": pytest.approx(213 * US),
        "phase_attributed_share": pytest.approx(100 * 320 / 327),
        "tracing_overhead": pytest.approx(10.0)}
    # a run whose trace has no program span or no device plane (the
    # parent commit; the CPU), and a run that was not traced
    for run in ({"samples": untraced, "traced_wall_s": None},
                {"samples": [{"traced": True, "wall_s": 1.0, "xplane": None}]
                 + untraced, "traced_wall_s": 1.0},
                {"samples": [{"traced": True, "wall_s": 1.0, "xplane":
                              os.path.join(DATA, "small.xplane.pb")}]
                 + untraced, "traced_wall_s": 1.0}):
        assert [_reader(name).read(run) for name in TEN] == [None] * 10
        assert run["phases"] is None
    assert capsys.readouterr().out == ""  # no table without phases


def test_run_id_join_on_the_chip_trace():
    """``small.xplane.pb`` (a v5e, three launches of ``jit_small_step``,
    no program span) with a request and two scopes drawn around what its
    host plane recorded: every launch finds its ``DoEnqueueProgram`` by
    ``run_id``, and the owner is the span open at that instant."""
    from jax.profiler import ProfileData

    real = ProfileData.from_file(os.path.join(DATA, "small.xplane.pb"))
    planes = {plane.name: plane for plane in real.planes}
    enqueues = sorted(
        float(ev.start_ns) for line in planes["/host:CPU"].lines
        for ev in line.events if ev.name == pr.ENQUEUE_EVENT)
    assert len(enqueues) == 3
    first, second, third = (ns / 1e3 for ns in enqueues)
    drawn = NS(name="drawn", events=[
        _span("request", first - 500, third + 30000, k=2),
        _span("a", second - 1000, second + 100),
        _span("b", third - 1000, third + 100)])
    lines = list(planes["/host:CPU"].lines)
    out = pr.reduce_profile(NS(planes=[
        NS(name="/host:CPU", lines=lines + [drawn]),
        planes["/device:TPU:0"]]))
    assert out["joined"] == {"run_id": 3, "pjit": 0}
    assert [out["spans"][p]["launches"] for p in ("request", "a", "b")] == [
        1, 1, 1]
    with open(os.path.join(DATA, "small.expected.json")) as f:
        expected = json.load(f)
    # each launch's device seconds are a third of the trace's busy time
    for path in ("request", "a", "b"):
        assert out["spans"][path]["device_s"] == pytest.approx(
            expected["device_busy_s"] / 3, rel=1e-3)
    assert out["request_device_s"] == pytest.approx(
        expected["device_busy_s"], rel=1e-6)
    # without the stat the PjitFunction calls give the same owners
    bare = [NS(name=line.name, events=[
        NS(name=ev.name, start_ns=ev.start_ns, duration_ns=ev.duration_ns,
           stats=[kv for kv in ev.stats if kv[0] != pr.RUN_ID])
        for ev in line.events]) for line in lines]
    again = pr.reduce_profile(NS(planes=[
        NS(name="/host:CPU", lines=bare + [drawn]),
        planes["/device:TPU:0"]]))
    assert again["joined"] == {"run_id": 0, "pjit": 3}
    assert [again["spans"][p]["launches"] for p in ("request", "a", "b")] \
        == [1, 1, 1]


def test_recorded_phase_trace_from_the_chip():
    """The annotated v5e trace of ``record_phase_trace.py`` against what
    the script knows by construction (``phase.recorded.json``) and the
    reduction read off it by hand (``phase.expected.json``)."""
    path = os.path.join(DATA, "phase.xplane.pb")
    if not os.path.isfile(path):
        pytest.skip("no recorded phase trace in this checkout")
    with open(os.path.join(DATA, "phase.expected.json")) as f:
        expected = json.load(f)
    out = pr.reduce_file(path)
    assert out["joined"] == expected["joined"]
    assert out["unowned"]["launches"] == 0
    assert {p: row["launches"] for p, row in out["spans"].items()} \
        == expected["launches"]
    for name, layer in expected["layers"].items():
        assert out["layers"][name]["launches"] == layer["launches"]
        assert out["layers"][name]["device_s"] == pytest.approx(
            layer["device_s"], rel=1e-6)
        assert out["layers"][name]["idle_s"] == pytest.approx(
            layer["idle_s"], rel=1e-6)
    assert out["attributed_share"] == pytest.approx(
        expected["attributed_share"], rel=1e-6)
    assert [gap[2] for gap in out["gaps"][:len(expected["gap_spans"])]] \
        == expected["gap_spans"]
