"""The reduction from a trace to numbers: on a synthetic profile whose
answers are known by construction, and on the small trace recorded on
the chip by ``record_small_trace.py`` (run by hand)."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from perfbench.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")


def _ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3,
              stats=list(stats.items()))


def _profile():
    """One chip: two launches.  The first is a while loop (100 us) whose
    body holds a sort (40), a gather (20) and a fusion (10); the second a
    scatter (50).  Between them the device idles 200 us while the host is
    inside ``ExtractSubgraphs``; a 30 us gap inside the loop has no host
    event over it."""
    ops = [_ev("while.1", 0, 100), _ev("sort.3", 0, 40),
           _ev("gather.2", 40, 20), _ev("fusion.9", 90, 10),
           _ev("scatter.4", 300, 50)]
    # the loop's own time is 100 - 70 = 30 us; its interval has no hole,
    # so busy time is 100 + 50
    modules = [_ev("jit_round(7)", 0, 100), _ev("jit_apply(8)", 300, 50)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=ops),
        NS(name="Steps", events=[])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("ExtractSubgraphs", 110, 180), _ev("tiny", 120, 1)])])
    other = NS(name="/device:TPU:0 something else", lines=[])
    return NS(planes=[host, device, other])


def test_synthetic_profile():
    out = tr.reduce_profile(_profile())
    assert out["chips"] == 1 and out["launches"] == 2
    assert out["device_busy_s"] == pytest.approx(150e-6)
    assert out["class_s"]["sort"] == pytest.approx(40e-6)
    assert out["class_s"]["gather_scatter"] == pytest.approx(70e-6)
    assert out["class_s"]["other"] == pytest.approx(40e-6)  # loop 30 + fusion
    assert sum(out["class_s"].values()) == pytest.approx(150e-6)
    assert out["device_ops"][0] == ["jit_apply/scatter.4 scatter",
                                    pytest.approx(50e-6)]
    assert out["device_ops"][1][0] == "jit_round/sort.3 sort"
    assert out["idle_gaps"] == [["ExtractSubgraphs", pytest.approx(200e-6)]]


def test_a_gap_is_named_by_the_innermost_program_span_over_half_of_it():
    """A 2.5 ms host call with the device idle: the gap begins 5 us before
    the call (the read-back's tail) and ends 10 us after it (the upload),
    so the enclosing spans cover all of it and the call 99.4 %.  The call
    names it.  A gap of which no program span covers half keeps the old
    rule: the host event that covers most of it."""
    up = tr.SPAN_PREFIX + "partitioning.uncoarsening"
    ops = [_ev("fusion.1", 0, 100), _ev("fusion.2", 2600, 50),
           _ev("fusion.3", 2850, 50), _ev("fusion.4", 3300, 10)]
    modules = [_ev("jit_a(1)", 0, 100), _ev("jit_b(2)", 2600, 50),
               _ev("jit_c(3)", 2850, 50), _ev("jit_d(4)", 3300, 10)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev(tr.SPAN_PREFIX + "request", 0, 3000),
        _ev(up, 10, 2980),
        _ev(up + ".kway-fm", 90, 2520),
        _ev(up + ".kway-fm.graph-download", 91, 12),
        _ev(up + ".kway-fm.fm-native", 105, 2485),
        _ev("TransferFromDevice", 92, 10),
        _ev("PjitFunction(f)", 3050, 250)])])
    out = tr.reduce_profile(NS(planes=[host, device]))
    assert out["idle_gaps"] == [
        [up + ".kway-fm.fm-native", pytest.approx(2500e-6)],
        # 2900..3300: the program's spans cover a quarter, jax's call 62 %
        ["PjitFunction(f)", pytest.approx(400e-6)],
        # 2650..2850: inside the uncoarsening span and nothing narrower
        [up, pytest.approx(200e-6)]]


def test_no_device_plane_reduces_to_none():
    profile = _profile()
    profile.planes = [p for p in profile.planes
                      if not tr.DEVICE_PLANE.match(p.name)]
    assert tr.reduce_profile(profile) is None


GATHER = ("%fusion.216 = s32[16384]{0:T(1024)S(1)} fusion(s32[1048576]{0:T(1024)"
          "S(1)} %multiply_add_fusion.3, s32[]{:T(128)} %constant.197), "
          "kind=kCustom, calls=%fused_computation.186.clone.clone")
WHILE = ("%while.39 = (s32[]{:T(128)}, s32[65536]{0:T(1024)}) while((s32[]{:T(128)"
         "}, s32[65536]{0:T(1024)}) %tuple.4), condition=%cond, body=%body")


def test_instruction_text_as_the_v5e_trace_writes_it():
    assert tr.instruction(GATHER) == {
        "name": "fusion.216", "opcode": "fusion", "kind": "kCustom",
        "shape": "s32[16384]"}
    assert tr.instruction(WHILE)["opcode"] == "while"
    assert tr.instruction(WHILE)["shape"] == "s32[]"
    assert tr.op_class(GATHER) == "gather_scatter"
    assert tr.op_class(GATHER.replace("kCustom", "kLoop")) == "other"
    assert tr.op_class(WHILE) == "other"
    assert tr.op_class("%sort.0 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %a, "
                       "s32[8]{0} %b), dimensions={0}") == "sort"
    assert tr.op_class("%sort.12") == "sort"
    assert tr.op_class("scatter-add.3") == "gather_scatter"
    assert tr.op_class("fusion.7") == "other"
    assert tr.op_label("jit_f", GATHER) == (
        "jit_f/fusion.216 fusion:kCustom s32[16384]")


def test_recorded_trace_from_the_chip():
    """A v5e trace of three launches of one step (a ``while`` whose body
    holds a sort, a gather and a scatter-add), 50 ms of host sleep after
    each.  The expected numbers were read off ``small.dump.json`` by hand."""
    with open(os.path.join(DATA, "small.expected.json")) as f:
        expected = json.load(f)
    out = tr.reduce_file(os.path.join(DATA, "small.xplane.pb"))
    assert out["launches"] == expected["launches"]
    assert out["device_busy_s"] == pytest.approx(expected["device_busy_s"],
                                                 rel=1e-6)
    for key, value in expected["class_s"].items():
        assert out["class_s"][key] == pytest.approx(value, rel=1e-6)
    # own times add up to the busy time but for the slivers by which the
    # loop's events overlap their children's
    assert sum(out["class_s"].values()) == pytest.approx(
        out["device_busy_s"], rel=0.05)
    assert [op[0] for op in out["device_ops"][:3]] == [
        "jit_small_step/fusion.9 fusion:kCustom s32[262144]",
        "jit_small_step/fusion.8 fusion:kCustom s32[262144]",
        "jit_small_step/sort.10 sort s32[262144]"]
    # the two sleeps between the three launches are the longest gaps
    assert len(out["idle_gaps"]) >= 2
    for _, seconds in out["idle_gaps"][:2]:
        assert 0.045 < seconds < 0.2
