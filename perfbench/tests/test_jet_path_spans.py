"""The spans that name the iteration a Jet call resolved to (``jet-rows``,
``jet-edges``, ``jet-lp``, one directly under every ``jet``) and the
metric that reads them, ``jet_rows_device_s``: on a synthetic profile
whose answers are known by construction, and on a program that writes
no path span (the parent of the PR that added them): nothing is read
and nothing raises (run by hand)."""

from types import SimpleNamespace as NS

import pytest

from perfbench.harness import phase_reduce as pr
from perfbench.tests.test_phase_reduce import US, _ev, _reader, _span

UP = "partitioning.uncoarsening"
JET = UP + ".jet"
EXTEND_JET = UP + ".extend-partition.jet"


def _profile(path_spans: bool, fine: str = "jet-rows"):
    """One request of 1000 us with three Jet calls as siblings under
    ``uncoarsening``, coarsest first.  Level 2 and level 1 take the
    edge-wide path (launches 1 and 2: 50 and 90 us), level 0 the path
    ``fine`` names (launch 3, 300 us); launch 4 (7 us) is enqueued in a
    ``jet``'s own time, under no path; the device bipartition's Jet
    under ``extend-partition`` is edge-wide (launch 5, 20 us); an LP
    refinement (launch 6, 40 us).  Sibling spans do not touch."""
    spans = [
        _span("request", 0, 1000),
        _span("partitioning", 5, 995),
        _span(UP, 10, 990),
        _span(JET, 20, 100),
        _span(JET + ".jet-edges", 21, 95),
        _span(JET, 110, 250),
        _span(JET + ".jet-edges", 111, 240),
        _span(UP + ".extend-partition", 260, 340),
        _span(EXTEND_JET, 270, 330),
        _span(EXTEND_JET + ".jet-edges", 271, 325),
        _span(UP + ".lp-refinement", 350, 420),
        _span(JET, 430, 900),
        _span(JET + "." + fine, 431, 880),
    ]
    if not path_spans:
        spans = [s for s in spans if ".jet.jet-" not in s.name]
    enqueues = [_ev(pr.ENQUEUE_EVENT, at, at + 1, run_id=run_id)
                for run_id, at in ((1, 25), (2, 115), (3, 440), (4, 890),
                                   (5, 275), (6, 355))]
    modules = [_ev("jit__jet_chunk(1)", 30, 80, run_id=1),
               _ev("jit__jet_chunk(1)", 120, 210, run_id=2),
               _ev("jit__jet_chunk(2)", 280, 300, run_id=5),
               _ev("jit__lp_refine_fused(3)", 360, 400, run_id=6),
               _ev("jit__jet_chunk(4)", 450, 750, run_id=3),
               _ev("jit__jet_round_close(5)", 891, 898, run_id=4)]
    ops = [_ev("fusion.1", 30, 80), _ev("fusion.1", 120, 210),
           _ev("fusion.2", 280, 300), _ev("fusion.3", 360, 400),
           _ev("fusion.4", 450, 750), _ev("fusion.5", 891, 898)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[
        NS(name="main/7", events=enqueues),
        NS(name="python3", events=spans)])
    return NS(planes=[host, device])


def _run(path_spans: bool, fine: str = "jet-rows") -> dict:
    return {"samples": [],
            "phases": pr.reduce_profile(_profile(path_spans, fine))}


def _read(name, run):
    return _reader(name).read(run)


def test_path_spans_stay_in_the_refinement_layer_and_in_jet():
    for name in ("jet-rows", "jet-edges", "jet-lp"):
        assert pr.layer_of(f"{JET}.{name}") == ("refinement", "jet")
        assert pr.layer_of(f"{EXTEND_JET}.{name}") == ("refinement", "jet")
    with_spans = pr.reduce_profile(_profile(True))
    without = pr.reduce_profile(_profile(False))
    assert with_spans["layers"] == without["layers"]
    assert with_spans["jet"] == without["jet"]
    assert with_spans["attributed_share"] == pytest.approx(100.0)
    assert with_spans["spans"][JET + ".jet-rows"]["device_s"] == (
        pytest.approx(300 * US))
    assert with_spans["spans"][JET + ".jet-edges"]["device_s"] == (
        pytest.approx(140 * US))
    # a launch in the jet scope's own time is no path's
    assert with_spans["spans"][JET]["device_s"] == pytest.approx(7 * US)
    assert JET + ".jet-rows" in pr.render(with_spans)


def test_the_accepted_readers_read_what_they_read_without_the_spans():
    with_spans, without = _run(True), _run(False)
    for name in ("jet_device_s", "refinement_device_s", "extend_device_s",
                 "phase_attributed_share", "refinement_idle_s"):
        assert _read(name, with_spans) == pytest.approx(_read(name, without))
    assert _read("jet_device_s", with_spans) == pytest.approx(467 * US)
    assert _read("refinement_device_s", with_spans) == pytest.approx(507 * US)


def test_jet_rows_device_s_is_the_sum_of_its_spans():
    assert _read("jet_rows_device_s", _run(True)) == pytest.approx(300 * US)


@pytest.mark.parametrize("fine", ["jet-edges", "jet-lp"])
def test_no_rows_call_reads_zero_and_not_nothing(fine):
    assert _read("jet_rows_device_s", _run(True, fine)) == 0.0


def test_a_program_without_path_spans_gives_nothing_and_does_not_raise():
    assert _read("jet_rows_device_s", _run(False)) is None
    nothing = {"samples": [], "phases": None, "trace": None}
    assert _read("jet_rows_device_s", nothing) is None
