"""The readers of the rating-engine spans (``sort2_device_s``,
``lp_clustering_device_s``) and ``sort_s``, on a synthetic profile whose
answers are known by construction, and on a program that writes no
engine span (the parent of the PR that added them): nothing is read and
nothing raises (run by hand)."""

from types import SimpleNamespace as NS

import pytest

from perfbench.harness import phase_reduce as pr
from perfbench.tests.test_phase_reduce import US, _ev, _reader, _span

LP = "partitioning.coarsening.lp-clustering"
EXTEND_LP = ("partitioning.uncoarsening.extend-partition.coarsening"
             ".lp-clustering")


def _profile(engine_spans: bool):
    """One request of 1000 us.  Level 0 clusters with ``scatter`` (launch
    1, 100 us) and level 1 with ``sort2`` (launch 2, 60 us); launch 3 (5
    us) is enqueued in ``lp-clustering``'s own time, under no engine; a
    contraction (launch 4, 40 us); a ``sort2`` clustering under
    ``extend-partition`` (launch 5, 20 us); Jet (launch 6, 200 us).
    Sibling spans do not touch: the profiler's clock never gives two the
    same nanosecond."""
    spans = [
        _span("request", 0, 1000),
        _span("partitioning", 5, 995),
        _span("partitioning.coarsening", 10, 495),
        _span(LP, 20, 145),
        _span(LP + ".rating-scatter", 21, 140),
        _span("partitioning.coarsening.contraction", 150, 250),
        _span(LP, 260, 400),
        _span(LP + ".rating-sort2", 261, 390),
        _span("partitioning.uncoarsening", 500, 990),
        _span("partitioning.uncoarsening.extend-partition", 510, 695),
        _span("partitioning.uncoarsening.extend-partition.coarsening",
              520, 690),
        _span(EXTEND_LP, 530, 680),
        _span(EXTEND_LP + ".rating-sort2", 531, 670),
        _span("partitioning.uncoarsening.jet", 700, 980),
    ]
    if not engine_spans:
        spans = [s for s in spans if ".rating-" not in s.name]
    enqueues = [_ev(pr.ENQUEUE_EVENT, at, at + 1, run_id=run_id)
                for run_id, at in ((1, 25), (2, 270), (3, 395), (4, 160),
                                   (5, 540), (6, 710))]
    modules = [_ev("jit_cluster(1)", 30, 130, run_id=1),
               _ev("jit_contract(2)", 170, 210, run_id=4),
               _ev("jit_cluster(1)", 280, 340, run_id=2),
               _ev("jit_own(3)", 396, 401, run_id=3),
               _ev("jit_cluster(1)", 550, 570, run_id=5),
               _ev("jit_jet(4)", 720, 920, run_id=6)]
    ops = [_ev("fusion.1", 30, 130), _ev("fusion.2", 170, 210),
           _ev("sort.3", 280, 340), _ev("fusion.4", 396, 401),
           _ev("sort.3", 550, 570), _ev("fusion.5", 720, 920)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[
        NS(name="main/7", events=enqueues),
        NS(name="python3", events=spans)])
    return NS(planes=[host, device])


def _read(name, run):
    return _reader(name).read(run)


def _run(engine_spans: bool) -> dict:
    return {"samples": [], "phases": pr.reduce_profile(_profile(engine_spans)),
            "trace": {"device_busy_s": 425 * US,
                      "class_s": {"sort": 80 * US,
                                  "gather_scatter": 300 * US}}}


def test_engine_spans_stay_in_the_coarsening_layer():
    assert pr.layer_of(LP + ".rating-sort2") == ("coarsening", "coarsening")
    assert pr.layer_of(EXTEND_LP + ".rating-hash") == (
        "coarsening", "coarsening")
    with_spans = pr.reduce_profile(_profile(True))
    without = pr.reduce_profile(_profile(False))
    assert with_spans["layers"] == without["layers"]
    assert with_spans["attributed_share"] == pytest.approx(100.0)
    assert with_spans["spans"][LP + ".rating-sort2"]["device_s"] == (
        pytest.approx(60 * US))
    # a launch in the clustering's own time is no engine's
    assert with_spans["spans"][LP]["device_s"] == pytest.approx(5 * US)
    assert LP + ".rating-sort2" in pr.render(with_spans)


def test_the_three_readers():
    run = _run(True)
    assert _read("sort2_device_s", run) == pytest.approx(80 * US)
    assert _read("lp_clustering_device_s", run) == pytest.approx(185 * US)
    assert _read("sort_s", run) == pytest.approx(80 * US)
    assert _read("coarsening_device_s", run) == pytest.approx(225 * US)


def test_no_sort2_level_reads_zero_and_not_nothing():
    profile = _profile(True)
    for event in profile.planes[0].lines[1].events:
        event.name = event.name.replace("rating-sort2", "rating-dense")
    run = {"samples": [], "phases": pr.reduce_profile(profile)}
    assert _read("sort2_device_s", run) == 0.0


def test_a_program_without_engine_spans_gives_nothing_and_does_not_raise():
    run = _run(False)
    assert _read("sort2_device_s", run) is None
    assert _read("lp_clustering_device_s", run) == pytest.approx(185 * US)
    nothing = {"samples": [], "phases": None, "trace": None}
    for name in ("sort2_device_s", "lp_clustering_device_s", "sort_s"):
        assert _read(name, nothing) is None
