"""The reader of the host FM refiner's scope (``fm_s``, from the timer
trees), on recorded trees whose answers are known by construction: a
``strong`` request of a program that writes the engine and upload scopes
under ``kway-fm``, the same request of a program that writes ``kway-fm``
as one opaque scope (the parent of the PR that added them), and a
``default`` request, which reads 0.  What the layer roll-up makes of
``kway-fm`` is rehearsed on a synthetic profile: a scope of the
refinement layer since PR 36 (run by hand)."""

from types import SimpleNamespace as NS

import pytest

from perfbench.harness import phase_reduce as pr
from perfbench.tests.test_phase_reduce import US, _ev, _reader, _span

UP = "partitioning.uncoarsening"
FM = UP + ".kway-fm"
INNER = ("graph-download", "fm-native", "partition-upload")


def _node(elapsed_s=0.0, count=1, **children):
    return {"elapsed_s": elapsed_s, "count": count,
            "children": {name.replace("_", "-"): child
                         for name, child in children.items()}}


def _tree(fm_s, fm_count, inner=True):
    """A request's timer tree: ``fm_count`` FM calls of ``fm_s`` seconds
    in all beside a Jet node; none at all where ``fm_count`` is 0."""
    below = {}
    if fm_count:
        kids = ({"graph_download": _node(0.1 * fm_s, fm_count),
                 "fm_native": _node(0.8 * fm_s, fm_count),
                 "partition_upload": _node(0.05 * fm_s, fm_count)}
                if inner else {"graph_download": _node(0.1 * fm_s, fm_count)})
        below["kway_fm"] = _node(fm_s, fm_count, **kids)
    return _node(children={}) | {"children": {"partitioning": _node(
        10.0, 1, uncoarsening=_node(8.0, 1, jet=_node(2.0, 14), **below))}}


def _profile(fm: bool, inner: bool):
    """One request of 1000 us: a Jet call (launch 1, 100 us on the
    device), then, where ``fm``, two FM calls of 200 and 300 us in which
    the device runs nothing but the upload's transfer program (launch 2,
    4 us, enqueued under ``partition-upload``, or under ``kway-fm``
    itself where the program writes no scope there), then a Jet call
    (launch 3, 50 us)."""
    spans = [
        _span("request", 0, 1000),
        _span("partitioning", 5, 995),
        _span(UP, 10, 990),
        _span(UP + ".jet", 20, 140),
    ]
    if fm:
        spans += [
            _span(FM, 150, 350),
            _span(FM + ".graph-download", 151, 180),
            _span(FM + ".fm-native", 181, 340),
            _span(FM + ".partition-upload", 341, 349),
            _span(FM, 400, 700),
            _span(FM + ".graph-download", 401, 440),
            _span(FM + ".fm-native", 441, 690),
            _span(FM + ".partition-upload", 691, 699),
        ]
    if not inner:
        spans = [s for s in spans if s.name.rsplit(".", 1)[-1]
                 not in INNER[1:]]
    spans.append(_span(UP + ".jet", 710, 800))
    launches = [(1, 25, "jit__jet_chunk(1)", 30, 130),
                (3, 715, "jit__jet_chunk(1)", 720, 770)]
    if fm:
        launches.insert(1, (2, 342, "jit_convert(2)", 344, 348))
    enqueues = [_ev(pr.ENQUEUE_EVENT, at, at + 1, run_id=run_id)
                for run_id, at, _, _, _ in launches]
    modules = [_ev(name, lo, hi, run_id=run_id)
               for run_id, _, name, lo, hi in launches]
    ops = [_ev(f"fusion.{run_id}", lo, hi)
           for run_id, _, _, lo, hi in launches]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[
        NS(name="main/7", events=enqueues),
        NS(name="python3", events=spans)])
    return NS(planes=[host, device])


def _run(fm: bool, inner: bool = True) -> dict:
    trees = [_tree(s, 6 if fm else 0, inner) for s in (8.0, 8.4, 8.2)]
    return {"samples": [], "trees": trees,
            "phases": pr.reduce_profile(_profile(fm, inner))}


def _read(name, run):
    return _reader(name).read(run)


@pytest.mark.parametrize("inner", [True, False])
def test_a_strong_request_reads_its_fm(inner):
    run = _run(True, inner)
    assert _read("fm_s", run) == pytest.approx(8.2)
    if inner:
        spans = run["phases"]["spans"]
        assert spans[FM + ".fm-native"]["idle_s"] == pytest.approx(408 * US)
        assert spans[FM + ".partition-upload"]["launches"] == 1
        assert FM + ".fm-native" in pr.render(run["phases"])


def test_kway_fm_is_a_scope_of_the_refinement_layer():
    """FM's idle seconds (200 + 300 us of spans less the 4 us the device
    ran in them) are refinement's, its host seconds are in
    ``refinement_s``, its transfer program's device seconds in
    ``refinement_device_s``, and the driver keeps what no layer names;
    with or without the scopes below ``kway-fm`` they read the same."""
    for name in INNER + ("fm-numpy",):
        assert pr.layer_of(f"{FM}.{name}") == ("refinement", "kway-fm")
    assert pr.layer_of(FM) == ("refinement", "kway-fm")
    with_scopes, opaque, default = _run(True), _run(True, False), _run(False)
    for name in ("driver_idle_s", "refinement_idle_s", "refinement_device_s",
                 "jet_device_s", "phase_attributed_share", "refinement_s",
                 "jet_s", "extend_s"):
        assert _read(name, with_scopes) == pytest.approx(_read(name, opaque))
    # the driver's idle is that of the spans no layer names, FM or no FM
    rest = sum(row["idle_s"]
               for path, row in with_scopes["phases"]["spans"].items()
               if "kway-fm" not in path and not path.endswith(".jet"))
    assert _read("driver_idle_s", with_scopes) == pytest.approx(rest)
    jet_idle = sum(row["idle_s"]
                   for path, row in with_scopes["phases"]["spans"].items()
                   if path.endswith(".jet"))
    assert _read("refinement_idle_s", with_scopes) == pytest.approx(
        496 * US + jet_idle)
    # the two FM calls end where the driver's glue began: that glue is
    # the only idle that differs from the request without FM
    assert _read("refinement_idle_s", default) == pytest.approx(jet_idle)
    assert _read("refinement_s", with_scopes) == pytest.approx(2.0 + 8.2)
    assert _read("refinement_s", default) == pytest.approx(2.0)
    assert _read("jet_s", with_scopes) == pytest.approx(2.0)
    assert _read("refinement_device_s", with_scopes) == pytest.approx(
        _read("jet_device_s", with_scopes) + 4 * US)
    assert _read("extend_s", with_scopes) == 0.0
    assert _read("phase_attributed_share", with_scopes) == pytest.approx(100.0)


def test_a_default_request_reads_zero_and_not_nothing():
    run = _run(False)
    assert _read("fm_s", run) == 0.0


def test_without_a_trace_or_a_partition_nothing_is_read_and_nothing_raises():
    nothing = {"samples": [], "trees": [], "phases": None, "trace": None}
    assert _read("fm_s", nothing) is None
    # an untraced run still has its trees
    untraced = dict(_run(True), phases=None)
    assert _read("fm_s", untraced) == pytest.approx(8.2)
    # a tree that holds no partition (the request raised before one)
    empty = {"samples": [], "trees": [_node()], "phases": None}
    assert _read("fm_s", empty) is None
