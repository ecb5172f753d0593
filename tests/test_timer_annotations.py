"""Timer scopes as profiler spans (utils/timer.py).

One module-scoped profiler session on the CPU, which has a host plane:
every scenario runs inside it once, the trace is read back with
``jax.profiler.ProfileData``, and the tests look at the events whose name
starts with ``SPAN_PREFIX``.  The same scenarios also run outside the
session, for the trees that must not depend on it.
"""

import glob
import os
from contextlib import contextmanager
from types import SimpleNamespace

import pytest

from kaminpar_tpu.utils import timer
from kaminpar_tpu.utils.timer import REQUEST_SPAN, SPAN_PREFIX, Timer

#: the leaf scopes added beside the phase scopes: five where the host
#: reads back from the device, one where the device waits for the host
NEW_SCOPES = {"graph-download", "extend-pull", "refine-probe",
              "balance-check", "partition-download", "isolated-nodes"}
#: directly under every `kway-fm` (the `strong` preset's host FM): the
#: level read back, the engine that ran, the labels going back up
FM_SCOPES = {"graph-download", "fm-native", "partition-upload"}
#: the nodes the benchmark's span metrics address (perfbench/harness/
#: timer_tree.py), by path or by name wherever they sit
PHASE_PATHS = ("partitioning.coarsening", "partitioning.initial-partitioning",
               "partitioning.uncoarsening")


def _tree(node, path=""):
    """{dotted path: count} of a TimerNode's descendants."""
    out = {}
    for name, child in node.children.items():
        child_path = f"{path}.{name}" if path else name
        out[child_path] = child.count
        out.update(_tree(child, child_path))
    return out


def _nested(t: Timer) -> None:
    with t.scope("t-nested"):
        with t.scope("first"):
            with t.scope("leaf"):
                pass
        for _ in range(2):
            with t.scope("second"):
                pass


def _unwound(t: Timer) -> None:
    outer = t.scope("t-unwind")
    outer.__enter__()
    depth = len(t._stack)
    inner = t.scope("rung")
    inner.__enter__()
    deepest = t.scope("attempt")
    deepest.__enter__()
    assert t.unwind_to(depth) == 2
    # the generators resume after the force-close: no double accounting
    deepest.__exit__(None, None, None)
    inner.__exit__(None, None, None)
    with t.scope("after"):
        pass
    outer.__exit__(None, None, None)


def _disabled(t: Timer) -> None:
    with t.scope("t-disabled-outer"):
        t.enabled = False
        with t.scope("t-disabled"):
            pass
        t.enabled = True


def _partition(preset="default", spec="gen:rmat;n=8192;m=60000;seed=3", k=4):
    import kaminpar_tpu as ktp
    from kaminpar_tpu.graphs.factories import generate
    from kaminpar_tpu.utils.logger import OutputLevel

    graph = generate(spec)
    solver = ktp.KaMinPar(preset)
    solver.set_output_level(OutputLevel.QUIET)
    part = solver.set_graph(graph).compute_partition(k=k, epsilon=0.03, seed=1)
    return graph, part, _tree(timer.GLOBAL_TIMER.root)


@contextmanager
def _profiler_session(trace_dir):
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _program_spans(trace_dir):
    """(line, start_ns, end_ns, name, stats) of every program span of the
    one trace under `trace_dir`, outermost first."""
    from jax.profiler import ProfileData

    (xplane_path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(xplane_path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((line.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns, ev.name,
                                  {str(k): str(v) for k, v in ev.stats}))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return spans


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    scenarios = {"nested": _nested, "unwound": _unwound,
                 "disabled": _disabled}
    outside = {}
    for name, scenario in scenarios.items():
        t = Timer()
        scenario(t)
        outside[name] = _tree(t.root)
    graph, part_outside, tree_outside = _partition()

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with _profiler_session(trace_dir):
        inside, timers = {}, {}
        for name, scenario in scenarios.items():
            timers[name] = t = Timer()
            scenario(t)
            inside[name] = _tree(t.root)
        _, part_inside, tree_inside = _partition()
    spans = _program_spans(trace_dir)
    return SimpleNamespace(
        spans=spans, outside=outside, inside=inside, timers=timers,
        graph=graph, part_outside=part_outside, part_inside=part_inside,
        tree_outside=tree_outside, tree_inside=tree_inside)


def _named(session, prefix):
    return [s for s in session.spans
            if s[3].startswith(SPAN_PREFIX + prefix)]


def test_nested_scopes_are_spans_named_by_their_dotted_path(session):
    spans = _named(session, "t-nested")
    assert [s[3][len(SPAN_PREFIX):] for s in spans] == [
        "t-nested", "t-nested.first", "t-nested.first.leaf",
        "t-nested.second", "t-nested.second"]
    assert len({s[0] for s in spans}) == 1  # one thread's line
    outer, first, leaf, second, again = spans
    assert outer[1] <= first[1] <= leaf[1] <= leaf[2] <= first[2]
    assert first[2] <= second[1] <= second[2] <= again[1]
    assert again[2] <= outer[2]


@pytest.mark.parametrize("scenario", ["nested", "unwound", "disabled"])
def test_the_timer_tree_does_not_depend_on_the_session(session, scenario):
    assert session.inside[scenario] == session.outside[scenario]
    assert session.inside[scenario]  # and is not empty


def test_unwind_to_closes_the_annotations_it_force_closes(session):
    t = session.timers["unwound"]
    assert t.idle() and t._open_spans == [] and t._open_starts == []
    assert session.inside["unwound"] == {
        "t-unwind": 1, "t-unwind.rung": 1, "t-unwind.rung.attempt": 1,
        "t-unwind.after": 1}
    spans = {s[3][len(SPAN_PREFIX):]: s for s in _named(session, "t-unwind")}
    # every scope is one closed event; the force-closed ones end before
    # the scope opened after the unwind starts
    assert set(spans) == set(session.inside["unwound"])
    assert spans["t-unwind.rung.attempt"][2] <= spans["t-unwind.rung"][2]
    assert spans["t-unwind.rung"][2] <= spans["t-unwind.after"][1]
    assert spans["t-unwind.after"][2] <= spans["t-unwind"][2]


def test_a_disabled_timer_emits_no_span(session):
    assert [s[3] for s in _named(session, "t-disabled")] == [
        SPAN_PREFIX + "t-disabled-outer"]
    assert session.inside["disabled"] == {"t-disabled-outer": 1}


def test_one_request_span_contains_every_span_of_the_partition(session):
    roots = [s for s in session.spans if s[3] == REQUEST_SPAN]
    assert len(roots) == 1
    line, start, end, _, stats = roots[0]
    assert stats == {"k": "4", "n": str(session.graph.n),
                     "m": str(session.graph.m)}
    inside = [s for s in session.spans
              if s[3] != REQUEST_SPAN and not s[3].startswith(
                  SPAN_PREFIX + "t-")]
    assert inside and all(s[0] == line and start <= s[1] and s[2] <= end
                          for s in inside)
    # the spans are the timer tree: same paths, same counts
    counts = {}
    for s in inside:
        path = s[3][len(SPAN_PREFIX):]
        counts[path] = counts.get(path, 0) + 1
    assert counts == session.tree_inside
    # properly nested on the one line
    open_ends = []
    for _, lo, hi, _, _ in inside:
        while open_ends and open_ends[-1] <= lo:
            open_ends.pop()
        assert not open_ends or hi <= open_ends[-1]
        open_ends.append(hi)


def test_the_partition_does_not_depend_on_the_session(session):
    assert (session.part_inside == session.part_outside).all()
    assert session.tree_inside == session.tree_outside


def test_new_scopes_are_leaves_beside_the_phase_scopes(session):
    tree = session.tree_inside
    by_name = {}
    for path in tree:
        by_name.setdefault(path.rsplit(".", 1)[-1], []).append(path)
    # every new scope ran, and below one there is at most another new one
    assert NEW_SCOPES <= set(by_name)
    for path in tree:
        names = path.split(".")
        for i, name in enumerate(names[:-1]):
            if name in NEW_SCOPES:
                assert set(names[i + 1:]) <= NEW_SCOPES, path
    # the nodes the benchmark's span metrics read keep their paths
    for path in PHASE_PATHS:
        assert path in tree
    assert "partitioning.initial-partitioning.graph-download" in tree
    for name in ("jet", "lp-refinement", "overload-balancer",
                 "extend-partition", "refine-probe"):
        for path in by_name[name]:
            assert path.rsplit(".", 1)[0] in (
                "partitioning", "partitioning.uncoarsening"), path
    # the extend pull is a sibling of extend-partition, never its parent
    for path in by_name["extend-pull"]:
        parent = path.rsplit(".", 1)[0]
        assert f"{parent}.extend-partition" in tree
    assert tree["partitioning.isolated-nodes"] == 1
    assert tree["partitioning.balance-check"] == 1
    assert tree["partitioning.partition-download"] == 1


def test_the_rating_engine_is_a_scope_under_lp_clustering(session):
    """Every LP clustering runs inside one `rating-<engine>` scope named
    by the engine the level resolved to, so a profiler trace of a run
    with telemetry off still says which engine ran; the benchmark's
    roll-up finds `coarsening` above it, so its layers do not move."""
    from perfbench.harness import phase_reduce

    tree = session.tree_inside
    clusterings = [p for p in tree if p.endswith(".lp-clustering")]
    assert clusterings
    for path in clusterings:
        below = {p[len(path) + 1:]: count for p, count in tree.items()
                 if p.startswith(path + ".")}
        assert all(name.startswith("rating-") and "." not in name
                   for name in below)
        assert sum(below.values()) == tree[path]
        for name in below:
            assert phase_reduce.layer_of(f"{path}.{name}") == (
                "coarsening", "coarsening")
    # this small skewed graph (avg degree 15, skew over 8) takes scatter;
    # a mesh takes sort2 (tests/test_mesh_deployment.py)
    scatter = "partitioning.coarsening.lp-clustering.rating-scatter"
    assert scatter in tree
    assert SPAN_PREFIX + scatter in {s[3] for s in session.spans}


#: a skewed graph at k = 4, and a mesh at the k of the benchmark's
#: `strong` cell (three k-doublings, one of them refined lightly)
STRONG_REQUESTS = {"rmat-k4": ("gen:rmat;n=8192;m=60000;seed=3", 4),
                   "delaunay-k16": ("gen:delaunay;n=4096;seed=1", 16)}


@pytest.fixture(scope="module", params=list(STRONG_REQUESTS))
def strong_session(request, tmp_path_factory):
    """One `strong` request (Jet and the host k-way FM alternated) in a
    profiler session of its own: its spans and its timer tree."""
    trace_dir = str(tmp_path_factory.mktemp("trace-strong"))
    with _profiler_session(trace_dir):
        _, _, tree = _partition("strong", *STRONG_REQUESTS[request.param])
    return SimpleNamespace(spans=_program_spans(trace_dir), tree=tree)


def test_the_fm_scopes_are_spans_inside_their_kway_fm(strong_session):
    """Every `kway-fm` call holds one span each of the read-back, the
    engine and the upload, in that order and inside it; the spans are
    the timer tree, path for path and count for count."""
    counts = {}
    for _, _, _, name, _ in strong_session.spans:
        if name != REQUEST_SPAN:
            path = name[len(SPAN_PREFIX):]
            counts[path] = counts.get(path, 0) + 1
    assert counts == strong_session.tree
    calls = [s for s in strong_session.spans if s[3].endswith(".kway-fm")]
    assert calls
    for line, lo, hi, name, _ in calls:
        inside = [s for s in strong_session.spans
                  if s[3].startswith(name + ".") and lo <= s[1] and s[2] <= hi]
        assert [s[3][len(name) + 1:] for s in inside] == [
            "graph-download", "fm-native", "partition-upload"]
        assert all(s[0] == line for s in inside)
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
    names = {path.rsplit(".", 1)[-1] for path in strong_session.tree}
    assert "lp-refinement" not in names and "fm-numpy" not in names


def test_the_benchmarks_layers_do_not_move_for_the_paths_that_were_there(
        session, strong_session):
    """Every older path keeps the layer `phase_reduce.layer_of` gave it,
    and a `strong` request's paths, the FM scopes among them, fall into
    one of the four layers (which one is the benchmark's to say)."""
    from perfbench.harness import phase_reduce

    up = "partitioning.uncoarsening"
    assert {
        path: phase_reduce.layer_of(path) for path in (
            "partitioning", "partitioning.coarsening.lp-clustering",
            "partitioning.coarsening.contraction",
            "partitioning.initial-partitioning.graph-download",
            up + ".jet", up + ".jet.jet-edges", up + ".lp-refinement",
            up + ".overload-balancer", up + ".underload-balancer",
            up + ".refine-probe", up + ".extend-pull.graph-download",
            up + ".extend-partition.jet.jet-edges",
            "partitioning.partition-download")
    } == {
        "partitioning": ("driver", ""),
        "partitioning.coarsening.lp-clustering": ("coarsening", "coarsening"),
        "partitioning.coarsening.contraction": ("coarsening", "coarsening"),
        "partitioning.initial-partitioning.graph-download": ("driver", ""),
        up + ".jet": ("refinement", "jet"),
        up + ".jet.jet-edges": ("refinement", "jet"),
        up + ".lp-refinement": ("refinement", "lp-refinement"),
        up + ".overload-balancer": ("refinement", "overload-balancer"),
        up + ".underload-balancer": ("refinement", "underload-balancer"),
        up + ".refine-probe": ("driver", ""),
        up + ".extend-pull.graph-download": ("extend", "extend-pull"),
        up + ".extend-partition.jet.jet-edges": ("refinement", "jet"),
        "partitioning.partition-download": ("driver", ""),
    }
    layers = {"coarsening", "refinement", "extend", "driver"}
    for tree in (session.tree_inside, strong_session.tree):
        assert {phase_reduce.layer_of(path)[0] for path in tree} <= layers
    fm_paths = [p for p in strong_session.tree if "kway-fm" in p.split(".")]
    assert {p.rsplit(".", 1)[-1] for p in fm_paths} == FM_SCOPES | {"kway-fm"}
