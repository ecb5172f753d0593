"""``verdict`` and the replay guarantee it holds a configuration to
(``harness/validate.py``), in plain numpy on synthetic samples, no jax
(run by hand: seconds).  The rule as it stood before configurations
stated ``guarantees.replay`` is copied here as the plain reference: a
configuration that states ``bitwise``, or nothing, gets exactly its
reasons and its ``failed`` count."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from perfbench.generators._csr import csr_from_edges  # noqa: E402
from perfbench.harness import validate  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CUT_BOUND = next(m["bound"] for m in json.load(_f)["end_to_end"]
                     if m["name"] == "cut")

BITWISE = {"replay": "bitwise"}
FEASIBLE = {"replay": "feasible", "cut_within": 0.04}
NOTHING = {"executables": 0}


def parent_verdict(samples, raised, window_compile):
    """``run.py``'s ``verdict`` at the parent of PR 36, verbatim."""
    reasons, failed = [], 0
    for i, sample in enumerate(samples):
        if sample["errors"]:
            failed += 1
            reasons.extend(f"partition {i}: {e}" for e in sample["errors"])
    if raised is not None:
        failed += 1
        reasons.append(f"a partition raised {raised}")
    for i, sample in enumerate(samples[1:], 1):
        if not np.array_equal(sample["partition"], samples[0]["partition"]):
            differ = int((sample["partition"]
                          != samples[0]["partition"]).sum())
            reasons.append(f"partition {i} differs from partition 0 in "
                           f"{differ} labels")
    if window_compile["executables"]:
        reasons.append(
            f"{window_compile['executables']} executables were compiled or "
            "loaded inside the window")
    if not samples:
        reasons.append("no partition ended")
    return failed, reasons


# a ring of 200 nodes cut into two arcs: moving the arcs' ends moves the
# labels and keeps the cut; cutting one arc in two raises the cut by 2
N = 200
RING = csr_from_edges(N, np.stack([np.arange(N), (np.arange(N) + 1) % N], 1))


def _arcs(*bounds):
    """Labels 0, 1, 0, 1, ... changing at each of ``bounds``."""
    part = np.zeros(N, dtype=np.int32)
    for at in bounds:
        part[at:] ^= 1
    return part


def _sample(part, k=2, epsilon=0.03, reported=None, anytime=None):
    """What ``window.check_sample`` makes of a returned partition."""
    checked = validate.check_partition(RING, part, k, epsilon)
    errors = list(checked["errors"])
    if anytime is not None:
        errors.insert(0, f"wound down early: {anytime}")
    if reported is None:
        reported = checked["cut"]
    if checked["cut"] is not None and reported != checked["cut"]:
        errors.append(f"the program reports cut {reported}, the benchmark "
                      f"counts {checked['cut']}")
    return {"partition": np.asarray(part), "cut": checked["cut"],
            "reported_cut": reported, "bound": checked["bound"],
            "max_block_weight": checked["max_block_weight"],
            "errors": errors}


HALVES = _arcs(100)                  # cut 2
SHIFTED = np.roll(HALVES, 1)         # cut 2, two labels differ from HALVES
THREE_ARCS = _arcs(50, 100, 150)     # cut 4
INFEASIBLE = _arcs(110)              # 110 > 1.03 * 100
OUT_OF_RANGE = np.where(np.arange(N) == 7, 2, HALVES).astype(np.int32)

CASES = {
    "all equal": ([HALVES] * 4, None, 0),
    "one label pair differs": ([HALVES, HALVES, SHIFTED, HALVES], None, 0),
    "every replay differs": ([HALVES, SHIFTED, THREE_ARCS], None, 0),
    "an infeasible partition": ([HALVES, INFEASIBLE, HALVES], None, 0),
    "labels out of range": ([HALVES, OUT_OF_RANGE], None, 0),
    "a partition raised": ([HALVES, HALVES], "ValueError: boom", 0),
    "compiled in the window": ([HALVES, HALVES], None, 3),
    "the warm-up alone": ([HALVES], None, 0),
    "nothing ended": ([], "RuntimeError: first", 0),
}


@pytest.mark.parametrize("stated", ["bitwise", "nothing", "no guarantees"])
@pytest.mark.parametrize("case", list(CASES))
def test_bitwise_or_nothing_stated_is_the_parents_rule(case, stated):
    parts, raised, executables = CASES[case]
    samples = [_sample(p) for p in parts]
    if case == "labels out of range":
        assert samples[1]["cut"] is None
    guarantee = validate.replay_guarantee(
        {"bitwise": {"replay": "bitwise", "determinism": "prose"},
         "nothing": {"determinism": "prose"}, "no guarantees": None}[stated],
        CUT_BOUND)
    assert guarantee == BITWISE
    window_compile = {"executables": executables}
    assert validate.verdict(samples, raised, window_compile, guarantee) == \
        parent_verdict(samples, raised, window_compile)


def test_bitwise_one_differing_label_pair_is_incorrect_with_the_old_message():
    samples = [_sample(HALVES), _sample(HALVES), _sample(SHIFTED)]
    failed, reasons = validate.verdict(samples, None, NOTHING, BITWISE)
    assert failed == 0
    assert reasons == ["partition 2 differs from partition 0 in 2 labels"]
    assert validate.compared(samples, failed, NOTHING, BITWISE)[
        "labels_differing"] == [2, 0]


def test_bitwise_a_partition_of_another_shape_is_a_reason_not_a_crash():
    samples = [_sample(HALVES), _sample(HALVES[:-1])]
    failed, reasons = validate.verdict(samples, None, NOTHING, BITWISE)
    assert failed == 1 and any("shape" in r for r in reasons)
    assert any("differs from partition 0" in r for r in reasons)


def test_feasible_differing_partitions_inside_the_band_are_correct():
    # cuts 100 x 2 and one of 104 (+4 %) would need a larger graph; the
    # band is on the cut, so the samples carry cuts of a real size
    samples = [dict(_sample(p), cut=c, reported_cut=c) for p, c in (
        (HALVES, 4000), (SHIFTED, 4100), (THREE_ARCS, 3900), (HALVES, 4000))]
    assert validate.distinct_partitions(samples) == 3
    failed, reasons = validate.verdict(samples, None, NOTHING, FEASIBLE)
    assert (failed, reasons) == (0, [])
    # the same samples under bitwise are not
    assert validate.verdict(samples, None, NOTHING, BITWISE)[1]
    compared = validate.compared(samples, failed, NOTHING, FEASIBLE)
    assert compared["cut_off_median"] == [pytest.approx(0.025), 0.04]
    assert "labels_differing" not in compared


def test_feasible_one_cut_outside_the_band_is_incorrect():
    samples = [dict(_sample(p), cut=c, reported_cut=c) for p, c in (
        (HALVES, 4000), (SHIFTED, 4000), (THREE_ARCS, 4200), (HALVES, 3990))]
    failed, reasons = validate.verdict(samples, None, NOTHING, FEASIBLE)
    assert failed == 0
    assert reasons == ["partition 2: cut 4200 is 5.00 % off the median "
                       "4000, the configuration allows 4 %"]
    assert validate.compared(samples, failed, NOTHING, FEASIBLE)[
        "cut_off_median"] == [pytest.approx(0.05), 0.04]


def test_feasible_the_real_cuts_of_the_ring_are_far_apart():
    """2 against 4: the validator's own recount feeds the band."""
    samples = [_sample(HALVES), _sample(SHIFTED), _sample(THREE_ARCS)]
    _, reasons = validate.verdict(samples, None, NOTHING, FEASIBLE)
    assert reasons == ["partition 2: cut 4 is 100.00 % off the median 2, "
                       "the configuration allows 4 %"]


@pytest.mark.parametrize("bad, why", [
    (INFEASIBLE, "infeasible"), (OUT_OF_RANGE, "outside [0, 2)")])
def test_feasible_an_invalid_partition_is_incorrect_and_counted(bad, why):
    samples = [_sample(HALVES), _sample(bad), _sample(SHIFTED)]
    failed, reasons = validate.verdict(samples, None, NOTHING, FEASIBLE)
    assert failed == 1
    assert len(reasons) == 1 and reasons[0].startswith("partition 1: ")
    assert why in reasons[0]
    assert validate.compared(samples, failed, NOTHING, FEASIBLE)[
        "partitions_failed"] == [1, 0]


def test_feasible_holds_the_other_checks_as_they_are():
    samples = [_sample(HALVES), _sample(SHIFTED)]
    assert validate.verdict(samples, None, {"executables": 2}, FEASIBLE) == (
        0, ["2 executables were compiled or loaded inside the window"])
    assert validate.verdict(samples, "X: y", NOTHING, FEASIBLE) == (
        1, ["a partition raised X: y"])
    assert validate.verdict([], None, NOTHING, FEASIBLE) == (
        0, ["no partition ended"])
    wrong = _sample(SHIFTED, reported=3)
    early = _sample(SHIFTED, anytime="deadline")
    failed, reasons = validate.verdict([samples[0], wrong, early], None,
                                       NOTHING, FEASIBLE)
    assert failed == 2
    assert any("reports cut 3" in r for r in reasons)
    assert any("wound down early" in r for r in reasons)
    assert validate.compared([samples[0], wrong], failed, NOTHING, FEASIBLE)[
        "cut_recount_gap"] == [1, 0]


@pytest.mark.parametrize("guarantees, said", [
    ({"replay": "feasible", "replay_cut_within": 0}, "positive"),
    ({"replay": "feasible", "replay_cut_within": -0.01}, "positive"),
    ({"replay": "feasible", "replay_cut_within": 0.05}, "at most half"),
    ({"replay": "feasible", "replay_cut_within": True}, "needs a number"),
    ({"replay": "feasible", "replay_cut_within": "0.02"}, "needs a number"),
    ({"replay": "feasible"}, "needs a number"),
    ({"replay": "approximate", "replay_cut_within": 0.02}, "not one of"),
    ({"replay": None}, "not one of"),
    ({"replay": "bitwise", "replay_cut_within": 0.02}, "belongs to"),
    ({"replay_cut_within": 0.02}, "belongs to"),
])
def test_a_guarantee_the_benchmark_does_not_know_is_refused(guarantees, said):
    with pytest.raises(ValueError, match=said):
        validate.replay_guarantee(guarantees, CUT_BOUND)


def test_the_band_is_at_most_half_the_manifests_bound_on_cut():
    half = CUT_BOUND / 2
    assert validate.replay_guarantee(
        {"replay": "feasible", "replay_cut_within": half}, CUT_BOUND) == {
            "replay": "feasible", "cut_within": half}
    with pytest.raises(ValueError, match="at most half"):
        validate.replay_guarantee(
            {"replay": "feasible", "replay_cut_within": half * 1.01},
            CUT_BOUND)
    with pytest.raises(ValueError, match="needs an end-to-end metric"):
        validate.replay_guarantee(
            {"replay": "feasible", "replay_cut_within": 0.01}, None)


@pytest.mark.parametrize("cuts, want", [
    ([3783, 3783, 3783, 3783], 3783), ([3783, 3783, 3783], 3783),
    ([3783], 3783), ([4000, 4100, 3900], 4000), ([4000, 4100], 4050),
    ([4001, 4100], 4050.5), ([None, 4000, None], 4000), ([], None),
    ([None], None)])
def test_the_cut_reported_is_the_median_and_an_int_where_it_is_one(cuts, want):
    got = validate.median_cut([{"cut": c} for c in cuts])
    assert got == want and type(got) is type(want)


def _checkout(tmp_path, guarantees):
    """The benchmark with one more configuration, as new files only; the
    program is linked (``run.py`` refuses a directory without it)."""
    shutil.copytree(os.path.join(REPO, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(REPO, "kaminpar_tpu"), tmp_path / "kaminpar_tpu")
    with open(os.path.join(REPO, "perfbench", "configs",
                           "delaunay-n17-strong.json")) as f:
        config = json.load(f)
    config["name"] = "delaunay-n17-strong-mt"
    config["guarantees"].update(guarantees)
    (tmp_path / "perfbench" / "configs" / "delaunay-n17-strong-mt.json"
     ).write_text(json.dumps(config))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": config["name"], "source": "test", "reduced": [],
        "why": "test", "file": "perfbench/configs/delaunay-n17-strong-mt.json"})
    manifest["workloads"].append({
        "name": "delaunay-n17-strong-mt.k16", "config": config["name"],
        "traffic": "k16", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp_path


@pytest.mark.parametrize("guarantees, said", [
    ({"replay": "feasible", "replay_cut_within": 0.05}, "at most half"),
    ({"replay": "feasible", "replay_cut_within": 0}, "positive"),
    ({"replay": "near enough"}, "not one of bitwise, feasible"),
])
def test_the_command_exits_before_any_graph_is_built(tmp_path, guarantees,
                                                     said):
    """Before the look for a chip too: nothing of jax is imported."""
    root = _checkout(tmp_path, guarantees)
    proc = subprocess.run(
        [sys.executable, str(root / "perfbench" / "run.py"), "--workload",
         "delaunay-n17-strong-mt.k16", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        cwd=str(root), env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "configuration 'delaunay-n17-strong-mt'" in proc.stderr
    assert said in proc.stderr
    assert "graph seed" not in proc.stdout and "platform=" not in proc.stdout
