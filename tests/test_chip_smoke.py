"""chip_smoke.py off the chip: it must refuse a CPU, its checks must
catch a broken run, and the compile cache must be placeable.

The smoke itself only passes on a TPU (sent through the chip tool); here
its checking functions are called directly on a small CPU partition.
"""

import copy
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import kaminpar_tpu as ktp
from kaminpar_tpu import telemetry
from kaminpar_tpu.graphs import factories
from kaminpar_tpu.utils.logger import OutputLevel

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_cpu_before_building_a_graph():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, _SMOKE], env=env, capture_output=True, text=True,
        timeout=120, cwd=_REPO,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    # it printed what jax sees, then stopped: no run, no result line
    assert "platform=cpu" in proc.stdout
    assert "RESULT" not in proc.stdout and '"ok"' not in proc.stdout


def test_verdict_line_holds_exactly_what_the_driver_reads(smoke):
    # the driver refused a last line that carried the measurements too
    line = smoke.verdict_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


@pytest.fixture(scope="module")
def small_run():
    """One CPU partition with its run report (telemetry on)."""
    from kaminpar_tpu.telemetry.report import build_run_report

    graph = factories.generate("gen:rmat;n=16384;m=120000;seed=3")
    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        solver = ktp.KaMinPar("default")
        solver.set_output_level(OutputLevel.QUIET)
        part = solver.set_graph(graph).compute_partition(k=4, epsilon=0.03)
        report = json.loads(json.dumps(build_run_report()))
    finally:
        if not was_enabled:
            telemetry.disable()
        telemetry.reset()
    return graph, part, report


def test_checks_pass_on_a_good_run(smoke, small_run):
    graph, part, report = small_run
    smoke.check_report(report)
    out = smoke.check_partition(
        graph, part, 4, 0.03, ref_cut=10**9,
        reported_cut=report["result"]["cut"],
    )
    assert out["cut"] == report["result"]["cut"] and out["feasible"]


def test_partition_check_catches_a_flipped_label(smoke, small_run):
    graph, part, report = small_run
    # a node whose move changes the cut: its neighbours in its own
    # block and in the next block differ in number
    bad = None
    for u in range(graph.n):
        nbrs = part[graph.adjncy[graph.xadj[u]:graph.xadj[u + 1]]]
        other = (part[u] + 1) % 4
        if (nbrs == part[u]).sum() != (nbrs == other).sum():
            bad = np.array(part, copy=True)
            bad[u] = other
            break
    assert bad is not None
    with pytest.raises(smoke.SmokeFailure, match="reported cut"):
        smoke.check_partition(graph, bad, 4, 0.03, 10**9,
                              report["result"]["cut"])
    with pytest.raises(smoke.SmokeFailure, match="labels outside"):
        out_of_range = np.array(part, copy=True)
        out_of_range[0] = 4
        smoke.check_partition(graph, out_of_range, 4, 0.03, 10**9,
                              report["result"]["cut"])
    with pytest.raises(smoke.SmokeFailure, match="reference binary"):
        smoke.check_partition(graph, part, 4, 0.03, ref_cut=1,
                              reported_cut=report["result"]["cut"])


def test_report_check_catches_degradation(smoke, small_run):
    _, _, report = small_run
    degraded = copy.deepcopy(report)
    degraded["degraded"] = [
        {"name": "degraded", "t": 0.1, "attrs": {"site": "native-build"}}
    ]
    with pytest.raises(smoke.SmokeFailure, match="degraded events"):
        smoke.check_report(degraded)
    rung1 = copy.deepcopy(report)
    rung1["memory_budget"] = {"enabled": True, "rung": 1,
                              "rung_name": "tight-pads"}
    with pytest.raises(smoke.SmokeFailure, match="memory ladder"):
        smoke.check_report(rung1)
    dirty = copy.deepcopy(report)
    dirty["integrity"]["verdict"] = "recovered"
    with pytest.raises(smoke.SmokeFailure, match="integrity"):
        smoke.check_report(dirty)
    ungated = copy.deepcopy(report)
    ungated["output_gate"] = {"checked": False}
    with pytest.raises(smoke.SmokeFailure, match="output gate"):
        smoke.check_report(ungated)


def test_compile_cache_is_placeable(monkeypatch, tmp_path):
    import jax

    from kaminpar_tpu.utils.platform import configure_compile_cache

    assigned = {}
    monkeypatch.setattr(jax.config, "update", assigned.__setitem__)

    # placed from outside: no directory is assigned in code
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert configure_compile_cache() == placed
    assert not [key for key in assigned if key.endswith("cache_dir")]
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == placed

    # not placed: <checkout>/.jax_cache, whatever the working directory
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(_REPO, ".jax_cache")
    for cwd in (tmp_path, _REPO):
        assigned.clear()
        monkeypatch.chdir(cwd)
        assert configure_compile_cache() == want
        assert assigned["jax_compilation_cache_dir"] == want
