"""The four set-up metrics and ``tools/setup_table.py`` on the CPU at a
tiny size (run by hand: ``JAX_PLATFORMS=cpu pytest perfbench/tests``).
A run here says whether the account, the readers and the tool agree with
each other and with the benchmark's own listener; nothing it prints is a
device number."""

import json
import os
import subprocess
import sys

import pytest

from .test_rehearsal import _run, checkout  # noqa: F401  (its tiny cell)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "grid-64.k4"
SETUP_METRICS = {"trace_lower_s", "first_request_s", "setup_unattributed_s",
                 "package_import_s"}

# the tool as ``cpu_override.py`` runs ``run.py``: the harness's device
# module patched in this process, nothing added to the tool
TOOL_ON_CPU = """
import runpy, sys
sys.path.insert(0, sys.argv[1])
from perfbench.harness import device
device.REQUIRED_PLATFORM = "cpu"
device.PEAKS["cpu"] = {"source": "test-only row, not a peak"}
tool = sys.argv[1] + "/perfbench/tools/setup_table.py"
sys.argv = [tool] + sys.argv[2:]
runpy.run_path(tool, run_name="__main__")
"""


def _tool(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", TOOL_ON_CPU, str(root), "--workload", CELL,
         *args], capture_output=True, text=True, timeout=900, env=env,
        cwd=str(root))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_traced_run_reports_the_four_setup_metrics(checkout):
    result, out = _run(checkout, "--seed", "1", "--seconds", "3",
                       "--trace", "1")
    metrics = {name: entry["value"]
               for name, entry in result["metrics"].items()}
    assert SETUP_METRICS <= set(metrics), out
    assert all(result["metrics"][m]["unit"] == "s" for m in SETUP_METRICS)
    # the harness imported jax before the package: the package's own
    assert 0 < metrics["package_import_s"] < metrics["first_request_s"]
    assert 0 < metrics["trace_lower_s"] < metrics["first_request_s"]
    # the account's backend seconds are the benchmark's compile_s: both
    # listen to the same events, and the warm-up is request 1
    assert metrics["setup_unattributed_s"] < (
        metrics["first_request_s"] - metrics["trace_lower_s"]
        - 0.99 * metrics["compile_s"])
    assert "executables were compiled or loaded inside" not in out


def test_the_tool_agrees_with_the_benchmarks_listener(checkout, tmp_path):
    numbers, out = _tool(checkout, "--seed", "1", "--replays", "2", "--out",
                         str(tmp_path))
    account, listener = numbers["account"], numbers["listener"]
    assert account["closed"] == listener["executables"] > 0
    assert account["backend_s"] == pytest.approx(listener["seconds"],
                                                 rel=0.01)
    assert account["unplaced_events"] == 0 and account["nested"] == 0
    assert numbers["listener_replays"]["executables"] == 0
    assert set(numbers["by_request"]) == {"1"}
    metrics = numbers["metrics"]
    first = metrics["first_request_s"]
    assert first <= numbers["first_serve_s"]  # the facade's call lies inside
    later = sorted(numbers["replay_s"])
    assert metrics["setup_unattributed_s"] == pytest.approx(
        first - metrics["trace_lower_s"] - account["backend_s"]
        - sum(later) / 2, abs=0.05)
    assert "costliest by backend seconds" in out and "by layer:" in out
    with open(tmp_path / f"{CELL}.seed1.json") as f:
        kept = json.load(f)
    assert len(kept["records"]) == account["records"]


def test_a_program_without_the_accessor_reads_none(monkeypatch):
    sys.path.insert(0, REPO)
    from kaminpar_tpu.telemetry import compile_account
    from perfbench.layer_metrics import _setup_account

    monkeypatch.delattr(compile_account, "summary")
    assert _setup_account.summary() is None
    assert _setup_account.read(_setup_account.first_request_s) is None
