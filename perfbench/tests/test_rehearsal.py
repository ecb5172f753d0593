"""The whole command on the CPU at a tiny size, in a temporary checkout
that ADDS two configurations (one states ``"replay": "feasible"``, one
states nothing and so replays bitwise), a traffic mix, a generator
family and a per-layer metric as new files and new manifest entries only
(run by hand: ``JAX_PLATFORMS=cpu pytest perfbench/tests``; ~3 min).

The CPU is accepted only through ``cpu_override.py``, which lives here
and not in ``run.py``.  Nothing a run prints here is a device number.
``fault_override.py`` breaks the timed path underneath the same command,
and ``correct`` has to come out false."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

GRID_GENERATOR = '''
"""A rows x cols grid: a generator family the benchmark did not have."""
import numpy as np
from ._csr import csr_from_edges


def generate(params, seed):
    rows, cols = int(params["rows"]), int(params["cols"])
    ids = np.arange(rows * cols).reshape(rows, cols)
    right = np.stack([ids[:, :-1].ravel(), ids[:, 1:].ravel()], 1)
    down = np.stack([ids[:-1, :].ravel(), ids[1:, :].ravel()], 1)
    # the seed drops a few edges, so that two seeds give two graphs
    edges = np.concatenate([right, down])
    keep = np.random.default_rng(seed).random(len(edges)) > 0.01
    return csr_from_edges(rows * cols, edges[keep])
'''

UPLOAD_METRIC = '''
"""Timer node partitioning.device-upload: a per-layer metric the
benchmark did not have."""
from perfbench.harness import timer_tree

LAYER, UNIT, MOVES, SOURCE, CELLS = (
    "driver", "s", "partition_s", "program_span", ["grid-64.k4"])


def read(run):
    def one(tree):
        node = timer_tree.at(tree, "partitioning.device-upload")
        return None if node is None else node["elapsed_s"]
    return timer_tree.median_over(run["trees"], one)
'''


def _digests(folder):
    out = {}
    for base, _, names in os.walk(folder):
        for name in names:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, folder)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with the program (linked), the benchmark (copied) and
    one new cell made of new files only."""
    root = tmp_path_factory.mktemp("checkout")
    ignore = shutil.ignore_patterns("__pycache__", "tests")
    shutil.copytree(os.path.join(REPO, "perfbench"), root / "perfbench",
                    ignore=ignore)
    os.symlink(os.path.join(REPO, "kaminpar_tpu"), root / "kaminpar_tpu")
    before = _digests(root / "perfbench")

    bench = root / "perfbench"
    (bench / "generators" / "grid.py").write_text(GRID_GENERATOR)
    (bench / "layer_metrics" / "upload_s.py").write_text(UPLOAD_METRIC)
    grid = {"name": "grid-64", "source": "test", "generator": "grid",
            "params": {"rows": 64, "cols": 64}, "graph_seed_base": 10,
            "preset": "default"}
    (bench / "configs" / "grid-64.json").write_text(json.dumps(dict(
        grid, guarantees={"replay": "feasible", "replay_cut_within": 0.04})))
    # the same deployment stating nothing: replays have to be bitwise equal
    (bench / "configs" / "grid-64-bitwise.json").write_text(json.dumps(dict(
        grid, name="grid-64-bitwise")))
    (bench / "traffic" / "k4.json").write_text(json.dumps({
        "name": "k4", "k": 4, "epsilon": 0.03, "callers": 1,
        "pattern": "replay"}))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for name in ("grid-64", "grid-64-bitwise"):
        manifest["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"perfbench/configs/{name}.json"})
        manifest["workloads"].append({
            "name": name + ".k4", "config": name, "traffic": "k4",
            "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "upload_s", "unit": "s", "better": "lower",
        "source": "program_span", "layer": "driver", "moves": "partition_s",
        "workloads": ["grid-64.k4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    after = _digests(root / "perfbench")
    assert {k: after[k] for k in before} == before  # nothing edited
    return root


def _run(root, *args, cell="grid-64.k4", fault=None, stderr=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    script = ([os.path.join(HERE, "cpu_override.py")] if fault is None else
              [os.path.join(HERE, "fault_override.py"), fault, "--cpu"])
    proc = subprocess.run(
        [sys.executable, *script, str(root), "--workload", cell, *args],
        capture_output=True, text=True, timeout=900, env=env, cwd=str(root))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    return result, (proc.stderr if stderr else proc.stdout)


def test_untraced_run_reports_the_end_to_end_metrics(checkout):
    result, out = _run(checkout, "--seed", "1", "--seconds", "4",
                       "--trace", "0")
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True, out
    # the configuration states `feasible`; the program replays bitwise
    # all the same, and the run says so
    assert "replay feasible: 1 distinct partitions" in out
    assert result["compared"]["cut_off_median"] == [0.0, 0.04]
    assert result["compared"]["window_executables"] == [0, 0]
    heaviest, bound = result["compared"]["max_block_weight"]
    assert 0 < heaviest <= bound
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"partition_s", "cut", "setup_s"}
    assert result["metrics"]["cut"]["value"] > 0
    assert result["metrics"]["partition_s"]["unit"] == "s"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}


def test_second_run_compiles_nothing_and_repeats_the_cut(checkout):
    """Needs the run above to have filled the checkout's .jax_cache."""
    first, _ = _run(checkout, "--seed", "1", "--seconds", "3", "--trace", "0")
    again, out = _run(checkout, "--seed", "1", "--seconds", "3",
                      "--trace", "0")
    assert again["metrics"]["cut"] == first["metrics"]["cut"]
    assert ": 0 compiled," in out, out


def test_traced_run_reports_the_new_and_the_old_layer_metrics(checkout):
    result, out = _run(checkout, "--seed", "1", "--seconds", "4",
                       "--trace", "1")
    names = set(result["metrics"])
    # the timer-tree and listener metrics exist on any platform; the
    # trace-derived ones need a device plane, which a CPU trace lacks,
    # and are left out (and the run says it is not a correct traced run)
    assert {"upload_s", "coarsening_s", "initial_s", "refinement_s", "jet_s",
            "extend_s", "executables", "compile_s", "peak_hbm_mb"} <= names
    assert not names & {"launches", "device_busy_s", "idle_share"}
    assert result["correct"] is False
    assert "the trace holds no device operation" in out
    assert result["metrics"]["extend_s"]["value"] > 0  # k=4: one doubling


def test_what_was_compared_ends_standard_error(checkout):
    result, err = _run(checkout, "--seed", "1", "--seconds", "2",
                       "--trace", "0", cell="grid-64-bitwise.k4", stderr=True)
    assert result["correct"] is True
    assert result["compared"]["labels_differing"] == [0, 0]
    last = err.strip().splitlines()[-len(result["compared"]):]
    assert [line.split()[2] for line in last] == list(result["compared"])
    assert all(line.startswith("perfbench: compared: ") for line in last)


def test_an_answer_altered_where_it_is_produced_is_not_correct(checkout):
    """One label of every second answer: a `bitwise` configuration is
    broken, and the partitions as such are still sound."""
    result, out = _run(checkout, "--seed", "1", "--seconds", "3",
                       "--trace", "0", cell="grid-64-bitwise.k4",
                       fault="label")
    assert result["correct"] is False and result["failed"] == 0
    assert "differs from partition 0 in 1 labels" in out
    assert result["compared"]["labels_differing"] == [1, 0]
    assert "replay bitwise: 2 distinct partitions" in out


def test_the_same_fault_is_inside_what_a_feasible_configuration_states(
        checkout):
    """Not a defect of the rule: the cut moves by a few edges of ~1,000.
    The line says that the replays were not bitwise equal."""
    result, out = _run(checkout, "--seed", "1", "--seconds", "3",
                       "--trace", "0", fault="label")
    assert result["correct"] is True, out
    assert "replay feasible: 2 distinct partitions" in out
    off, within = result["compared"]["cut_off_median"]
    assert 0 < off < within


def test_an_infeasible_answer_is_not_correct_under_feasible_either(checkout):
    result, out = _run(checkout, "--seed", "1", "--seconds", "3",
                       "--trace", "0", fault="infeasible")
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 2
    assert "infeasible: block weight" in out
    heaviest, bound = result["compared"]["max_block_weight"]
    assert heaviest > bound


def test_another_seed_is_another_graph(checkout):
    one, _ = _run(checkout, "--seed", "1", "--seconds", "2", "--trace", "0")
    two, _ = _run(checkout, "--seed", "2", "--seconds", "2", "--trace", "0")
    assert one["metrics"]["cut"] != two["metrics"]["cut"]


def test_the_benchmark_itself_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", "rmat-s16.k16", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=env, cwd=REPO)
    assert proc.returncode != 0
    assert "no CPU mode" in proc.stderr
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")


def test_a_bare_directory_is_refused(tmp_path):
    shutil.copytree(os.path.join(REPO, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "perfbench" / "run.py"),
         "--workload", "rmat-s16.k2", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=str(tmp_path))
    assert proc.returncode != 0 and "no kaminpar_tpu package" in proc.stderr
