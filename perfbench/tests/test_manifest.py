"""``BENCHMARK.json`` against the limits of its contract that a file can
be checked for, and against the files it names (run by hand)."""

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["perfbench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert isinstance(MANIFEST["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    cells = len(MANIFEST["workloads"])
    # what a full check may cost with the full 24 cells
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200
    assert 2 <= cells <= 24
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, cells // 2)


def test_configs_and_workloads_name_files_that_exist():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for config in MANIFEST["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(config["name"]) and _line(config["source"])
        assert _line(config["why"]) and len(config["reduced"]) <= 16
        assert config["file"].startswith("perfbench/")
        with open(os.path.join(REPO, config["file"])) as f:
            body = json.load(f)
        assert body["source"] == config["source"]
        # how a replay relates to another is stated, not left to the default
        assert body["guarantees"]["replay"] in ("bitwise", "feasible")
        assert os.path.isfile(os.path.join(
            BENCH, "generators", body["generator"] + ".py"))
        for key in config["reduced"]:
            assert NAME.match(key) and key in body["reduced"]
    pairs = set()
    for cell in MANIFEST["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["config"] in configs and cell["chips"] in (1, 4)
        assert _line(cell["why"])
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", cell["traffic"] + ".json"))
        pairs.add((cell["config"], cell["traffic"]))
    assert len(pairs) == len(MANIFEST["workloads"])
    assert {c["config"] for c in MANIFEST["workloads"]} == set(configs)


def test_metrics_agree_with_their_readers():
    names = set()
    end_to_end = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    for metric in MANIFEST["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "bound", "source"}
        assert metric["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= metric["bound"] <= 0.25
    for metric in MANIFEST["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "source", "layer", "moves"}
        assert metric["moves"] in end_to_end and _line(metric["layer"])
        path = os.path.join(BENCH, "layer_metrics", metric["name"] + ".py")
        spec = importlib.util.spec_from_file_location(metric["name"], path)
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["moves"],
            metric["source"])
        assert reader.CELLS == metric.get("workloads")
        assert callable(reader.read)
    for metric in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
        assert metric["name"] not in names
        names.add(metric["name"])


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            assert ok.match(os.path.relpath(os.path.join(base, name), REPO))
