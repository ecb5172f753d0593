"""Everything that belongs to one configuration, one traffic mix, one
generator family or one per-layer metric is a file of its own, found by
the name ``BENCHMARK.json`` gives it.  Adding one is new files and new
entries, never an edit of a file that is there."""

from __future__ import annotations

import importlib.util
import json
import os


class Registry:
    """``root`` is the checkout: it holds ``BENCHMARK.json``; ``bench``
    is the benchmark's own directory (``perfbench``)."""

    def __init__(self, root: str, bench: str) -> None:
        self.root = root
        self.bench = bench
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)

    def _entry(self, section: str, name: str) -> dict:
        for entry in self.manifest[section]:
            if entry["name"] == name:
                return entry
        known = ", ".join(e["name"] for e in self.manifest[section])
        raise SystemExit(f"perfbench: no {section} entry {name!r} in "
                         f"BENCHMARK.json (known: {known})")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        path = os.path.join(self.root, self._entry("configs", name)["file"])
        with open(path) as f:
            return json.load(f)

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.bench, "traffic", name + ".json")) as f:
            return json.load(f)

    def _module(self, folder: str, name: str):
        path = os.path.join(self.bench, folder, name + ".py")
        if not os.path.isfile(path):
            raise SystemExit(f"perfbench: {path} is missing")
        spec = importlib.util.spec_from_file_location(
            f"perfbench.{folder}.{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def generator(self, family: str):
        """A module with ``generate(params, seed) -> CSR dict``."""
        return self._module("generators", family)

    def metrics_of(self, section: str, workload: str) -> list:
        """The manifest's metrics of ``end_to_end`` or ``per_layer`` that
        this cell reports."""
        return [m for m in self.manifest[section]
                if "workloads" not in m or workload in m["workloads"]]

    def layer_reader(self, metric: str):
        """A module with ``read(run) -> number or None``."""
        return self._module("layer_metrics", metric)
