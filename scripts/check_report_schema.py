#!/usr/bin/env python
"""Validate a run-report JSON against the checked-in schema.

CI / tooling backstop for the telemetry run report (`--report-json`,
bench.py's embedded `report`): the schema lives at
kaminpar_tpu/telemetry/run_report.schema.json and this validator is a
dependency-free subset of JSON Schema (type / required / properties /
items / enum) — enough to catch drift (renamed or dropped sections,
type changes) without pulling in the `jsonschema` package.  A fast
tier-1 test (tests/test_telemetry.py) generates a report and runs this
validator, so schema and producer cannot drift apart silently.

Usage:  python scripts/check_report_schema.py report.json [--schema S]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, List

DEFAULT_SCHEMA = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    os.pardir,
    "kaminpar_tpu",
    "telemetry",
    "run_report.schema.json",
)

_TYPES = {
    "object": dict,
    "array": list,
    "string": str,
    "number": (int, float),
    "integer": int,
    "boolean": bool,
    "null": type(None),
}


def _type_ok(value: Any, expected) -> bool:
    if isinstance(expected, list):  # union, e.g. ["number", "null"]
        return any(_type_ok(value, e) for e in expected)
    py = _TYPES.get(expected)
    if py is None:
        return True  # unknown type keyword: don't fail on it
    if expected in ("integer", "number") and isinstance(value, bool):
        return False  # bool is an int subclass in Python; JSON disagrees
    return isinstance(value, py)


def validate_instance(instance: Any, schema: dict, path: str = "$") -> List[str]:
    """Returns a list of human-readable violations (empty = valid)."""
    errors: List[str] = []
    expected = schema.get("type")
    if expected is not None and not _type_ok(instance, expected):
        errors.append(
            f"{path}: expected {expected}, got {type(instance).__name__}"
        )
        return errors  # child checks would only cascade
    enum = schema.get("enum")
    if enum is not None and instance not in enum:
        errors.append(f"{path}: value {instance!r} not in enum {enum}")
    if isinstance(instance, dict):
        for req in schema.get("required", []):
            if req not in instance:
                errors.append(f"{path}: missing required key {req!r}")
        props = schema.get("properties", {})
        for key, sub in props.items():
            if key in instance:
                errors.extend(
                    validate_instance(instance[key], sub, f"{path}.{key}")
                )
    if isinstance(instance, list):
        items = schema.get("items")
        if items:
            for i, item in enumerate(instance):
                errors.extend(
                    validate_instance(item, items, f"{path}[{i}]")
                )
    return errors


def version_checks(report: Any) -> List[str]:
    """Schema_version-conditional requirements the dependency-free
    validator subset cannot express (no if/then): v2+ reports must carry
    the `progress` and `compile` sections, v3+ additionally the
    `checkpoint` and `anytime` sections, v4+ additionally the `serving`
    section, v5+ additionally the `perf` section, v6+ additionally the
    `memory_budget` section, v7+ additionally the `quality` section,
    v8+ additionally the `dist_resilience` section, v9+ additionally
    the `external` section, v10+ additionally the `supervision`
    section, v11+ additionally the `dynamic` section, v12+ additionally
    the `tracing` section, v13+ additionally the `ledger` section,
    v14+ additionally the `integrity` section; older reports remain
    valid without them during the transition."""
    errors: List[str] = []
    if not isinstance(report, dict):
        return errors
    version = report.get("schema_version")
    if not isinstance(version, int):
        return errors
    required_by_version = [
        (2, ("progress", "compile")),
        (3, ("checkpoint", "anytime")),
        (4, ("serving",)),
        (5, ("perf",)),
        (6, ("memory_budget",)),
        (7, ("quality",)),
        (8, ("dist_resilience",)),
        (9, ("external",)),
        (10, ("supervision",)),
        (11, ("dynamic",)),
        (12, ("tracing",)),
        (13, ("ledger",)),
        (14, ("integrity",)),
    ]
    for min_version, keys in required_by_version:
        if version < min_version:
            continue
        for key in keys:
            if key not in report:
                errors.append(
                    f"$: schema_version {version} requires section {key!r}"
                )
    return errors


def _minimal_v1_report() -> dict:
    """A minimal schema_version-1 report (the pre-progress/compile
    layout) — the transition fixture --selftest validates alongside the
    live v2 producer, so v1 artifacts (old BENCH lines, archived
    --report-json files) keep validating."""
    return {
        "schema_version": 1,
        "environment": {
            "version": "0", "python": "3", "platform": "cpu",
            "device_count": 1, "process_count": 1, "jax_version": "0",
        },
        "run": {"preset": "default", "seed": 1, "k": 2},
        "result": {"cut": 0, "imbalance": 0.0, "feasible": True},
        "scope_tree": {},
        "levels": [],
        "comm": {"caveat": "none", "records": []},
        "events": [],
        "counters": {},
        "faults": {"plan": None, "sites": [], "injected": []},
        "degraded": [],
        "output_gate": {"checked": False},
    }


def _minimal_v2_report() -> dict:
    """A minimal schema_version-2 report (progress/compile present, no
    checkpoint/anytime sections) — the second transition fixture."""
    r = _minimal_v1_report()
    r["schema_version"] = 2
    r["progress"] = []
    r["compile"] = {"caveat": "none", "totals": {}, "phases": {}}
    return r


def _minimal_v3_report() -> dict:
    """A minimal schema_version-3 report (checkpoint/anytime present, no
    serving section) — the third transition fixture."""
    r = _minimal_v2_report()
    r["schema_version"] = 3
    r["checkpoint"] = {"enabled": False}
    r["anytime"] = {"anytime": False}
    return r


def _minimal_v4_report() -> dict:
    """A minimal schema_version-4 report (serving present, no perf
    section) — the fourth transition fixture."""
    r = _minimal_v3_report()
    r["schema_version"] = 4
    r["serving"] = {"enabled": False}
    return r


def _minimal_v5_report() -> dict:
    """A minimal schema_version-5 report (perf present, no
    memory_budget section) — the fifth transition fixture."""
    r = _minimal_v4_report()
    r["schema_version"] = 5
    r["perf"] = {"enabled": False}
    return r


def _minimal_v6_report() -> dict:
    """A minimal schema_version-6 report (memory_budget present, no
    quality section) — the sixth transition fixture."""
    r = _minimal_v5_report()
    r["schema_version"] = 6
    r["memory_budget"] = {"enabled": False}
    return r


def _minimal_v7_report() -> dict:
    """A minimal schema_version-7 report (quality present, no
    dist_resilience section) — the seventh transition fixture."""
    r = _minimal_v6_report()
    r["schema_version"] = 7
    r["quality"] = {"enabled": False}
    return r


def _minimal_v8_report() -> dict:
    """A minimal schema_version-8 report (dist_resilience present, no
    external section) — the eighth transition fixture."""
    r = _minimal_v7_report()
    r["schema_version"] = 8
    r["dist_resilience"] = {"enabled": False}
    return r


def _minimal_v9_report() -> dict:
    """A minimal schema_version-9 report (external present, no
    supervision section) — the ninth transition fixture."""
    r = _minimal_v8_report()
    r["schema_version"] = 9
    r["external"] = {"enabled": False}
    return r


def _minimal_v10_report() -> dict:
    """A minimal schema_version-10 report (supervision present, no
    dynamic section) — the tenth transition fixture."""
    r = _minimal_v9_report()
    r["schema_version"] = 10
    r["supervision"] = {"enabled": False}
    return r


def _minimal_v11_report() -> dict:
    """A minimal schema_version-11 report (dynamic present, no
    tracing section) — the eleventh transition fixture."""
    r = _minimal_v10_report()
    r["schema_version"] = 11
    r["dynamic"] = {"enabled": False}
    return r


def _minimal_v12_report() -> dict:
    """A minimal schema_version-12 report (tracing present, no
    ledger section) — the twelfth transition fixture."""
    r = _minimal_v11_report()
    r["schema_version"] = 12
    r["tracing"] = {"enabled": False, "traces": []}
    return r


def _minimal_v13_report() -> dict:
    """A minimal schema_version-13 report (ledger present, no
    integrity section) — the thirteenth transition fixture."""
    r = _minimal_v12_report()
    r["schema_version"] = 13
    r["ledger"] = {"enabled": False}
    return r


def _selftest_report(path: str) -> None:
    """Generate a minimal live report so producer and schema are checked
    against each other with no partition run (the pre-commit /
    check_all.sh fast path).  Annotates non-default `checkpoint`,
    `anytime`, and `serving` sections so the v3/v4 producer surface is
    exercised, not just its empty defaults; the v5 `perf` section comes
    from the live observatory (a pad-waste record and a memory sample
    are injected so the producer emits non-empty subsections)."""
    # run as a script, sys.path[0] is scripts/ — add the repo root
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from kaminpar_tpu import telemetry
    from kaminpar_tpu.telemetry.report import write_run_report

    telemetry.enable()
    telemetry.annotate(result={"cut": 0, "imbalance": 0.0, "feasible": True})
    telemetry.annotate(
        checkpoint={
            "enabled": True, "dir": "/tmp/ckpt", "memory_only": False,
            "generation": 2, "writes": 2, "bytes": 1024, "wall_s": 0.01,
            "resumed_from": "uncoarsen:1",
            "snapshots": ["level-0", "state"],
        },
        anytime={
            "anytime": True, "reason": "budget", "stage": "uncoarsen:1",
            "budget_s": 1.0, "grace_s": 30.0, "elapsed_s": 1.2,
        },
        memory_budget={
            "enabled": True, "budget_bytes": 1 << 30,
            "estimate_bytes": 900 << 20, "bucket": "8192/65536/4",
            "rung": 2, "rung_name": "spill-hierarchy", "initial_rung": 0,
            "exhausted": False, "watermark_bytes": 800 << 20,
            "pressure_events": 1, "shed_cache_bytes": 4096,
            "spills": {"count": 2, "bytes": 1 << 20, "reloads": 2,
                       "reload_bytes": 1 << 20},
        },
        serving={
            "enabled": True,
            "requests": [
                {"request_id": "req-1", "verdict": "served", "k": 4,
                 "n": 100, "m": 400, "cut": 12, "imbalance": 0.01,
                 "feasible": True, "cached": False, "gate_valid": True,
                 "bucket": "256/512/4", "wall_s": 0.5,
                 "hard_ceiling_s": 30.0},
                {"request_id": "req-2", "verdict": "rejected",
                 "reason": "queue-full", "k": 4, "n": -1, "m": -1,
                 "cut": -1, "imbalance": 0.0, "feasible": False,
                 "cached": False, "wall_s": 0.0},
            ],
            "counts": {"served": 1, "anytime": 0, "degraded": 0,
                       "rejected": 1, "failed": 0},
            "admission": {"max_queue_depth": 64,
                          "max_queued_cost": 5e7,
                          "max_request_cost": 2.5e7, "rejected": 1},
            "cache": {"result": {"hits": 0, "misses": 1,
                                 "hit_rate": 0.0},
                      "executable": {"buckets": 1, "hits": 0,
                                     "misses": 1, "hit_rate": 0.0},
                      "hit_rate": 0.0},
            "drained": False,
        },
        dynamic={
            "enabled": True,
            "sessions": [
                {"id": "s1", "n": 100, "m": 400, "k": 4,
                 "deltas_applied": 3, "in_place": 2, "rebuilds": 1,
                 "repartitions": 3, "chain": "dyn:abc123",
                 "bucket": "256/512/4", "cut": 10},
            ],
            "decisions": [
                {"session": "s1", "step": 1, "mode": "warm",
                 "drift": 0.01, "cut_before": 12, "cut": 10,
                 "feasible": True, "stable": True, "gate_valid": True,
                 "escalated": False, "seeded": 1, "in_place": True,
                 "wall_s": 0.2, "warm_wall_s": 0.2,
                 "cold_wall_s": None},
                {"session": "s1", "step": 2, "mode": "replica",
                 "drift": 0.4, "cut_before": 10, "cut": 11,
                 "feasible": True, "stable": True, "escalated": False,
                 "seeded": 0, "wall_s": 0.5, "warm_wall_s": 0.2,
                 "cold_wall_s": 0.3, "replica_cuts": [12, 11]},
            ],
            "counts": {"warm": 1, "cold": 0, "replica": 1,
                       "escalated": 0, "deltas": 3, "in_place": 2,
                       "rebuilds": 1},
            "cut_trajectory": [10, 11],
        },
        supervision={
            "enabled": True,
            "isolation": "process",
            "workers": {"spawned": 2, "recycled": 1, "killed": 1,
                        "crashed": 1, "requests": 10},
            "hangs": [{"stage": "worker-compute",
                       "path": "partitioning.coarsening",
                       "ceiling_s": 2.0, "request": "req-9",
                       "worker_pid": 1234}],
            "heartbeat": {"file": "/tmp/hb", "count": 42},
            "watchdog": {"armed": 3, "fired": 1},
        },
    )
    # exercise the v5 perf producer surface: one pad-waste record and
    # one barrier-style memory sample (both host-side no-ops when the
    # layer is off; here telemetry is on so they land in the report)
    from kaminpar_tpu.telemetry import perf

    perf.record_padding(n=100, n_pad=256, m=400, m_pad=512, k=4, k_pad=4)
    perf.sample_memory("selftest")
    # exercise the v7 quality producer surface: drive the recorder over
    # a tiny handmade hierarchy (pure numpy — no device work) so the
    # section carries a real attribution row, not just its default
    from kaminpar_tpu.graphs.factories import make_cycle
    from kaminpar_tpu.telemetry import quality

    if quality.enabled():
        import numpy as np

        g = make_cycle(8)
        qh = quality.begin("selftest")
        try:
            # one contraction: pair up the cycle's nodes
            quality.note_cmap(
                1, np.repeat(np.arange(4, dtype=np.int64), 2), 8
            )
            part = np.asarray([0, 0, 0, 0, 1, 1, 1, 1], dtype=np.int32)
            quality.note_projected(1, cut=4)
            quality.note_refined(1, cut=3)
            quality.finalize_host(qh, g, part)
        finally:
            quality.end(qh)
    write_run_report(path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="validate a kaminpar-tpu run report against the schema"
    )
    ap.add_argument(
        "report", nargs="?", default=None,
        help="run-report JSON file (--report-json); omit with --selftest",
    )
    ap.add_argument(
        "--schema", default=DEFAULT_SCHEMA, help="schema file to check against"
    )
    ap.add_argument(
        "--selftest", action="store_true",
        help="generate a minimal report from the live producer (schema "
        "v14) and validate it plus the embedded v1-v13 transition "
        "fixtures (no report file needed)",
    )
    args = ap.parse_args(argv)

    if args.selftest:
        if args.report is not None:
            ap.error("--selftest generates its own report; drop the "
                     "report argument (or the flag) — refusing to "
                     "silently ignore the given file")
        import tempfile

        fd, args.report = tempfile.mkstemp(
            prefix="kmp_report_", suffix=".json"
        )
        os.close(fd)
        try:
            _selftest_report(args.report)
            with open(args.schema) as f:
                schema = json.load(f)
            with open(args.report) as f:
                report = json.load(f)
        finally:
            os.unlink(args.report)
        # live producer must emit v14 (progress/compile +
        # checkpoint/anytime + serving + perf + memory_budget +
        # quality + dist_resilience + external + supervision +
        # dynamic + tracing + ledger + integrity)
        if report.get("schema_version") != 14:
            print(
                f"SCHEMA VIOLATION $: selftest producer emitted "
                f"schema_version {report.get('schema_version')!r}, "
                f"expected 14",
                file=sys.stderr,
            )
            return 1
        for key in ("checkpoint", "anytime", "serving", "perf",
                    "memory_budget", "quality", "dist_resilience",
                    "external", "supervision", "dynamic", "tracing",
                    "ledger", "integrity"):
            if key not in report:
                print(
                    f"SCHEMA VIOLATION $: selftest producer emitted no "
                    f"{key!r} section",
                    file=sys.stderr,
                )
                return 1
        # the injected pad-waste record must surface as a non-empty
        # producer subsection (catches a silently dead observatory);
        # KAMINPAR_TPU_PERF=0 legitimately disables the layer
        if report["perf"].get("enabled") and not report["perf"].get(
            "pad_waste"
        ):
            print(
                "SCHEMA VIOLATION $: selftest perf section carries no "
                "pad_waste rows despite an injected record",
                file=sys.stderr,
            )
            return 1
        # the injected hierarchy must surface as a non-default quality
        # section (catches a silently dead quality observatory);
        # KAMINPAR_TPU_QUALITY=0 legitimately disables the layer
        if report["quality"].get("enabled") and not report["quality"].get(
            "levels"
        ):
            print(
                "SCHEMA VIOLATION $: selftest quality section carries "
                "no level rows despite an injected hierarchy",
                file=sys.stderr,
            )
            return 1
        # transition coverage: the v1-v12 layouts must STILL validate
        for label, fixture in (
            ("v1", _minimal_v1_report()), ("v2", _minimal_v2_report()),
            ("v3", _minimal_v3_report()), ("v4", _minimal_v4_report()),
            ("v5", _minimal_v5_report()), ("v6", _minimal_v6_report()),
            ("v7", _minimal_v7_report()), ("v8", _minimal_v8_report()),
            ("v9", _minimal_v9_report()), ("v10", _minimal_v10_report()),
            ("v11", _minimal_v11_report()), ("v12", _minimal_v12_report()),
            ("v13", _minimal_v13_report()),
        ):
            fx_errors = (
                validate_instance(fixture, schema) + version_checks(fixture)
            )
            if fx_errors:
                for e in fx_errors:
                    print(
                        f"SCHEMA VIOLATION ({label} fixture) {e}",
                        file=sys.stderr,
                    )
                return 1
    elif args.report is None:
        ap.error("a report file is required unless --selftest is given")
    else:
        with open(args.schema) as f:
            schema = json.load(f)
        with open(args.report) as f:
            report = json.load(f)

    errors = validate_instance(report, schema) + version_checks(report)
    if errors:
        for e in errors:
            print(f"SCHEMA VIOLATION {e}", file=sys.stderr)
        print(f"{args.report}: {len(errors)} violation(s)", file=sys.stderr)
        return 1
    print(f"{args.report}: OK (schema_version "
          f"{report.get('schema_version')})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
