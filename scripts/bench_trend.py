#!/usr/bin/env python
"""Render (and sanity-check) the recorded BENCH trajectory.

The harness records one `BENCH_rNN.json` per round: the bench.py exit
status, output tail, and the parsed BENCH line (which, since the
telemetry layer landed, embeds the schema-validated run report).  This
tool turns the checked-in trajectory into a table — cut, vs_baseline,
wall seconds, and the compile split when a round carries a v2 report —
so "did round N regress round N-1" is a read, not an archaeology dig.

Usage:
  python scripts/bench_trend.py [--dir REPO] [--json]
  python scripts/bench_trend.py --check     # CI: structural validation

`--check` exits non-zero when a recorded round is malformed (unreadable
JSON, rc==0 without a parsed BENCH line, parsed line missing the metric
fields, a schema-v5 report without its `perf` section) — the
perf-observatory columns' movements between rounds (hbm_util,
pad_waste, p95_ms) are PRINTED, not gated: rounds run on different code
by design, and the per-PR regression gate is `telemetry.diff` on
like-for-like reports (scripts/check_all.sh), which DOES gate serving
hit-rate and served-count regressions.

`--check` is ALSO the kernel regression gate (round 9): the LATEST
parsed round must keep `vs_baseline` above the cut floor (cuts are
platform-independent), must still carry every 10M-coverage key
(BENCH_r05 dropped them silently — presence is gated, null marks a
failed measurement), and — on accelerator rounds only, where walls are
meaningful — must keep `lp_coarsening_seconds` under the ceiling and
`hbm_util` above the utilization floor.  Floors are flags
(--cut-floor/--coarsening-ceiling/--hbm-util-floor) so a deliberate
re-baseline is an explicit diff, not a silent drift.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

REQUIRED_PARSED_KEYS = ("metric", "value", "unit")

#: 10M-edge coverage keys every round from r06 on must carry (null =
#: the measurement failed; ABSENT = the bench silently lost coverage,
#: which is what r05 did and what this gate exists to catch), plus the
#: kernel-utilization probes.
LARGE_COVERAGE_KEYS = (
    "lp_coarsening_10m_seconds", "cut_10m", "feasible_10m",
    "vs_baseline_cut_10m", "util_gather_pct_hbm",
    "util_scatter_add_pct_hbm", "util_stream_cumsum_pct_hbm",
)
#: Rounds BELOW this index predate the coverage contract (the gate
#: applies to rno >= LARGE_COVERAGE_SINCE, i.e. r06 onward).
LARGE_COVERAGE_SINCE = 6

#: Quality-attribution keys (round 11, telemetry/quality.py): the BENCH
#: line must always carry them from r06 on (same presence contract as
#: the 10M block — null marks a run without attribution, absence a
#: silent coverage loss).  Their VALUES are advisory only (see
#: --locked-frac-ceiling): the floor is relative to each run's own
#: final partition, so the fraction is a direction signal, not a gate.
QUALITY_COVERAGE_KEYS = ("coarsening_locked_frac",
                         "refinement_left_frac")

#: Out-of-core streaming keys (round 13, kaminpar_tpu/external/): the
#: BENCH line must always carry them from r06 on (null = the external
#: measurement was skipped/failed, absence = silent coverage loss of
#: the scale path — the r05 regression class).
EXTERNAL_COVERAGE_KEYS = ("external_seconds", "stream_overlap")

#: Supervised-serving key (round 14, resilience/supervisor.py): the
#: BENCH line must always carry it from r06 on (null = the supervised
#: batch was skipped/failed or the platform can't spawn workers,
#: absence = silent coverage loss of the containment boundary's
#: latency trend — the r05 regression class).
SUPERVISED_COVERAGE_KEYS = ("supervised_p95_ms",)

#: Dynamic-repartitioning keys (round 15, kaminpar_tpu/dynamic/): the
#: BENCH line must always carry them from r06 on (null = the dynamic
#: chain measurement was skipped/failed, absence = silent coverage
#: loss of the warm-repartition trend — the r05 regression class).
DYNAMIC_COVERAGE_KEYS = ("dynamic_warm_speedup", "dynamic_cut_drift")

#: Serving-throughput keys (round 16, fleet observatory): the BENCH
#: line must always carry them from r06 on (null = the supervised
#: batch was skipped/failed, absence = silent coverage loss of the
#: throughput trend — the r05 regression class).
THROUGHPUT_COVERAGE_KEYS = ("requests_per_second", "batch_occupancy")

#: Static-analysis key (round 17, tpulint v2): the BENCH line must
#: always carry the full-rule lint pass's wall from r06 on (null = the
#: lint run errored, absence = silent coverage loss of the commit
#: gate's own cost trend — the r05 regression class).
LINT_COVERAGE_KEYS = ("tpulint_seconds",)

#: Execution-ledger keys (round 19, telemetry/ledger.py): the BENCH
#: line must always carry them from r06 on (null = the report had no
#: ledger, absence = silent coverage loss of the launch-honesty and
#: transfer-bytes trends — the r05 regression class).  The transfer
#: VALUES are advisory (printed as a column, never gated); the honesty
#: of accelerator rounds IS gated — see _roofline_honesty_errors.
LEDGER_COVERAGE_KEYS = ("util_honest", "launches_total",
                        "transfer_bytes_per_phase")

#: Integrity-sentinel key (round 20, resilience/integrity.py): the
#: BENCH line must always carry the sentinel-overhead percentage from
#: r06 on (0.0 = the kill switch disabled the layer, absence = silent
#: coverage loss of the corruption-defense cost trend — the r05
#: regression class).  The VALUE is advisory only (printed as a
#: column, never gated): the < 3% dormancy budget is a test assertion
#: (tests/test_integrity.py), not a trend gate.
INTEGRITY_COVERAGE_KEYS = ("integrity_overhead_pct",)

#: Platforms whose wall/utilization figures are meaningful (the CPU
#: fallback's walls are smoke signals by repo doctrine — bench.py
#: stamps `platform` exactly so gates can tell).
ACCEL_PLATFORMS = ("tpu",)

#: Dist-resilience coverage keys the MULTICHIP dryrun tail must carry
#: from r06 on (round 12, __graft_entry__.dryrun_multichip): the
#: kill-and-resume cut-identity probe and the agreed-OOM-ladder probe.
#: Same presence contract as the 10M block — absence means the dryrun
#: silently lost the coverage, which is the r05 regression class.
#: The comm-volume key (round 16): the dryrun tail must carry the
#: machine-readable per-run collective rollup from r06 on.
MULTICHIP_COVERAGE_KEYS = (
    "dist_resumable=", "dist_ladder=", "comm_bytes_total=",
)
MULTICHIP_COVERAGE_SINCE = 6


def load_multichip_rounds(repo: str) -> List[Tuple[str, dict]]:
    paths = sorted(glob.glob(os.path.join(repo, "MULTICHIP_r*.json")))
    return [(p, json.load(open(p))) for p in paths]


def check_multichip_round(path: str, entry: Any) -> List[str]:
    """MULTICHIP_rNN structural + coverage validation: a successful
    (ok, not skipped) round from r06 on must carry the dist-resilience
    keys in its tail."""
    errors: List[str] = []
    name = os.path.basename(path)
    if not isinstance(entry, dict):
        return [f"{name}: not a JSON object"]
    rno = _round_number(name)
    if (
        rno is None
        or rno < MULTICHIP_COVERAGE_SINCE
        or not entry.get("ok")
        or entry.get("skipped")
    ):
        return errors
    tail = entry.get("tail") or ""
    for key in MULTICHIP_COVERAGE_KEYS:
        if key not in tail:
            errors.append(
                f"{name}: MULTICHIP coverage key {key!r} missing from "
                "the dryrun tail (r05 regression class — "
                "dryrun_multichip must emit it every round)"
            )
    return errors


def _roofline_honesty_errors(name: str, parsed: dict) -> List[str]:
    """Accelerator rounds with a v13+ embedded report must have a LIVE
    launch ledger: when every roofline row that reports hbm_util
    carries honest=false, the ledger recorded nothing and the recorded
    utilization trend silently degraded to compile-time lower bounds
    (KAMINPAR_TPU_LEDGER=0 on a recorded round, or the executable-call
    interception died).  Pre-v13 reports (no `honest` stamps) and
    CPU-fallback rounds are exempt."""
    report = parsed.get("report") or {}
    if not isinstance(report, dict):
        return []
    version = report.get("schema_version")
    if not isinstance(version, int) or version < 13:
        return []
    if parsed.get("platform") not in ACCEL_PLATFORMS:
        return []
    roof = (report.get("perf") or {}).get("roofline") or {}
    rows = [
        e for e in roof.values()
        if isinstance(e, dict) and e.get("hbm_util") is not None
    ]
    if rows and all(not e.get("honest") for e in rows):
        return [
            f"{name}: every roofline row is honest=false on an "
            "accelerator round — the launch ledger recorded nothing "
            "(dead interception or KAMINPAR_TPU_LEDGER=0 on a "
            "recorded round)"
        ]
    return []


def _round_number(name: str) -> Optional[int]:
    """BENCH_r07.json -> 7 (None for non-conforming names)."""
    stem = os.path.splitext(name)[0]
    digits = "".join(ch for ch in stem if ch.isdigit())
    return int(digits) if digits else None


def load_rounds(repo: str) -> List[Tuple[str, dict]]:
    paths = sorted(glob.glob(os.path.join(repo, "BENCH_r*.json")))
    return [(p, json.load(open(p))) for p in paths]


def check_round(path: str, entry: Any) -> List[str]:
    errors: List[str] = []
    name = os.path.basename(path)
    if not isinstance(entry, dict):
        return [f"{name}: not a JSON object"]
    for key in ("n", "cmd", "rc"):
        if key not in entry:
            errors.append(f"{name}: missing key {key!r}")
    rc = entry.get("rc")
    parsed = entry.get("parsed")
    if rc == 0:
        if not isinstance(parsed, dict):
            errors.append(f"{name}: rc==0 but no parsed BENCH line")
        else:
            for key in REQUIRED_PARSED_KEYS:
                if key not in parsed:
                    errors.append(
                        f"{name}: parsed BENCH line missing {key!r}"
                    )
            report = parsed.get("report")
            if report is not None and (
                not isinstance(report, dict)
                or "schema_version" not in report
            ):
                errors.append(
                    f"{name}: embedded report lacks schema_version"
                )
            elif (
                isinstance(report, dict)
                and isinstance(report.get("schema_version"), int)
                and report["schema_version"] >= 5
                and "perf" not in report
            ):
                errors.append(
                    f"{name}: schema-v5 report carries no perf section"
                )
    return errors


def _row(path: str, entry: dict) -> Dict[str, Any]:
    parsed = entry.get("parsed") or {}
    report = parsed.get("report") or {}
    compile_totals = report.get("compile", {}).get("totals", {})
    # v4 reports from a serving run carry the bounded-cache hit rate —
    # the first-class serving metric alongside cut/seconds (rounds
    # without a serving section show "-")
    serving = report.get("serving") or {}
    cache_hit = (serving.get("cache") or {}).get("hit_rate")
    # v5 reports carry the perf observatory's headline columns: overall
    # achieved-vs-peak HBM utilization, overall padding waste, and (for
    # serve-mode rounds) the caller-observed p95 latency
    perf_totals = (report.get("perf") or {}).get("totals") or {}
    p95_ms = (
        ((serving.get("latency") or {}).get("phases") or {})
        .get("total", {}).get("p95_ms")
    )
    # per-kernel seconds (round-9 bench.py `kernel_seconds`); older
    # rounds fall back to the embedded report's scope tree
    kernels = parsed.get("kernel_seconds") or {}
    if not kernels:
        coars = (
            (report.get("scope_tree") or {})
            .get("partitioning", {}).get("children", {})
            .get("coarsening", {}).get("children", {})
        )
        kernels = {
            short: coars[scope]["elapsed_s"]
            for short, scope in (("lp", "lp-clustering"),
                                 ("contraction", "contraction"))
            if scope in coars
        }
    engines = parsed.get("rating_engines") or (
        (report.get("rating") or {}).get("engines") or {}
    )
    # round-11 quality attribution: promoted BENCH keys first, falling
    # back to the embedded report's quality totals for older rounds
    q_totals = (report.get("quality") or {}).get("totals") or {}
    locked = parsed.get(
        "coarsening_locked_frac", q_totals.get("coarsening_locked_frac")
    )
    left = parsed.get(
        "refinement_left_frac", q_totals.get("refinement_left_frac")
    )
    # round-13 out-of-core streaming: promoted BENCH keys first, the
    # embedded report's external section as the older-round fallback
    ext_section = report.get("external") or {}
    ext_s = parsed.get("external_seconds")
    overlap = parsed.get(
        "stream_overlap", ext_section.get("overlap_frac")
    )
    return {
        "round": os.path.basename(path),
        "rc": entry.get("rc"),
        "cut": parsed.get("value"),
        "vs_baseline": parsed.get("vs_baseline"),
        "total_s": parsed.get("total_seconds"),
        "coarsening_s": parsed.get("lp_coarsening_seconds"),
        "lp_s": kernels.get("lp"),
        "contract_s": kernels.get("contraction"),
        "engines": ",".join(
            f"{k}:{v}" for k, v in sorted(engines.items())
        ) or None,
        "platform": parsed.get("platform"),
        "compile_s": compile_totals.get("compile_s"),
        "cache_hit": cache_hit,
        "hbm_util": parsed.get("hbm_util", perf_totals.get("hbm_util")),
        "pad_waste": parsed.get(
            "pad_waste", perf_totals.get("pad_waste")
        ),
        "locked": locked,
        "left": left,
        "external_s": ext_s,
        "overlap": overlap,
        "p95_ms": p95_ms,
        "sup_p95": parsed.get("supervised_p95_ms"),
        # round-16 fleet observatory: promoted throughput keys first,
        # the embedded report's serving.throughput as the fallback
        "rps": parsed.get(
            "requests_per_second",
            (serving.get("throughput") or {}).get("requests_per_second"),
        ),
        "occupancy": parsed.get(
            "batch_occupancy",
            (serving.get("throughput") or {}).get("batch_occupancy"),
        ),
        "dyn_speedup": parsed.get("dynamic_warm_speedup"),
        "dyn_drift": parsed.get("dynamic_cut_drift"),
        # round-19 execution ledger (advisory columns): whether the
        # hbm_util figure is launch-joined truth, and the total
        # host<->device bytes (promoted key first, embedded report's
        # ledger totals as the fallback)
        "honest": parsed.get("util_honest"),
        "xfer_b": _transfer_bytes(parsed, report),
        # round-20 integrity sentinels (advisory column): host-side
        # sentinel wall as % of the partition wall — the dormancy
        # budget as a trend line
        "integ_pct": parsed.get("integrity_overhead_pct"),
        "schema": report.get("schema_version"),
    }


def _transfer_bytes(parsed: dict, report: dict) -> Optional[int]:
    totals = (
        ((report.get("ledger") or {}).get("transfers") or {})
        .get("totals") or {}
    )
    if totals:
        return (
            int(totals.get("h2d_bytes", 0)) + int(totals.get("d2h_bytes", 0))
        ) or None
    phases = parsed.get("transfer_bytes_per_phase")
    if isinstance(phases, dict):
        return sum(
            int(t.get("h2d_bytes", 0)) + int(t.get("d2h_bytes", 0))
            for t in phases.values() if isinstance(t, dict)
        ) or None
    return None


def _fmt(v: Optional[Any]) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.3g}"
    return str(v)


def render(rows: List[Dict[str, Any]]) -> str:
    cols = ("round", "rc", "cut", "vs_baseline", "total_s",
            "coarsening_s", "lp_s", "contract_s", "engines",
            "compile_s", "cache_hit", "hbm_util",
            "pad_waste", "locked", "left", "external_s", "overlap",
            "p95_ms", "sup_p95", "rps", "occupancy",
            "dyn_speedup", "dyn_drift", "honest", "xfer_b",
            "integ_pct", "platform", "schema")
    table = [cols] + [tuple(_fmt(r[c]) for c in cols) for r in rows]
    widths = [max(len(row[i]) for row in table) for i in range(len(cols))]
    lines = [
        "  ".join(cell.ljust(widths[i]) for i, cell in enumerate(row))
        for row in table
    ]
    # movement annotations between consecutive parsed rounds
    prev = None
    for r in rows:
        if prev and r["cut"] and prev["cut"]:
            delta = 100.0 * (r["cut"] - prev["cut"]) / prev["cut"]
            if abs(delta) >= 5.0:
                lines.append(
                    f"note: {prev['round']} -> {r['round']} cut moved "
                    f"{delta:+.1f}%"
                )
        if prev:
            # perf-observatory movement notes (printed, never gated —
            # see the module docstring's gating rationale)
            for col, floor in (("hbm_util", 0.01), ("pad_waste", 0.05),
                               ("locked", 0.1), ("left", 0.1),
                               ("p95_ms", None)):
                a, b = prev.get(col), r.get(col)
                if a is None or b is None:
                    continue
                if col == "p95_ms":
                    if a > 0 and abs(b - a) / a >= 0.5:
                        lines.append(
                            f"note: {prev['round']} -> {r['round']} "
                            f"p95_ms moved {a} -> {b}"
                        )
                elif abs(b - a) >= floor:
                    lines.append(
                        f"note: {prev['round']} -> {r['round']} "
                        f"{col} moved {a} -> {b}"
                    )
        if r["cut"] is not None:
            prev = r
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="render / validate the BENCH_r*.json trajectory"
    )
    ap.add_argument(
        "--dir",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        help="repo root holding BENCH_r*.json (default: this repo)",
    )
    ap.add_argument("--json", action="store_true", help="emit rows as JSON")
    ap.add_argument(
        "--check", action="store_true",
        help="CI mode: exit non-zero on structurally malformed rounds "
        "or a latest round past the kernel/cut gates",
    )
    ap.add_argument(
        "--cut-floor", type=float, default=0.9,
        help="latest round must keep vs_baseline >= this "
        "(platform-independent; default 0.9)",
    )
    ap.add_argument(
        "--coarsening-ceiling", type=float, default=2.0,
        help="latest ACCELERATOR round must keep lp_coarsening_seconds "
        "<= this (default 2.0 s; CPU-fallback rounds skip wall gates)",
    )
    ap.add_argument(
        "--hbm-util-floor", type=float, default=0.005,
        help="latest ACCELERATOR round must keep hbm_util >= this when "
        "the column is present (default 0.005)",
    )
    ap.add_argument(
        "--locked-frac-ceiling", type=float, default=0.75,
        metavar="FRAC",
        help="ADVISORY ceiling on the latest round's "
        "coarsening_locked_frac: past it a note is printed (never a "
        "violation — the attribution floor is relative to each run's "
        "own final partition, a lower bound like hbm_util); default "
        "0.75",
    )
    args = ap.parse_args(argv)

    try:
        rounds = load_rounds(args.dir)
    except (OSError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    if not rounds:
        print(f"no BENCH_r*.json under {args.dir}", file=sys.stderr)
        return 0 if not args.check else 1

    errors: List[str] = []
    # MULTICHIP dist-resilience coverage (rounds >= r06): presence
    # gated on successful rounds; earlier rounds predate the contract
    try:
        for path, entry in load_multichip_rounds(args.dir):
            errors.extend(check_multichip_round(path, entry))
    except (OSError, json.JSONDecodeError) as e:
        errors.append(f"MULTICHIP rounds unreadable: {e}")
    for path, entry in rounds:
        errors.extend(check_round(path, entry))
        # 10M-coverage contract for rounds newer than r05 (see
        # LARGE_COVERAGE_KEYS): presence gated, null tolerated
        name = os.path.basename(path)
        parsed = entry.get("parsed") if isinstance(entry, dict) else None
        rno = _round_number(name)
        if (
            isinstance(parsed, dict)
            and rno is not None and rno >= LARGE_COVERAGE_SINCE
        ):
            for key in LARGE_COVERAGE_KEYS:
                if key not in parsed:
                    errors.append(
                        f"{name}: 10M coverage key {key!r} missing "
                        "(r05 regression class — bench.py must emit it "
                        "every run)"
                    )
            for key in QUALITY_COVERAGE_KEYS:
                if key not in parsed:
                    errors.append(
                        f"{name}: quality coverage key {key!r} missing "
                        "(bench.py must emit it every run; null marks a "
                        "run without attribution)"
                    )
            for key in EXTERNAL_COVERAGE_KEYS:
                if key not in parsed:
                    errors.append(
                        f"{name}: external coverage key {key!r} missing "
                        "(bench.py must emit it every run; null marks a "
                        "skipped/failed external measurement)"
                    )
            for key in SUPERVISED_COVERAGE_KEYS:
                if key not in parsed:
                    errors.append(
                        f"{name}: supervised coverage key {key!r} "
                        "missing (bench.py must emit it every run; null "
                        "marks a skipped/failed supervised batch)"
                    )
            for key in DYNAMIC_COVERAGE_KEYS:
                if key not in parsed:
                    errors.append(
                        f"{name}: dynamic coverage key {key!r} missing "
                        "(bench.py must emit it every run; null marks a "
                        "skipped/failed dynamic chain measurement)"
                    )
            for key in THROUGHPUT_COVERAGE_KEYS:
                if key not in parsed:
                    errors.append(
                        f"{name}: throughput coverage key {key!r} "
                        "missing (bench.py must emit it every run; null "
                        "marks a skipped/failed supervised batch)"
                    )
            for key in LINT_COVERAGE_KEYS:
                if key not in parsed:
                    errors.append(
                        f"{name}: lint coverage key {key!r} missing "
                        "(bench.py must emit it every run; null marks "
                        "an errored lint pass)"
                    )
            for key in LEDGER_COVERAGE_KEYS:
                if key not in parsed:
                    errors.append(
                        f"{name}: ledger coverage key {key!r} missing "
                        "(bench.py must emit it every run; null marks "
                        "a report without a ledger section)"
                    )
            for key in INTEGRITY_COVERAGE_KEYS:
                if key not in parsed:
                    errors.append(
                        f"{name}: integrity coverage key {key!r} "
                        "missing (bench.py must emit it every run; 0.0 "
                        "marks a kill-switched integrity layer)"
                    )
            errors.extend(_roofline_honesty_errors(name, parsed))
    # kernel/cut regression gate on the LATEST parsed round (--check):
    # older rounds ran older code and are history, not a gate target
    latest = None
    for path, entry in reversed(rounds):
        if isinstance(entry, dict) and isinstance(entry.get("parsed"), dict):
            latest = (os.path.basename(path), entry["parsed"])
            break
    if latest is not None:
        name, parsed = latest
        # advisory quality-attribution note (never gated): a round whose
        # gap mass is mostly locked by coarsening says the next quality
        # PR should aim at clustering, not refinement schedules
        locked_frac = parsed.get("coarsening_locked_frac")
        if (
            isinstance(locked_frac, (int, float))
            and locked_frac > args.locked_frac_ceiling
        ):
            print(
                f"advisory: {name} coarsening_locked_frac {locked_frac} "
                f"exceeds {args.locked_frac_ceiling} — most of the cut "
                "gap is locked in by coarsening; triage with "
                "python -m kaminpar_tpu.telemetry.quality (not gated)"
            )
        vs = parsed.get("vs_baseline")
        if isinstance(vs, (int, float)) and vs > 0 and vs < args.cut_floor:
            errors.append(
                f"{name}: vs_baseline {vs} under the cut floor "
                f"{args.cut_floor}"
            )
        if parsed.get("platform") in ACCEL_PLATFORMS:
            wall = parsed.get("lp_coarsening_seconds")
            if (
                isinstance(wall, (int, float))
                and wall > args.coarsening_ceiling
            ):
                errors.append(
                    f"{name}: lp_coarsening_seconds {wall} over the "
                    f"ceiling {args.coarsening_ceiling}"
                )
            hbm = parsed.get("hbm_util")
            if isinstance(hbm, (int, float)) and hbm < args.hbm_util_floor:
                errors.append(
                    f"{name}: hbm_util {hbm} under the floor "
                    f"{args.hbm_util_floor}"
                )
        elif args.check:
            print(
                f"kernel gate: {name} ran on "
                f"platform={parsed.get('platform')!r} — wall/util gates "
                "skipped (CPU-fallback walls are not TPU numbers); cut "
                "and coverage gates still applied"
            )
    rows = [_row(p, e) for p, e in rounds if isinstance(e, dict)]
    if args.json:
        print(json.dumps(rows))
    else:
        print(render(rows))
    if errors:
        for e in errors:
            print(f"TREND VIOLATION {e}", file=sys.stderr)
    if args.check:
        print(f"trend check: {len(rounds)} round(s), "
              f"{len(errors)} violation(s)")
        return 1 if errors else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
