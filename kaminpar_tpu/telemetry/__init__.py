"""Unified telemetry layer: spans, one-shot decision events, run annotations.

The reference solver's observability story is its parseable hierarchical
timer tree plus the per-PE min/avg/max finalize (kaminpar-common/
timer.{h,cc}, kaminpar-dist/timer.cc).  This package is the shared stream
those utilities publish into here: every `utils.timer` scope exit emits a
structured *span* (name, dotted path, wall time, optional sync time,
host/HBM peaks when heap profiling is on, statistics-counter deltas), and
discrete runtime decisions that previously vanished — jit (re)traces of
collective phases, native FM refusals, host balancer fallbacks — are
recorded as one-shot *events*.

Two exporters consume the stream:

  * `telemetry.chrome_trace` — Chrome trace-event JSON (`--trace-out`),
    loadable in Perfetto / chrome://tracing, one track per process on
    multi-host runs;
  * `telemetry.report` — a per-partition-call JSON run report
    (`--report-json`) carrying the scope tree, result metrics, per-level
    graph sizes, the collective-traffic table and an environment stamp.
    `bench.py` embeds the same dict into its BENCH line so the perf
    trajectory and ad-hoc runs share one schema
    (`run_report.schema.json`, validated by
    `scripts/check_report_schema.py`).

Disabled (the default) the layer is free: producers guard on one module
bool and record nothing — the zero-overhead-when-disabled contract the
existing timer/heap-profiler/statistics utilities already honor.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List

_enabled = False

_lock = threading.Lock()
_epoch = time.perf_counter()
_spans: List["Span"] = []
_events: List["Event"] = []
_progress: List["ProgressSeries"] = []
_run_info: Dict[str, Any] = {}
_tids: Dict[int, int] = {}


@dataclass
class Span:
    """One closed timer scope (the stream twin of a TimerNode visit)."""

    name: str
    path: str  # dotted scope path, identical to the timer tree's paths
    start: float  # seconds since the run epoch
    duration: float  # wall seconds
    tid: int = 0
    attrs: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "path": self.path,
            "start": self.start,
            "duration": self.duration,
            "tid": self.tid,
            "attrs": self.attrs,
        }


@dataclass
class Event:
    """One discrete decision (probe verdict, refusal, fallback, trace)."""

    name: str
    t: float  # seconds since the run epoch
    attrs: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"name": self.name, "t": self.t, "attrs": self.attrs}


@dataclass
class ProgressSeries:
    """Per-iteration convergence series of one algorithm loop run
    (telemetry/progress.py): parallel same-length lists keyed by stat
    name, plus the dotted scope path of the enclosing timer scope."""

    kind: str  # "lp", "jet", "fm", "balancer", "dist-lp", "dist-jet"
    path: str  # dotted scope path at emit time (timer-tree aligned)
    t0: float  # loop entry, seconds since the run epoch (0 if unknown)
    t1: float  # emit time, seconds since the run epoch
    iterations: int
    series: Dict[str, List[Any]] = field(default_factory=dict)
    attrs: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "kind": self.kind,
            "path": self.path,
            "t0": self.t0,
            "t1": self.t1,
            "iterations": self.iterations,
            "series": self.series,
            "attrs": self.attrs,
        }


def enabled() -> bool:
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True
    # The three accountings hook jax at install time: compile-cost
    # accounting listens on jax.monitoring (the package's import has
    # installed it already: it runs with telemetry off too), the perf
    # observatory wraps the backend-compile boundary and the execution
    # ledger the executable-call boundary.  Each install is idempotent,
    # and the latter two pass straight through while telemetry is
    # disabled (or KAMINPAR_TPU_PERF=0 / KAMINPAR_TPU_LEDGER=0).  A hook
    # that no longer fits the installed jax raises here rather than
    # leaving an empty section in the report.
    from . import compile_account, ledger, perf

    compile_account.install()
    perf.install()
    ledger.install()


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Clear the stream and restart the run epoch (enable state is kept).

    Callers that may run nested inside another pipeline (shm KaMinPar as
    the distributed driver's initial partitioner) must guard with
    `utils.timer.GLOBAL_TIMER.idle()` — the same open-scope caveat the
    timer's own reset documents."""
    global _epoch
    with _lock:
        _spans.clear()
        _events.clear()
        _progress.clear()
        _run_info.clear()
        _tids.clear()
        _epoch = time.perf_counter()
    try:
        from . import compile_account

        compile_account.reset()
    except Exception:
        pass
    try:
        from . import perf

        perf.reset()
    except Exception:
        pass
    try:
        from . import ledger

        ledger.reset()
    except Exception:
        pass
    try:
        from . import quality

        quality.reset()
    except Exception:
        pass


def jsonable(v: Any) -> Any:
    """Coerce attribute values to JSON-clean types (numpy scalars/arrays
    included); anything exotic degrades to str rather than poisoning an
    export."""
    if v is None or isinstance(v, (str, bool, int, float)):
        return v
    if isinstance(v, dict):
        return {str(k): jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [jsonable(x) for x in v]
    for conv in ("item", "tolist"):
        fn = getattr(v, conv, None)
        if callable(fn):
            try:
                return jsonable(fn())
            except Exception:
                pass
    return str(v)


def _tid() -> int:
    ident = threading.get_ident()
    t = _tids.get(ident)
    if t is None:
        t = _tids[ident] = len(_tids)
    return t


def record_span(name: str, path: str, start: float, duration: float,
                **attrs: Any) -> None:
    """Record a closed scope.  `start` is a time.perf_counter() stamp."""
    if not _enabled:
        return
    clean = {k: jsonable(v) for k, v in attrs.items() if v is not None}
    with _lock:
        _spans.append(
            Span(name, path, start - _epoch, duration, _tid(), clean)
        )


def event(name: str, **attrs: Any) -> None:
    """Record a one-shot event at the current time."""
    if not _enabled:
        return
    clean = {k: jsonable(v) for k, v in attrs.items() if v is not None}
    with _lock:
        _events.append(Event(name, time.perf_counter() - _epoch, clean))


def current_scope_path() -> str:
    """Dotted path of the open timer-scope stack ("" at top level) —
    progress series and compile-cost records align to the same paths
    the scope tree and the spans use."""
    try:
        from ..utils.timer import GLOBAL_TIMER

        return ".".join(n.name for n in GLOBAL_TIMER._stack[1:])
    except Exception:
        return ""


def record_progress(kind: str, series: Dict[str, list], iterations: int,
                    t0: float | None = None, **attrs: Any) -> None:
    """Record one per-iteration convergence series (progress.emit*)."""
    if not _enabled:
        return
    t1 = time.perf_counter() - _epoch
    clean = {k: jsonable(v) for k, v in attrs.items() if v is not None}
    entry = ProgressSeries(
        kind=kind,
        path=current_scope_path(),
        t0=(t0 - _epoch) if t0 is not None else t1,
        t1=t1,
        iterations=int(iterations),
        series={str(k): jsonable(v) for k, v in series.items()},
        attrs=clean,
    )
    with _lock:
        _progress.append(entry)


def progress_series(kind: str | None = None) -> List["ProgressSeries"]:
    """Recorded convergence series (named to avoid shadowing the
    `telemetry.progress` submodule)."""
    with _lock:
        out = list(_progress)
    if kind is not None:
        out = [p for p in out if p.kind == kind]
    return out


def annotate(**kv: Any) -> None:
    """Attach run-level key/values (preset, k, result metrics, ...) that
    the run report surfaces as its `run` / `result` sections."""
    if not _enabled:
        return
    clean = {k: jsonable(v) for k, v in kv.items()}
    with _lock:
        _run_info.update(clean)


def spans() -> List[Span]:
    with _lock:
        return list(_spans)


def events(name: str | None = None) -> List[Event]:
    with _lock:
        evs = list(_events)
    if name is not None:
        evs = [e for e in evs if e.name == name]
    return evs


def run_info() -> Dict[str, Any]:
    with _lock:
        return dict(_run_info)


def gate_verdict() -> Any:
    """The current stream's output-gate verdict as a tri-state:
    True/False when the gate checked this run's partition, None when it
    never ran (gate disabled, no partition in this stream).  The one
    place the `output_gate` annotation shape is interpreted — the
    serving layer and the dynamic repartition policy both read it."""
    gate = run_info().get("output_gate")
    if isinstance(gate, dict) and gate.get("checked"):
        return bool(gate.get("valid"))
    return None


def is_primary_process() -> bool:
    """True on process 0 (or without a backend).  File-writing exporters
    gate on this: on multi-host runs every process must still CALL them
    (their gathers are collective), but only one may write the path."""
    try:
        from ..utils.platform import process_index

        return process_index() == 0
    except Exception:
        return True


# --- shared CLI surface (cli.py + dcli.py) --------------------------------


def add_cli_args(parser) -> None:
    """The --trace-out / --report-json flags, shared by both CLIs."""
    parser.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="write a Chrome trace-event JSON of the run (open in "
        "Perfetto / chrome://tracing; one track per process); enables "
        "telemetry",
    )
    parser.add_argument(
        "--report-json", default=None, metavar="PATH",
        help="write the per-run JSON report (scope tree, result metrics, "
        "comm table, events; schema: "
        "kaminpar_tpu/telemetry/run_report.schema.json); enables telemetry",
    )
    parser.add_argument(
        "--diff-base", default=None, metavar="BASE.report.json",
        help="after the run, diff this run's --report-json against a "
        "baseline report (telemetry.diff) and exit non-zero past the "
        "regression thresholds; requires --report-json",
    )
    parser.add_argument(
        "--diff-wall-threshold", type=float, default=None, metavar="FRAC",
        help="fractional wall-time regression tolerated by --diff-base "
        "(default 0.10)",
    )
    parser.add_argument(
        "--diff-cut-threshold", type=float, default=None, metavar="FRAC",
        help="fractional edge-cut regression tolerated by --diff-base "
        "(default 0.10)",
    )


def enable_if_requested(args) -> None:
    """Enable telemetry when either CLI output flag was given."""
    if getattr(args, "trace_out", None) or getattr(args, "report_json", None):
        enable()


def export_cli_outputs(args, extra_run=None, quiet: bool = False) -> int:
    """Write the files requested via add_cli_args (no-op without flags).
    Collective on multi-host runs — call from every process.

    Returns a process exit code: 0 normally; with --diff-base, the
    telemetry.diff verdict against the baseline report (non-zero on a
    regression past the thresholds, primary process only)."""
    primary = is_primary_process()
    if getattr(args, "trace_out", None):
        from .chrome_trace import write_chrome_trace

        write_chrome_trace(args.trace_out)
        if not quiet and primary:
            print(f"TRACE written to {args.trace_out} (open in Perfetto)")
    if getattr(args, "report_json", None):
        from .report import write_run_report

        report = write_run_report(args.report_json, extra_run=extra_run)
        if not quiet and primary:
            print(f"REPORT written to {args.report_json}")
            print(
                "  triage: python -m kaminpar_tpu.telemetry.top "
                f"{args.report_json}"
            )
            if (report.get("quality") or {}).get("levels"):
                print(
                    "  quality: python -m kaminpar_tpu.telemetry.quality "
                    f"{args.report_json}"
                )
    if getattr(args, "diff_base", None):
        if not getattr(args, "report_json", None):
            import sys

            print("error: --diff-base requires --report-json",
                  file=sys.stderr)
            return 2
        if not primary:
            return 0
        from .diff import main as diff_main

        argv = [args.diff_base, args.report_json]
        if getattr(args, "diff_wall_threshold", None) is not None:
            argv += ["--wall-threshold", str(args.diff_wall_threshold)]
        if getattr(args, "diff_cut_threshold", None) is not None:
            argv += ["--cut-threshold", str(args.diff_cut_threshold)]
        if quiet:
            argv.append("--quiet")
        return diff_main(argv)
    return 0
