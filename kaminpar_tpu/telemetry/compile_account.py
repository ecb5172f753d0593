"""Compile-cost accounting: XLA trace/lower/compile time per phase, per
executable and per request, with telemetry on or off.

XLA compile time is the dominant small-graph cost (the rationale of
graphs/csr.py's shape floors), and with a filled persistent cache what
is left of it (tracing, lowering, loading) is most of a process's first
request.  jax meters every stage through `jax.monitoring`:

  scalar events (a stage is entered) and duration events (it is left)
    /jax/core/compile/jaxpr_trace_duration           (python tracing)
    /jax/core/compile/jaxpr_to_mlir_module_duration  (lowering)
    /jax/core/compile/backend_compile_duration       (XLA compiles, or
        the persistent cache retrieves and deserialises)
  count events
    /jax/compilation_cache/cache_hits | cache_misses (persistent cache)
    /jax/compilation_cache/compile_requests_use_cache

The listeners are installed once a process by the package's import
(`kaminpar_tpu/__init__.py`) and fire only when jax traces, lowers or
asks the backend for an executable, so a warm request pays nothing.
Three things are kept:

  * per-scope sums, attributed to the dotted timer-scope path open at
    dispatch (jit compiles run synchronously under the caller's scope,
    so the attribution matches the scope tree and the profiler spans,
    `kaminpar/<path>`).  `snapshot()` is the run report's `compile`
    section; `reset()` clears it, once a request (`telemetry.reset`).
  * one record per executable asked for (`records()`), for the life of
    the process: `fun_name`, `scope`, `request` (the ordinal of the
    request open at dispatch, 0 outside any), `trace_s`, `lower_s`,
    `backend_s` (None where no backend event followed: `eval_shape`, an
    in-memory hit after a retrace), `cache_hit`, `inlined_traces`,
    `nested` and `end`, the `time.perf_counter()` stamp of its last
    event (the backend event's end once closed).
  * requests (`request()`, entered by `utils/timer.request_span`):
    ordinal, start and end on `time.perf_counter()`, the first one and
    the last 64.

Pairing.  The enter and leave events of one thread form a stack.  A
trace event opens a record at its depth; the next lower and backend
events at that depth whose `fun_name` is `jit(<the trace's>)` join it,
and the backend event closes it.  A lower or backend event that finds
no open record (the trace was cached) opens one of its own; one that
finds an open record of another name counts as unplaced
(`totals.unplaced_s`) and also opens its own.  Stages entered inside
another stage are nested: a nested record with nothing but a trace is a
jitted helper inlined into its parent (`jnp.where` under a kernel); its
seconds stay in the parent's stage, which counts it in
`inlined_traces`, and it is not kept.  A nested record that did reach
the backend is kept, marked `nested`, and its seconds are taken out of
the parent's stage.  So no second is counted twice, in the records or
in the per-scope sums.

Caveats (stamped on the section): an in-process executable-cache hit
registers ~nothing, so a warm request showing zero seconds is the cache
working; persistent hit/miss counters only move when jax's compilation
cache is configured (utils/platform.configure_compile_cache, called by
every entry point).  `package_import_s` is the package's own top-level
import; jax's import and the runtime's start precede it, and modules
the package imports lazily later are not in it.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from statistics import median
from typing import Any, Dict, List, Optional

CAVEAT = (
    "durations are metered via jax.monitoring at dispatch time and "
    "attributed to the open timer scope; in-process executable-cache "
    "hits register no compile time, and persistent-cache hit/miss "
    "counters only move when a persistent compilation cache directory "
    "is configured"
)

_DURATION_KEYS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_COUNT_KEYS = {
    _CACHE_HIT: "persistent_cache_hits",
    "/jax/compilation_cache/cache_misses": "persistent_cache_misses",
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
}

#: the roll-up of PERF.md section 3 (perfbench/harness/phase_reduce.py
#: spells the same names; the benchmark does not import the program),
#: innermost scope first; everything else is the driver's
_LAYER_OF_SCOPE = {
    "jet": "refinement", "lp-refinement": "refinement",
    "overload-balancer": "refinement", "underload-balancer": "refinement",
    "coarsening": "coarsening",
    "extend-partition": "extend", "extend-pull": "extend",
    "initial-partitioning": "initial partitioning",
}
OUTSIDE = "(outside scopes)"
_KEEP_RECORDS = 4096  # the first so many and the last so many
_KEEP_REQUESTS = 64  # the first request and the last so many
_TOP = 10

_lock = threading.Lock()


class _Stage:
    """One stage (trace, lower or backend) a thread has entered."""

    __slots__ = ("key", "children", "child_s", "cache_hit")

    def __init__(self, key: str) -> None:
        self.key = key
        self.children: List[dict] = []  # records opened directly inside
        self.child_s = 0.0  # seconds of the stages entered directly inside
        self.cache_hit = False


class _Thread(threading.local):
    """One thread's open stages, and per depth the record still waiting
    for its lower or backend event."""

    def __init__(self) -> None:
        self.stages: List[_Stage] = []
        self.open: List[Optional[dict]] = []


_local = _Thread()
_installed = False
# phase path -> {trace_s, lower_s, compile_s, compiles}; since reset()
_phases: Dict[str, Dict[str, float]] = {}
_totals: Dict[str, float] = {}
# for the life of the process (forget() clears)
_first_records: List[dict] = []
_last_records: deque = deque(maxlen=_KEEP_RECORDS)
_counts = {"dropped_records": 0, "unplaced_events": 0, "unplaced_s": 0.0}
_requests = {"ordinal": 0, "depth": 0, "first": None,
             "last": deque(maxlen=_KEEP_REQUESTS)}
_package_import_s: Optional[float] = None


def layer_of(scope: str) -> str:
    for name in reversed(scope.split(".")):
        layer = _LAYER_OF_SCOPE.get(name)
        if layer is not None:
            return layer
    return "driver"


def _bare(fun_name: str) -> str:
    """`jit(f)` (lowering, backend) -> `f` (tracing)."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def _keep(record: dict) -> None:
    """Caller holds the lock."""
    if len(_first_records) < _KEEP_RECORDS:
        _first_records.append(record)
        return
    if len(_last_records) == _KEEP_RECORDS:
        _counts["dropped_records"] += 1
    _last_records.append(record)


def _on_scalar(event: str, value: float, **kw: Any) -> None:
    key = _DURATION_KEYS.get(event)
    if key is not None:
        _local.stages.append(_Stage(key))


def _new_record(fun_name: str, scope: str, end: float) -> dict:
    return {"fun_name": fun_name, "scope": scope,
            "request": _requests["ordinal"] if _requests["depth"] else 0,
            "trace_s": 0.0, "lower_s": 0.0, "backend_s": None,
            "cache_hit": None, "inlined_traces": 0, "nested": False,
            "end": end}


def _on_duration(event: str, duration_secs: float, **kw: Any) -> None:
    key = _DURATION_KEYS.get(event)
    if key is None:
        return
    end = time.perf_counter()
    seconds = float(duration_secs)
    name = _bare(str(kw.get("fun_name", "?")))
    stages, open_ = _local.stages, _local.open
    unplaced = not (stages and stages[-1].key == key)
    if unplaced:  # left without having been entered: start over
        del stages[:]
        stage = _Stage(key)
    else:
        stage = stages.pop()
    depth = len(stages)
    if depth:
        stages[-1].child_s += seconds
    del open_[depth + 1:]
    open_.extend([None] * (depth + 1 - len(open_)))

    # what was opened inside this stage: helpers that were only traced
    # are part of it, executables that reached the backend are not
    own, inlined, nested = seconds, 0, []
    for child in stage.children:
        if child["lower_s"] == 0.0 and child["backend_s"] is None:
            inlined += 1 + child["inlined_traces"]
        else:
            child["nested"] = True
            own -= (child["trace_s"] + child["lower_s"]
                    + (child["backend_s"] or 0.0))
            nested.append(child)

    from . import current_scope_path

    path = current_scope_path() or OUTSIDE
    record = None if key == "trace_s" else open_[depth]
    if record is not None and (record["fun_name"] != name
                               or record["backend_s"] is not None
                               or (key == "lower_s" and record["lower_s"])):
        record, unplaced = None, True
    opened = record is None
    if opened:
        record = _new_record(name, path, end)
    record["inlined_traces"] += inlined
    record["end"] = end
    if key == "compile_s":
        record["backend_s"] = own
        record["cache_hit"] = stage.cache_hit
        open_[depth] = None
    else:
        record[key] += own
        open_[depth] = record

    with _lock:
        entry = _phases.setdefault(
            path,
            {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0, "compiles": 0},
        )
        entry[key] += seconds - stage.child_s
        if key == "compile_s":
            entry["compiles"] += 1
        _totals[key] = _totals.get(key, 0.0) + seconds - stage.child_s
        if unplaced:
            _counts["unplaced_events"] += 1
            _counts["unplaced_s"] += seconds
        for child in nested:
            _keep(child)
        if opened and not depth:
            _keep(record)
    if opened and depth:
        stages[-1].children.append(record)


def _on_event(event: str, **kw: Any) -> None:
    key = _COUNT_KEYS.get(event)
    if key is None:
        return
    stages = _local.stages
    if event == _CACHE_HIT and stages and stages[-1].key == "compile_s":
        stages[-1].cache_hit = True
    with _lock:
        _totals[key] = _totals.get(key, 0) + 1


def install() -> None:
    """Register the jax.monitoring listeners (idempotent; the package's
    import calls it, `telemetry.enable()` again)."""
    global _installed
    if _installed:
        return
    from jax import monitoring

    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _installed = True


def reset() -> None:
    """Clear the per-scope sums (`snapshot()`); records and requests
    stay, they are the process's."""
    with _lock:
        _phases.clear()
        _totals.clear()


def forget() -> None:
    """Clear records and requests and start the ordinals over (tests,
    and tools that measure one set-up)."""
    with _lock:
        _first_records.clear()
        _last_records.clear()
        _counts.update(dropped_records=0, unplaced_events=0, unplaced_s=0.0)
        _requests.update(ordinal=0, first=None)
        _requests["last"].clear()


def note_package_import(seconds: float) -> None:
    global _package_import_s
    _package_import_s = float(seconds)


@contextmanager
def request():
    """One request on the program's clock.  A request entered inside
    another (the shm pipeline under the distributed driver) is part of
    the outer one."""
    with _lock:
        outermost = _requests["depth"] == 0
        _requests["depth"] += 1
        if outermost:
            _requests["ordinal"] += 1
        ordinal = _requests["ordinal"]
    start = time.perf_counter()
    try:
        yield ordinal
    finally:
        end = time.perf_counter()
        with _lock:
            _requests["depth"] -= 1
            if outermost:
                entry = {"ordinal": ordinal, "start": start, "end": end,
                         "wall_s": end - start}
                if _requests["first"] is None:
                    _requests["first"] = entry
                else:
                    _requests["last"].append(entry)


def open_request() -> int:
    """The ordinal of the request open now, 0 outside any (the tag other
    process-level accounts give what they record)."""
    with _lock:
        return _requests["ordinal"] if _requests["depth"] else 0


def requests_begun() -> int:
    """How many requests the process has entered (the last ordinal)."""
    with _lock:
        return _requests["ordinal"]


def snapshot() -> dict:
    """The run report's `compile` section."""
    with _lock:
        phases = {
            p: {
                "trace_s": round(e["trace_s"], 6),
                "lower_s": round(e["lower_s"], 6),
                "compile_s": round(e["compile_s"], 6),
                "compiles": int(e["compiles"]),
            }
            for p, e in _phases.items()
        }
        totals: Dict[str, Any] = {
            "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "persistent_cache_hits": 0, "persistent_cache_misses": 0,
            "cache_requests": 0,
        }
        for k, v in _totals.items():
            totals[k] = round(v, 6) if isinstance(v, float) else int(v)
    totals["compiles"] = sum(e["compiles"] for e in phases.values())
    return {"caveat": CAVEAT, "totals": totals, "phases": phases}


def records() -> List[dict]:
    """Copies of the kept records, oldest first."""
    with _lock:
        return [dict(r) for r in _first_records] + [
            dict(r) for r in _last_records]


def _sums(recs: List[dict]) -> dict:
    closed = [r for r in recs if r["backend_s"] is not None]
    return {"records": len(recs), "closed": len(closed),
            "cache_hits": sum(bool(r["cache_hit"]) for r in closed),
            "inlined_traces": sum(r["inlined_traces"] for r in recs),
            "trace_s": sum(r["trace_s"] for r in recs),
            "lower_s": sum(r["lower_s"] for r in recs),
            "backend_s": sum(r["backend_s"] for r in closed)}


def summary() -> dict:
    """The process's set-up, from the records and the requests: totals;
    sums by layer (`layer_of`), by request ordinal, through the end of
    the first request, and of the first request alone beside its wall;
    the later requests' walls; the costliest records."""
    recs = records()
    with _lock:
        counts = dict(_counts)
        first = _requests["first"] and dict(_requests["first"])
        later = [dict(r) for r in _requests["last"]]
        ordinal = _requests["ordinal"]
    totals = _sums(recs)
    totals.update(counts, nested=sum(r["nested"] for r in recs))
    layers: Dict[str, list] = {}
    by_request: Dict[int, list] = {}
    for r in recs:
        layers.setdefault(layer_of(r["scope"]), []).append(r)
        by_request.setdefault(r["request"], []).append(r)
    first_end = first["end"] if first else float("inf")
    if first:
        first.update(_sums(by_request.get(first["ordinal"], [])))
    walls = [r["wall_s"] for r in later]
    closed = [r for r in recs if r["backend_s"] is not None]
    return {
        "package_import_s": _package_import_s,
        "totals": totals,
        "layers": {k: _sums(v) for k, v in sorted(layers.items())},
        "by_request": {k: _sums(v) for k, v in sorted(by_request.items())},
        "through_first_request": _sums(
            [r for r in recs if r["end"] <= first_end]),
        "requests": {"count": ordinal, "first": first,
                     "later": len(walls),
                     "later_median_wall_s": median(walls) if walls else None},
        "top_backend": sorted(
            closed, key=lambda r: -r["backend_s"])[:_TOP],
        "top_trace_lower": sorted(
            recs, key=lambda r: -(r["trace_s"] + r["lower_s"]))[:_TOP],
    }


def _record_line(r: dict) -> str:
    backend = ("      -" if r["backend_s"] is None
               else f"{r['backend_s']:7.3f}")
    how = {None: "", True: " loaded", False: " compiled"}[r["cache_hit"]]
    return (f"    trace {r['trace_s']:6.3f} lower {r['lower_s']:6.3f} "
            f"backend {backend}{how}  {r['fun_name']}  [{r['scope']}] "
            f"request {r['request']}")


def render() -> str:
    """Human-readable: the per-scope sums since `reset()` (compile vs
    execute, docs/performance.md), then the process's set-up by layer,
    by request and by executable."""
    snap = snapshot()
    t = snap["totals"]
    lines = [
        f"compile totals: trace={t['trace_s']:.3f}s "
        f"lower={t['lower_s']:.3f}s compile={t['compile_s']:.3f}s "
        f"({t['compiles']} backend compiles; persistent cache "
        f"{t['persistent_cache_hits']} hit / "
        f"{t['persistent_cache_misses']} miss)",
    ]
    for path, e in sorted(
        snap["phases"].items(), key=lambda kv: -kv[1]["compile_s"]
    ):
        lines.append(
            f"  {path}: trace={e['trace_s']:.3f}s lower={e['lower_s']:.3f}s "
            f"compile={e['compile_s']:.3f}s ({e['compiles']}x)"
        )
    s = summary()
    t = s["totals"]
    imported = s["package_import_s"]
    lines.append(
        f"set-up of this process: {t['records']} records, {t['closed']} "
        f"reached the backend ({t['cache_hits']} loaded from the persistent "
        f"cache), {t['inlined_traces']} inlined traces, {t['nested']} "
        f"nested; trace={t['trace_s']:.3f}s lower={t['lower_s']:.3f}s "
        f"backend={t['backend_s']:.3f}s; unplaced {t['unplaced_events']} "
        f"events {t['unplaced_s']:.3f}s; {t['dropped_records']} records "
        "dropped; package import "
        + ("not stamped" if imported is None else f"{imported:.3f}s"))

    def row(label, e):
        lines.append(
            f"    {label}: {e['records']} records, {e['closed']} backend "
            f"({e['cache_hits']} loaded), trace={e['trace_s']:.3f}s "
            f"lower={e['lower_s']:.3f}s backend={e['backend_s']:.3f}s")

    lines.append("  by layer:")
    for layer, e in s["layers"].items():
        row(layer, e)
    lines.append("  by request (0 is outside any):")
    for ordinal, e in s["by_request"].items():
        row(f"request {ordinal}", e)
    first = s["requests"]["first"]
    if first:
        later = s["requests"]["later_median_wall_s"]
        lines.append(
            f"  request {first['ordinal']}: wall {first['wall_s']:.3f}s"
            + ("" if later is None else
               f"; the {s['requests']['later']} later ones: median "
               f"{later:.3f}s"))
    lines.append("  costliest by backend seconds:")
    lines.extend(_record_line(r) for r in s["top_backend"])
    lines.append("  costliest by trace and lower seconds:")
    lines.extend(_record_line(r) for r in s["top_trace_lower"])
    return "\n".join(lines)
