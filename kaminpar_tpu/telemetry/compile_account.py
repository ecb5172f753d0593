"""Compile-cost accounting: XLA trace/lower/compile time per phase.

XLA compile time is the dominant small-graph cost (the rationale of
graphs/csr.py's shape floors), yet it was invisible in the run report
— a "slow run" could not be split into compile vs execute.  jax already
meters every stage through `jax.monitoring`:

  duration events
    /jax/core/compile/jaxpr_trace_duration           (python tracing)
    /jax/core/compile/jaxpr_to_mlir_module_duration  (lowering)
    /jax/core/compile/backend_compile_duration       (XLA backend compile)
    /jax/compilation_cache/compile_time_saved_sec    (persistent-cache hit)
    /jax/compilation_cache/cache_retrieval_time_sec
  count events
    /jax/compilation_cache/cache_hits | cache_misses (persistent cache)
    /jax/compilation_cache/compile_requests_use_cache

This module registers listeners (once, idempotent) and attributes every
duration to the dotted timer-scope path open at dispatch time — jit
compiles run synchronously under the caller's scope, so the attribution
matches the scope tree and the spans.  The aggregate surfaces as the run
report's `compile` section and splits wall time into compile vs execute
per phase (docs/performance.md triage workflow).

Caveats (stamped on the section): an executable-cache hit (in-process
jit cache or warm persistent cache) registers ~nothing, so a warm run
showing zero compile seconds is the cache working, not a meter failure;
persistent hit/miss counters only move when jax's compilation cache is
configured (utils/platform.configure_compile_cache, called by every
entry point).
"""

from __future__ import annotations

import threading
from typing import Any, Dict

CAVEAT = (
    "durations are metered via jax.monitoring at dispatch time and "
    "attributed to the open timer scope; executable-cache hits register "
    "no compile time, and persistent-cache hit/miss counters only move "
    "when a persistent compilation cache directory is configured"
)

_DURATION_KEYS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_TOTAL_ONLY_DURATION_KEYS = {
    "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
_COUNT_KEYS = {
    "/jax/compilation_cache/cache_hits": "persistent_cache_hits",
    "/jax/compilation_cache/cache_misses": "persistent_cache_misses",
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
}

_lock = threading.Lock()
_installed = False
# phase path -> {trace_s, lower_s, compile_s, compiles}
_phases: Dict[str, Dict[str, float]] = {}
_totals: Dict[str, float] = {}


def _on_duration(event: str, duration_secs: float, **kw: Any) -> None:
    from . import enabled as _telemetry_enabled

    if not _telemetry_enabled():
        return
    key = _DURATION_KEYS.get(event)
    if key is not None:
        from . import current_scope_path

        path = current_scope_path() or "(outside scopes)"
        with _lock:
            entry = _phases.setdefault(
                path,
                {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                 "compiles": 0},
            )
            entry[key] += float(duration_secs)
            if key == "compile_s":
                entry["compiles"] += 1
            _totals[key] = _totals.get(key, 0.0) + float(duration_secs)
        return
    key = _TOTAL_ONLY_DURATION_KEYS.get(event)
    if key is not None:
        with _lock:
            _totals[key] = _totals.get(key, 0.0) + float(duration_secs)


def _on_event(event: str, **kw: Any) -> None:
    from . import enabled as _telemetry_enabled

    if not _telemetry_enabled():
        return
    key = _COUNT_KEYS.get(event)
    if key is not None:
        with _lock:
            _totals[key] = _totals.get(key, 0) + 1


def install() -> None:
    """Register the jax.monitoring listeners (idempotent; the callbacks
    no-op while telemetry is disabled, so installation is free)."""
    global _installed
    if _installed:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _installed = True


def reset() -> None:
    with _lock:
        _phases.clear()
        _totals.clear()


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


def render() -> str:
    """Human-readable compile-vs-execute table (docs/performance.md)."""
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
    return "\n".join(lines)
