"""The benchmark's own ``jax.monitoring`` listeners.

jax reports one ``backend_compile_duration`` for every executable it
asks the backend for, whether XLA compiled it or the persistent cache
held it (``jax/_src/compiler.compile_or_get_cached`` runs inside that
timer), and one ``cache_hits`` event for each that the cache held.  So,
per phase of the run:

  executables       backend_compile_duration events
  cache_loads       cache_hits events
  backend_compiles  executables - cache_loads
  seconds           summed backend_compile_duration (compile or load)

``kaminpar_tpu/telemetry/compile_account.py`` listens to the same events
with program telemetry off too (since PR 34) and keeps one record an
executable; ``layer_metrics/_setup_account.py`` reads it for
``trace_lower_s``, ``first_request_s``, ``setup_unattributed_s`` and
``package_import_s``.  These listeners stay the benchmark's own count:
``compile_s`` and ``executables`` do not rest on the program's word, and
they alone know what the WINDOW compiled (``correct`` needs that)."""

from __future__ import annotations

_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


def _zero() -> dict:
    return {"executables": 0, "cache_loads": 0, "seconds": 0.0}


class CompileListener:
    """Counts per phase; ``phase`` is set by the run (``setup``, then
    ``window``).  jax offers no way to take a listener off again, so one
    is made per process."""

    def __init__(self) -> None:
        self.phase = "setup"
        self.counts: dict = {}

    def install(self) -> "CompileListener":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _entry(self) -> dict:
        return self.counts.setdefault(self.phase, _zero())

    def _on_duration(self, event: str, duration_secs: float, **kw) -> None:
        if event == _BACKEND:
            entry = self._entry()
            entry["executables"] += 1
            entry["seconds"] += float(duration_secs)

    def _on_event(self, event: str, **kw) -> None:
        if event == _HIT:
            self._entry()["cache_loads"] += 1

    def phase_counts(self, phase: str) -> dict:
        entry = dict(self.counts.get(phase, _zero()))
        entry["backend_compiles"] = entry["executables"] - entry["cache_loads"]
        return entry
