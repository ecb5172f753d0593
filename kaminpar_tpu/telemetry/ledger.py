"""Execution ledger: launch-honest rooflines, a host<->device transfer
ledger, and donation audits.

The perf observatory (telemetry/perf.py) captures XLA cost analysis once
per *backend compile*, so a scope that re-launches one compiled program
hundreds of times (every LP round, every level) under-counts bytes and
FLOPs by exactly its launch count — the utilization figures ROADMAP
item 2 gates on were lower bounds, not measurements.  This module is the
execution half of that observatory, three legs:

  * **launch ledger** — ``install()`` wraps the compiled-executable call
    boundary (``pxla.ExecuteReplicated.__call__``, the same
    dispatch-time host-side attribution contract as ``compile_account``
    and ``perf.install``) and counts executions per (scope path,
    executable).  jax's C++ pjit fastpath normally dispatches warm calls
    without touching Python, so while the ledger is armed the module
    also gates ``jax._src.pjit._get_fastpath_data`` to return ``None``:
    every dispatch then routes through the Python path where the wrapper
    can see it.  Tracing/compile caches are untouched (verified: launch
    counting adds zero recompiles); the only cost is Python dispatch
    overhead, paid exclusively while telemetry is on.  Per-launch costs
    join against the per-executable cost registry that
    ``perf._record_executable`` forwards here; a launch whose
    executable's cost was never captured (e.g. a persistent-cache warm
    start that skipped ``backend_compile``) is counted as *uncosted* and
    poisons the scope's ``honest`` stamp instead of silently
    under-reporting.  Distinct executables are distinct shape buckets
    (the jit cache keys on padded shapes — caching.bucket_key), so the
    per-executable launch counts are the per-bucket counts.
  * **transfer ledger** — ``transfer(direction, nbytes, kind)`` is the
    one hook every host-boundary chokepoint calls (device CSR upload,
    checkpoint spill/reload, chunkstore upload/pull, progress/stat
    pulls, dist gathers).  Aggregated per (scope, direction, kind) and
    rolled up per phase into the schema-v13 ``ledger`` report section;
    mirrored live into ``kmp_xfer_*`` fleet-observatory counters and a
    capped ``ledger-transfer`` event stream that chrome_trace renders as
    cumulative counter tracks.
  * **donation audit** — ``donation_begin(arrays)`` /
    ``donation_end(token)`` bracket a donated-buffer call (LP round
    carry, hierarchy level handoff) and verify the donated inputs were
    actually aliased: primary signal is the runtime ``is_deleted()``
    flag on each donated array (a donated buffer is invalidated by the
    runtime iff the aliasing was honored), cross-checked against the
    executable's ``input_output_alias`` metadata recorded at compile
    time, with a measured live-bytes-delta fallback when the flag is
    unavailable.  Reported as ``donation {requested, honored,
    bytes_saved}`` per scope.

Standing dormancy contract (pinned by tests/test_ledger.py): the kill
switch is ``KAMINPAR_TPU_LEDGER=0``; every hook is host-side (dispatch
boundaries, host pulls, compile results) so the traced jaxprs are
bitwise identical whether the ledger is on, off, or telemetry is
disabled entirely.  Disabled, every entry point is one bool check.

Arm telemetry BEFORE the first dispatch of the executables you want
counted: once a warm call has been served by the C++ fastpath cache
(ledger off at that moment), jax keeps dispatching that executable from
C++ and its launches stay invisible — the same cold-run methodology
bench.py already follows.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Tuple

ENV_VAR = "KAMINPAR_TPU_LEDGER"

#: Per-scope executable-name launch detail kept for triage; scopes and
#: transfer kinds are O(scope tree) / O(chokepoints), never per-launch.
MAX_EXECUTABLES_PER_SCOPE = 32
#: Cost registry bound: id(executable) -> cost.  Executables live as
#: long as the jit caches that own them, so id reuse is rare; a full
#: registry drops new entries (their launches then read as uncosted —
#: visible, not wrong).
MAX_EXECUTABLE_COSTS = 4096
#: Cap on ledger-transfer telemetry events (the chrome-trace counter
#: track); aggregation continues past the cap, only the event stream
#: stops growing.
MAX_TRANSFER_EVENTS = 512

_lock = threading.Lock()
_installed = False
# id(executable) -> {"flops","bytes","name","donated_params"}
_exe_costs: Dict[int, Dict[str, Any]] = {}
# dotted scope path -> {"launches","uncosted","bytes","flops",
#                       "executables": {name: count}}
_launches: Dict[str, Dict[str, Any]] = {}
# (dotted scope path, direction, kind) -> {"bytes","count"}
_transfers: Dict[Tuple[str, str, str], Dict[str, int]] = {}
# dotted scope path -> {"requested","honored","requested_bytes",
#                       "bytes_saved"}
_donation: Dict[str, Dict[str, int]] = {}
_transfer_events = 0
_xfer_totals = {"h2d": 0, "d2h": 0}


def enabled() -> bool:
    """True iff telemetry is on and KAMINPAR_TPU_LEDGER is not 0 — the
    one gate every hook checks before doing any work."""
    if os.environ.get(ENV_VAR, "") == "0":
        return False
    from . import enabled as _telemetry_enabled

    return _telemetry_enabled()


def reset() -> None:
    """Clear launch/transfer/donation state.  The executable cost
    registry survives: jit caches outlive a telemetry reset, and a warm
    executable whose compile predates the reset must still join."""
    global _transfer_events
    with _lock:
        _launches.clear()
        _transfers.clear()
        _donation.clear()
        _transfer_events = 0
        _xfer_totals["h2d"] = 0
        _xfer_totals["d2h"] = 0


# ---------------------------------------------------------------------------
# launch ledger
# ---------------------------------------------------------------------------


def install() -> None:
    """Wrap jax 0.9.0's compiled-executable call boundary,
    ``pxla.ExecuteReplicated.__call__``, and gate the C++ pjit fastpath
    (``jax._src.pjit._get_fastpath_data``) while the ledger is armed
    (idempotent; both wrappers pass straight through while the ledger
    is disabled, so installation is free).  Both names are private to
    jax: an installation that moved either raises here, at
    ``telemetry.enable()``, instead of reporting zero launches."""
    global _installed
    if _installed:
        return
    from jax._src import pjit as _pjit
    from jax._src.interpreters import pxla

    orig_call = pxla.ExecuteReplicated.__call__

    def _wrapped_call(self, *args: Any, **kwargs: Any):
        if enabled():
            _record_launch(self.xla_executable)
        return orig_call(self, *args, **kwargs)

    pxla.ExecuteReplicated.__call__ = _wrapped_call

    # Warm pjit calls are dispatched from C++ and never reach the
    # Python wrapper above; returning None here keeps the fastpath
    # uncached so every dispatch stays countable while the ledger is
    # armed.  Disabled, the original fastpath is untouched.
    orig_fastpath = _pjit._get_fastpath_data

    def _gated_fastpath(*args: Any, **kwargs: Any):
        if enabled():
            return None
        return orig_fastpath(*args, **kwargs)

    _pjit._get_fastpath_data = _gated_fastpath
    _installed = True


def register_executable(exe: Any, flops: float, nbytes: float,
                        name: str = "") -> None:
    """Record one freshly compiled executable's cost so later launches
    can join it (called by perf._record_executable at the compile
    boundary).  Also parses the executable's input/output alias
    metadata — the compile-time half of the donation audit."""
    donated = _parse_donated_params(exe)
    with _lock:
        if len(_exe_costs) >= MAX_EXECUTABLE_COSTS:
            return
        _exe_costs[id(exe)] = {
            "flops": float(flops),
            "bytes": float(nbytes),
            "name": str(name),
            "donated_params": donated,
        }


def _parse_donated_params(exe: Any) -> int:
    """Count aliased parameters from the HloModule header's
    ``input_output_alias={...}`` map (empty/absent -> 0)."""
    try:
        text = exe.hlo_modules()[0].to_string()
        header = text[: text.index("\n")] if "\n" in text else text
        marker = "input_output_alias={"
        i = header.find(marker)
        if i < 0:
            return 0
        body = header[i + len(marker): header.index("}", i)]
        return body.count(":") or (1 if body.strip() else 0)
    except Exception:
        return 0


def _record_launch(exe: Any) -> None:
    from . import current_scope_path

    path = current_scope_path() or "(outside scopes)"
    key = id(exe)
    with _lock:
        cost = _exe_costs.get(key)
        e = _launches.setdefault(
            path,
            {"launches": 0, "uncosted": 0, "bytes": 0.0, "flops": 0.0,
             "executables": {}},
        )
        e["launches"] += 1
        if cost is None:
            e["uncosted"] += 1
            exe_name = "(uncosted)"
        else:
            e["bytes"] += cost["bytes"]
            e["flops"] += cost["flops"]
            exe_name = cost["name"] or "(unnamed)"
        names = e["executables"]
        if exe_name in names or len(names) < MAX_EXECUTABLES_PER_SCOPE:
            names[exe_name] = names.get(exe_name, 0) + 1
    try:
        from . import metrics

        metrics.inc(
            "kmp_launches_total",
            "compiled-executable launches recorded by the execution "
            "ledger",
            1,
        )
    except Exception:
        pass


def launch_totals() -> Dict[str, Dict[str, Any]]:
    """Per-scope launch aggregates for the perf.snapshot() roofline
    join: {path: {launches, uncosted, bytes, flops}}."""
    with _lock:
        return {
            path: {k: e[k] for k in ("launches", "uncosted", "bytes",
                                     "flops")}
            for path, e in _launches.items()
        }


# ---------------------------------------------------------------------------
# transfer ledger
# ---------------------------------------------------------------------------


def transfer(direction: str, nbytes: Any, kind: str = "") -> None:
    """Record one host<->device transfer at a boundary chokepoint.

    ``direction`` is ``"h2d"`` or ``"d2h"``; ``nbytes`` the payload
    size; ``kind`` a short chokepoint tag (``csr-upload``,
    ``checkpoint-spill``, ``stat-pull``, ...).  Host-side aggregation
    keyed by the open timer scope — call from the factored chokepoint
    helpers, never from inside a driver span block (tpulint R1's hook
    shape, pinned by tests/lint_fixtures/r1_ledger_*)."""
    if not enabled():
        return
    try:
        nb = int(nbytes)
    except (TypeError, ValueError):
        return
    if nb <= 0 or direction not in ("h2d", "d2h"):
        return
    from . import current_scope_path

    path = current_scope_path() or "(outside scopes)"
    global _transfer_events
    with _lock:
        e = _transfers.setdefault(
            (path, direction, kind or "-"), {"bytes": 0, "count": 0}
        )
        e["bytes"] += nb
        e["count"] += 1
        _xfer_totals[direction] += nb
        emit_event = _transfer_events < MAX_TRANSFER_EVENTS
        if emit_event:
            _transfer_events += 1
        h2d_total, d2h_total = _xfer_totals["h2d"], _xfer_totals["d2h"]
    try:
        from . import metrics

        metrics.inc(
            f"kmp_xfer_{direction}_bytes_total",
            "host<->device transfer bytes by direction and chokepoint "
            "kind (execution ledger)",
            nb, kind=kind or "-",
        )
        metrics.inc(
            f"kmp_xfer_{direction}_total",
            "host<->device transfers by direction and chokepoint kind "
            "(execution ledger)",
            1, kind=kind or "-",
        )
    except Exception:
        pass
    if emit_event:
        from . import event

        # cumulative totals ride each event so chrome_trace can render
        # a monotone counter track without re-aggregating
        event(
            "ledger-transfer", direction=direction, kind=kind or "-",
            bytes=nb, h2d_total=h2d_total, d2h_total=d2h_total,
        )


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------


def donation_begin(arrays: Any, kind: str = "") -> Optional[dict]:
    """Open one donated-buffer audit: capture the donated inputs and
    their sizes BEFORE the donating call (the call rebinds the carry,
    so the caller's references are gone afterwards).  Returns an opaque
    token for donation_end, or None while the ledger is off."""
    if not enabled():
        return None
    arrs = list(arrays)
    sizes = []
    for a in arrs:
        try:
            sizes.append(int(a.nbytes))
        except Exception:
            sizes.append(0)
    from . import current_scope_path

    token: Dict[str, Any] = {
        "arrays": arrs,
        "sizes": sizes,
        "kind": kind,
        "path": current_scope_path() or "(outside scopes)",
        "live0": None,
    }
    try:
        from ..utils import heap_profiler

        token["live0"] = int(heap_profiler.live_device_bytes())
    except Exception:
        pass
    return token


def donation_end(token: Optional[dict]) -> Optional[dict]:
    """Close a donation audit after the donating call returned: a
    donated input whose buffer the runtime invalidated
    (``is_deleted()``) was aliased — the donation was honored and its
    bytes were saved.  Falls back to the live-bytes delta when the flag
    is unavailable.  Aggregates per scope; returns this audit's
    {requested, honored, bytes_saved} (None while off)."""
    if token is None:
        return None
    requested = len(token["arrays"])
    requested_bytes = sum(token["sizes"])
    honored = 0
    bytes_saved = 0
    flag_failed = False
    for arr, nb in zip(token["arrays"], token["sizes"]):
        try:
            if arr.is_deleted():
                honored += 1
                bytes_saved += nb
        except Exception:
            flag_failed = True
    if flag_failed and honored == 0 and token.get("live0") is not None:
        # fallback: if live device bytes did not grow by the donated
        # footprint, the buffers were reused (coarse — stamped as the
        # whole audit honored or not, never per array)
        try:
            from ..utils import heap_profiler

            grown = int(heap_profiler.live_device_bytes()) - token["live0"]
            if grown <= requested_bytes // 2:
                honored = requested
                bytes_saved = requested_bytes
        except Exception:
            pass
    path = token["path"]
    with _lock:
        e = _donation.setdefault(
            path,
            {"requested": 0, "honored": 0, "requested_bytes": 0,
             "bytes_saved": 0},
        )
        e["requested"] += requested
        e["honored"] += honored
        e["requested_bytes"] += requested_bytes
        e["bytes_saved"] += bytes_saved
    return {"requested": requested, "honored": honored,
            "bytes_saved": bytes_saved}


# ---------------------------------------------------------------------------
# supervised-worker marshal
# ---------------------------------------------------------------------------


def marshal_summary() -> Optional[dict]:
    """The worker-side half of the supervised marshal: a small,
    pickle/JSON-safe headline of this process's ledger (launch totals +
    transfer totals), shipped back on the worker's result reply.  None
    while the ledger is off."""
    if not enabled():
        return None
    with _lock:
        return {
            "launches": sum(e["launches"] for e in _launches.values()),
            "uncosted_launches": sum(
                e["uncosted"] for e in _launches.values()
            ),
            "h2d_bytes": int(_xfer_totals["h2d"]),
            "d2h_bytes": int(_xfer_totals["d2h"]),
        }


def absorb(summary: Optional[dict], kind: str = "worker") -> None:
    """The parent-side half: fold a worker's marshalled transfer totals
    into THIS process's ledger under the current scope (the serving
    layer calls this after a supervised request returns, so supervised
    runs keep their h2d/d2h accounting — the bytes moved in the worker
    on the request's behalf).  Launch counts are NOT absorbed: they
    cannot be joined with per-scope costs across the process boundary,
    and a fake uncosted entry would poison the parent's honest stamps
    for work the worker accounted honestly on its own."""
    if not summary or not enabled():
        return
    for direction in ("h2d", "d2h"):
        transfer(direction, summary.get(f"{direction}_bytes", 0),
                 kind=kind)


# ---------------------------------------------------------------------------
# snapshot: the run report's `ledger` section
# ---------------------------------------------------------------------------


def _phase_of(path: str) -> str:
    """Phase key for the per-phase transfer rollup: the first two
    dotted segments (``partitioning.coarsening``), matching the
    granularity bench.py's phase walls report at."""
    if not path or path == "(outside scopes)":
        return "(outside scopes)"
    return ".".join(path.split(".")[:2])


def snapshot() -> dict:
    """Assemble the schema-v13 ``ledger`` report section."""
    on = enabled()
    with _lock:
        launches = {
            p: {
                "launches": int(e["launches"]),
                "uncosted_launches": int(e["uncosted"]),
                "bytes": round(float(e["bytes"]), 1),
                "flops": round(float(e["flops"]), 1),
                "executables": dict(e["executables"]),
            }
            for p, e in _launches.items()
        }
        xfer_items = [(k, dict(e)) for k, e in _transfers.items()]
        donation = {p: dict(e) for p, e in _donation.items()}
        costed_exes = len(_exe_costs)

    rows: List[dict] = []
    by_phase: Dict[str, Dict[str, int]] = {}
    totals = {"h2d_bytes": 0, "d2h_bytes": 0, "h2d_count": 0,
              "d2h_count": 0}
    for (path, direction, kind), e in xfer_items:
        rows.append({
            "scope": path, "direction": direction, "kind": kind,
            "bytes": int(e["bytes"]), "count": int(e["count"]),
        })
        ph = by_phase.setdefault(
            _phase_of(path),
            {"h2d_bytes": 0, "d2h_bytes": 0, "h2d_count": 0,
             "d2h_count": 0},
        )
        ph[f"{direction}_bytes"] += int(e["bytes"])
        ph[f"{direction}_count"] += int(e["count"])
        totals[f"{direction}_bytes"] += int(e["bytes"])
        totals[f"{direction}_count"] += int(e["count"])
    rows.sort(key=lambda r: (-r["bytes"], r["scope"], r["kind"]))

    return {
        "enabled": on,
        "launches": launches,
        "totals": {
            "launches": sum(e["launches"] for e in launches.values()),
            "uncosted_launches": sum(
                e["uncosted_launches"] for e in launches.values()
            ),
            "costed_executables": int(costed_exes),
        },
        "transfers": {
            "rows": rows,
            "by_phase": by_phase,
            "totals": totals,
        },
        "donation": donation,
    }
