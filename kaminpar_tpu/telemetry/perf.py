"""Performance observatory: per-scope roofline accounting, device-memory
watermarks, padding-waste attribution, and serving-latency histograms.

PR 4's compile accounting answers "was the slow part compile or execute";
this layer answers the next question every ROADMAP item 1-4 PR has to ask
before writing kernel code: *where do the bytes, FLOPs and padded-away
slots actually go, and how far below the roofline does each scope sit*.
Four concerns, one module:

  * **roofline accounting** — `install()` wraps jax's backend-compile
    boundary (the same dispatch-time attribution contract as
    `compile_account`): every freshly compiled executable's XLA cost
    analysis (FLOPs, bytes accessed) and compiled memory stats (output /
    temp / argument bytes) are recorded against the dotted timer-scope
    path open at compile time.  `snapshot()` joins those costs with the
    measured per-scope wall from the hierarchical timer and the device
    peak (the published figures of the running `device_kind`,
    `DEVICE_PEAKS`; `KAMINPAR_TPU_PEAK_GBPS` / `KAMINPAR_TPU_PEAK_GFLOPS`
    override) to
    report achieved bytes/s and FLOPs/s *vs peak* per scope — the
    `vs peak` column BASELINE.json notes used to hand-compute.
  * **device-memory watermarks** — `sample_memory(stage)` records the
    live-device-byte figure (plus backend memory_stats where exposed) as
    a `perf-memory` telemetry event; the PR-5 multilevel barriers call it
    (resilience/checkpoint.barrier), so every coarsen / initial /
    uncoarsen boundary gets a resident-bytes sample with zero code in
    jitted regions.  chrome_trace renders the samples as counter tracks;
    the report's `perf.memory` subsection carries peak bytes, per-stage
    samples, per-level CSR buffer bytes and headroom vs the HBM limit.
  * **padding-waste attribution** — `record_padding(...)` (forwarded by
    `caching.record_padding` from every shape-bucket pad site: device
    CSR upload, contraction, subgraph slicing, the k bucket, the dist
    shards) aggregates real-vs-padded element counts per (scope, bucket)
    and axis, so the report shows what fraction of every kernel launch
    was padding — the direct input ROADMAP item 1 needs to pick fusion
    targets and item 5's bucketing-policy refactor needs to tune caps.
  * **latency histograms** — :class:`Histogram`, a fixed log-spaced
    streaming histogram (p50/p95/p99 without storing samples); the
    serving layer keeps one per request phase and per request class.

Instrumentation contract (pinned by tests/test_perf.py's jaxpr-equality
test): cost capture happens at compile boundaries, memory sampling at
barriers, pad accounting at host-side pad computations — NEVER inside
jitted code, so the traced jaxprs are identical whether the layer is on,
off (`KAMINPAR_TPU_PERF=0`), or telemetry is disabled entirely.

Meter honesty (stamped per roofline row since PR 19): cost is captured
once per *backend compile* and joined with the execution ledger's
per-launch counts (telemetry/ledger.py) — a row whose every launch ran a
costed executable carries ``honest: true`` and launch-multiplied bytes/
FLOPs; a row that saw a launch whose cost was never captured (e.g. a
persistent-cache warm start) carries ``honest: false`` and falls back to
the compile-time lower bound.
"""

from __future__ import annotations

import bisect
import math
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

ENV_VAR = "KAMINPAR_TPU_PERF"
ENV_PEAK_GBPS = "KAMINPAR_TPU_PEAK_GBPS"
ENV_PEAK_GFLOPS = "KAMINPAR_TPU_PEAK_GFLOPS"
ENV_HBM_BYTES = "KAMINPAR_TPU_HBM_BYTES"

#: Published per-chip peaks keyed by jax's ``device_kind``:
#: (HBM GB/s, bf16 GFLOP/s).  The one table every roofline share in the
#: repo divides by (bench.py reads it too).  A device that is not listed
#: is an error, not a default — add its row with a source.
DEVICE_PEAKS: Dict[str, Tuple[float, float]] = {
    # Google Cloud documentation, "TPU v5e": 819 GB/s HBM bandwidth,
    # 197 TFLOP/s bf16 (and 16 GB of HBM per chip; the memory ceiling
    # itself is read from the device, hbm_limit_bytes below)
    "TPU v5 lite": (819.0, 197_000.0),
    # XLA:CPU, the test backend.  NOT a measurement and not a published
    # figure: a placeholder so CPU test runs produce well-formed
    # rooflines; utilization against it means nothing.
    "cpu": (40.0, 150.0),
}


class UnknownDeviceError(LookupError):
    """The running device has no row in DEVICE_PEAKS."""


def device_peaks(device_kind: str) -> Tuple[float, float]:
    """(GB/s, GFLOP/s) of ``device_kind`` from DEVICE_PEAKS."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            "row with its source to telemetry/perf.DEVICE_PEAKS, or set "
            f"{ENV_PEAK_GBPS} and {ENV_PEAK_GFLOPS}"
        ) from None


CAVEAT = (
    "costs are captured once per backend compile, attributed to the "
    "open timer scope, and joined with the execution ledger's "
    "per-launch counts (KAMINPAR_TPU_LEDGER); rows with honest=true "
    "multiply cost by measured launches, rows with honest=false saw a "
    "launch whose cost was never captured (e.g. persistent-cache warm "
    "start) and fall back to the compile-time lower bound; peaks are "
    "the published figures of the running device_kind "
    "(telemetry/perf.DEVICE_PEAKS) unless KAMINPAR_TPU_PEAK_GBPS / "
    "KAMINPAR_TPU_PEAK_GFLOPS override them"
)

#: Per-scope executable detail kept for triage; aggregates are unbounded
#: (one entry per distinct scope path — O(scope tree)).
MAX_EXECUTABLES_PER_SCOPE = 32

_lock = threading.Lock()
_installed = False
# dotted scope path -> {"flops","bytes","output_bytes","temp_bytes",
#                       "arg_bytes","compiles","executables":[...]}
_scopes: Dict[str, Dict[str, Any]] = {}
# (dotted scope path, bucket str) -> axis counters
_pad: Dict[Tuple[str, str], Dict[str, int]] = {}


def enabled() -> bool:
    """True iff telemetry is on and KAMINPAR_TPU_PERF is not 0 — the one
    gate every producer checks before doing any work."""
    if os.environ.get(ENV_VAR, "") == "0":
        return False
    from . import enabled as _telemetry_enabled

    return _telemetry_enabled()


def reset() -> None:
    with _lock:
        _scopes.clear()
        _pad.clear()


# ---------------------------------------------------------------------------
# roofline: compile-time cost capture
# ---------------------------------------------------------------------------


def install() -> None:
    """Wrap jax 0.9.0's backend-compile entry point,
    ``jax._src.compiler.backend_compile_and_load`` (idempotent; the
    wrapper no-ops while the layer is disabled, so installation is
    free).  The name is private to jax: an installation that moved it
    raises here, at ``telemetry.enable()``, instead of recording empty
    rooflines."""
    global _installed
    if _installed:
        return
    from jax._src import compiler as _compiler

    orig = _compiler.backend_compile_and_load

    def _wrapped(*args: Any, **kwargs: Any):
        exe = orig(*args, **kwargs)
        if enabled():
            _record_executable(exe)
        return exe

    _compiler.backend_compile_and_load = _wrapped
    _installed = True


def _record_executable(exe: Any) -> None:
    """Harvest one freshly compiled executable's cost analysis and
    attribute it to the open scope (compiles run synchronously under the
    caller's scope — the compile_account attribution contract)."""
    cost: Dict[str, Any] = {}
    try:
        ca = exe.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        cost = dict(ca or {})
    except Exception:
        pass
    flops = max(float(cost.get("flops", 0.0) or 0.0), 0.0)
    nbytes = max(float(cost.get("bytes accessed", 0.0) or 0.0), 0.0)
    out_b = temp_b = arg_b = 0
    try:
        ms = exe.get_compiled_memory_stats()
        out_b = int(ms.output_size_in_bytes)
        temp_b = int(ms.temp_size_in_bytes)
        arg_b = int(ms.argument_size_in_bytes)
    except Exception:
        pass
    name = ""
    try:
        name = exe.hlo_modules()[0].name
    except Exception:
        pass
    try:
        # the execution ledger joins launches back to this compile's
        # cost by executable identity (telemetry/ledger.py)
        from . import ledger

        ledger.register_executable(exe, flops=flops, nbytes=nbytes,
                                   name=name)
    except Exception:
        pass
    from . import current_scope_path

    path = current_scope_path() or "(outside scopes)"
    with _lock:
        entry = _scopes.setdefault(
            path,
            {"flops": 0.0, "bytes": 0.0, "output_bytes": 0,
             "temp_bytes": 0, "arg_bytes": 0, "compiles": 0,
             "executables": []},
        )
        entry["flops"] += flops
        entry["bytes"] += nbytes
        entry["output_bytes"] += out_b
        entry["temp_bytes"] += temp_b
        entry["arg_bytes"] += arg_b
        entry["compiles"] += 1
        if len(entry["executables"]) < MAX_EXECUTABLES_PER_SCOPE:
            entry["executables"].append(
                {"name": name, "flops": flops, "bytes": nbytes,
                 "output_bytes": out_b}
            )


def peaks() -> Dict[str, Any]:
    """The roofline ceiling this process compares against: env override
    first, else the DEVICE_PEAKS row of the running device."""
    source = "env"
    gbps = _env_float(ENV_PEAK_GBPS)
    gflops = _env_float(ENV_PEAK_GFLOPS)
    if gbps is None or gflops is None:
        from ..utils import platform

        kind = platform.devices()[0].device_kind
        d_gbps, d_gflops = device_peaks(kind)
        if gbps is None:
            gbps = d_gbps
        if gflops is None:
            gflops = d_gflops
        source = f"default:{kind}"
    return {"gbps": float(gbps), "gflops": float(gflops),
            "source": source}


def _env_float(name: str) -> Optional[float]:
    raw = os.environ.get(name, "")
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


# ---------------------------------------------------------------------------
# device-memory watermarks
# ---------------------------------------------------------------------------


def _device_memory_stats() -> Dict[str, int]:
    """bytes_in_use / peak / limit where the backend exposes them (TPU
    does via memory_stats; CPU returns {})."""
    try:
        from ..utils import platform

        stats = platform.local_devices()[0].memory_stats()
    except Exception:
        stats = None
    if not stats:
        return {}
    out: Dict[str, int] = {}
    for src, dst in (
        ("bytes_in_use", "bytes_in_use"),
        ("peak_bytes_in_use", "peak_bytes_in_use"),
        ("bytes_limit", "bytes_limit"),
    ):
        if src in stats:
            out[dst] = int(stats[src])
    return out


def hbm_limit_bytes() -> Optional[int]:
    """The device memory ceiling headroom is computed against:
    KAMINPAR_TPU_HBM_BYTES first, else the backend's bytes_limit."""
    raw = _env_float(ENV_HBM_BYTES)
    if raw is not None:
        return int(raw)
    limit = _device_memory_stats().get("bytes_limit")
    return int(limit) if limit else None


def sample_memory(stage: str, level: Optional[int] = None
                  ) -> Optional[dict]:
    """Record one resident-memory sample as a `perf-memory` telemetry
    event (events ride the existing multi-host gather and become Chrome
    counter tracks).  Called from the PR-5 multilevel barriers — host
    side, between device launches, never inside traced code.  Returns
    the sample attrs, or None when the layer is off."""
    if not enabled():
        return None
    from ..utils import heap_profiler

    attrs: Dict[str, Any] = {
        "stage": str(stage),
        "live_bytes": int(heap_profiler.live_device_bytes()),
    }
    if level is not None:
        attrs["level"] = int(level)
    attrs.update(_device_memory_stats())
    from . import event

    event("perf-memory", **attrs)
    return attrs


def rank_memory_rollup() -> List[dict]:
    """Per-process live-device-bytes figures ([{rank, live_bytes}]).

    Collective on multi-host runs (allgather) — every process must call
    it together, same contract as the aggregated timers; single-process
    runs return just the local row.  The dist driver stamps the result
    into the run report (`perf.memory.ranks`)."""
    from ..utils import heap_profiler

    local = int(heap_profiler.live_device_bytes())
    try:
        from ..utils.platform import process_count, process_index

        nproc = process_count()
        rank = process_index()
    except Exception:
        return [{"rank": 0, "live_bytes": local}]
    if nproc <= 1:
        return [{"rank": int(rank), "live_bytes": local}]
    import numpy as np
    from jax.experimental import multihost_utils

    gathered = np.asarray(
        multihost_utils.process_allgather(
            np.array([local], dtype=np.int64)
        )
    ).reshape(-1)
    try:
        from . import ledger

        ledger.transfer("d2h", gathered.nbytes, "dist-gather")
    except Exception:
        pass
    return [
        {"rank": p, "live_bytes": int(gathered[p])} for p in range(nproc)
    ]


# ---------------------------------------------------------------------------
# padding-waste attribution
# ---------------------------------------------------------------------------


def record_padding(
    n: Optional[int] = None, n_pad: Optional[int] = None,
    m: Optional[int] = None, m_pad: Optional[int] = None,
    k: Optional[int] = None, k_pad: Optional[int] = None,
) -> None:
    """Record one padded launch shape: real vs padded element counts per
    axis, keyed by (open scope path, padded bucket).  Callers pass only
    the axes they padded; host-side, a dict update, nothing traced."""
    if not enabled():
        return
    from . import current_scope_path

    path = current_scope_path() or "(outside scopes)"
    bucket = "/".join(
        str(int(v)) if v is not None else "-"
        for v in (n_pad, m_pad, k_pad)
    )
    with _lock:
        e = _pad.setdefault(
            (path, bucket),
            {"launches": 0, "n": 0, "n_pad": 0, "m": 0, "m_pad": 0,
             "k": 0, "k_pad": 0},
        )
        e["launches"] += 1
        for axis, real, padded in (
            ("n", n, n_pad), ("m", m, m_pad), ("k", k, k_pad)
        ):
            if padded:
                e[axis] += int(real or 0)
                e[axis + "_pad"] += int(padded)


def _waste(real: int, padded: int) -> Optional[float]:
    if not padded:
        return None
    return round(1.0 - real / padded, 4)


# ---------------------------------------------------------------------------
# streaming latency histogram
# ---------------------------------------------------------------------------


class Histogram:
    """Fixed log-spaced streaming histogram over seconds.

    42 bucket edges from 100 µs up by sqrt(2) per bucket (~148 s span);
    a value exactly on an edge lands in the bucket *starting* at that
    edge, values below the first edge share bucket 0, values past the
    last edge share the final bucket.  Quantiles interpolate to the
    bucket's upper edge clamped to the observed maximum — conservative
    (never under-reports a latency SLO) and exact for boundary values.
    Single-writer by design (the serving loop is serial); snapshots are
    consistent under the GIL.
    """

    EDGES: Tuple[float, ...] = tuple(
        1e-4 * (2 ** (i / 2.0)) for i in range(42)
    )

    def __init__(self) -> None:
        self.counts = [0] * len(self.EDGES)
        self.count = 0
        self.total = 0.0
        self.max = 0.0

    def record(self, seconds: float) -> None:
        v = max(float(seconds), 0.0)
        i = bisect.bisect_right(self.EDGES, v) - 1
        if i < 0:
            i = 0
        self.counts[i] += 1
        self.count += 1
        self.total += v
        if v > self.max:
            self.max = v

    def quantile(self, q: float) -> Optional[float]:
        """The q-quantile in seconds (None when empty)."""
        if self.count == 0:
            return None
        target = max(1, math.ceil(q * self.count))
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= target:
                upper = (
                    self.EDGES[i + 1] if i + 1 < len(self.EDGES)
                    else self.max
                )
                return min(upper, self.max)
        return self.max

    def reset(self) -> None:
        self.counts = [0] * len(self.EDGES)
        self.count = 0
        self.total = 0.0
        self.max = 0.0

    def snapshot(self) -> dict:
        """Report-ready summary (milliseconds; empty histograms report
        null quantiles rather than inventing a zero)."""
        def ms(v: Optional[float]) -> Optional[float]:
            return round(v * 1000.0, 3) if v is not None else None

        nonzero = [
            [ms(self.EDGES[i]), c]
            for i, c in enumerate(self.counts) if c
        ]
        return {
            "count": int(self.count),
            "mean_ms": ms(self.total / self.count) if self.count else None,
            "max_ms": ms(self.max) if self.count else None,
            "p50_ms": ms(self.quantile(0.50)),
            "p95_ms": ms(self.quantile(0.95)),
            "p99_ms": ms(self.quantile(0.99)),
            "buckets": nonzero,
        }


# ---------------------------------------------------------------------------
# snapshot: the run report's `perf` section
# ---------------------------------------------------------------------------


def _timer_walls() -> Dict[str, Tuple[float, float, int]]:
    """Per-scope (inclusive wall, exclusive/self wall, call count).

    Self wall (inclusive minus the children's inclusive time) is what a
    cost attributed to a non-leaf scope actually ran in — a compile
    dispatched while only `coarsening` was open executed in coarsening's
    own time, not its children's — so the deficit ranking uses it; the
    inclusive figure stays the human-facing wall column."""
    from ..utils import timer

    out: Dict[str, Tuple[float, float, int]] = {}

    def rec(node, path: str) -> None:
        for child in node.children.values():
            p = f"{path}.{child.name}" if path else child.name
            child_total = sum(
                c.elapsed for c in child.children.values()
            )
            self_wall = max(0.0, child.elapsed - child_total)
            out[p] = (child.elapsed, self_wall, child.count)
            rec(child, p)

    rec(timer.GLOBAL_TIMER.root, "")
    return out


def _total_wall() -> float:
    from ..utils import timer

    return sum(
        c.elapsed for c in timer.GLOBAL_TIMER.root.children.values()
    )


def snapshot() -> dict:
    """Assemble the `perf` report section from the current state.

    Roofline rows join the per-scope compile costs with the scope's
    measured wall; memory samples come from the `perf-memory` event
    stream (so a multi-host report sees every rank's samples the same
    way spans are gathered); pad-waste rows aggregate per (scope,
    bucket) with per-axis waste fractions."""
    on = enabled()
    pk = peaks()
    with _lock:
        scopes = {p: dict(e) for p, e in _scopes.items()}
        pad_items = [(key, dict(e)) for key, e in _pad.items()]

    try:
        from . import ledger as _ledger

        launch_map = _ledger.launch_totals()
    except Exception:
        launch_map = {}

    walls = _timer_walls()
    roofline: Dict[str, Any] = {}
    tot_flops = tot_bytes = 0.0
    tot_eff_flops = tot_eff_bytes = 0.0
    tot_launches = tot_uncosted = 0
    empty = {"flops": 0.0, "bytes": 0.0, "output_bytes": 0,
             "temp_bytes": 0, "arg_bytes": 0, "compiles": 0,
             "executables": []}
    for path in sorted(set(scopes) | set(launch_map)):
        # a scope can launch without compiling (warm cache under a
        # fresh scope path) — it still gets a roofline row
        e = scopes.get(path, empty)
        lm = launch_map.get(
            path, {"launches": 0, "uncosted": 0, "bytes": 0.0,
                   "flops": 0.0},
        )
        wall, self_wall, calls = walls.get(path, (0.0, 0.0, 0))
        # honest: every launch in this scope ran a costed executable,
        # so the ledger figures are the true moved bytes/FLOPs; stale
        # (honest=false) rows fall back to the compile-time lower bound
        honest = lm["launches"] > 0 and lm["uncosted"] == 0
        eff_bytes = lm["bytes"] if honest else max(e["bytes"], lm["bytes"])
        eff_flops = lm["flops"] if honest else max(e["flops"], lm["flops"])
        row: Dict[str, Any] = {
            "flops": round(e["flops"], 1),
            "bytes": round(e["bytes"], 1),
            "output_bytes": int(e["output_bytes"]),
            "temp_bytes": int(e["temp_bytes"]),
            "compiles": int(e["compiles"]),
            "launches": int(lm["launches"]),
            "uncosted_launches": int(lm["uncosted"]),
            "ledger_bytes": round(lm["bytes"], 1),
            "ledger_flops": round(lm["flops"], 1),
            "honest": honest,
            "wall_s": round(wall, 6),
            "self_s": round(self_wall, 6),
            "calls": int(calls),
            "executables": e["executables"],
        }
        if wall > 0:
            achieved_gbps = eff_bytes / wall / 1e9
            achieved_gflops = eff_flops / wall / 1e9
            hbm_util = achieved_gbps / pk["gbps"] if pk["gbps"] else 0.0
            flops_util = (
                achieved_gflops / pk["gflops"] if pk["gflops"] else 0.0
            )
            row.update(
                achieved_gbps=round(achieved_gbps, 3),
                achieved_gflops=round(achieved_gflops, 3),
                hbm_util=round(hbm_util, 4),
                flops_util=round(flops_util, 4),
                # wall spent below the roofline: the triage ranking key
                # (telemetry.top --by util-deficit).  Exclusive wall, so
                # a non-leaf scope with one attributed compile does not
                # re-count its children's time and per-row deficits sum
                # to at most the total wall.
                deficit_s=round(
                    self_wall
                    * (1.0 - min(1.0, max(hbm_util, flops_util))), 6
                ),
            )
        roofline[path] = row
        tot_flops += e["flops"]
        tot_bytes += e["bytes"]
        tot_eff_flops += eff_flops
        tot_eff_bytes += eff_bytes
        tot_launches += lm["launches"]
        tot_uncosted += lm["uncosted"]

    pad_rows: List[dict] = []
    pad_real = pad_padded = 0
    axis_real = {"n": 0, "m": 0, "k": 0}
    axis_padded = {"n": 0, "m": 0, "k": 0}
    for (path, bucket), e in pad_items:
        row = {
            "scope": path,
            "bucket": bucket,
            "launches": int(e["launches"]),
        }
        for axis in ("n", "m", "k"):
            w = _waste(e[axis], e[axis + "_pad"])
            if w is not None:
                row[axis + "_real"] = int(e[axis])
                row[axis + "_pad"] = int(e[axis + "_pad"])
                row[axis + "_waste"] = w
                # per-bucket pad slack ("headroom", element count per
                # launch): the free padded slots of this bucket — the
                # same number that decides whether a dynamic-session
                # delta can apply IN PLACE (same executable bucket,
                # dynamic/session.py) or must rebuild and re-upload
                row[axis + "_slack"] = int(
                    (e[axis + "_pad"] - e[axis])
                    // max(int(e["launches"]), 1)
                )
                pad_real += e[axis]
                pad_padded += e[axis + "_pad"]
                axis_real[axis] += e[axis]
                axis_padded[axis] += e[axis + "_pad"]
        pad_rows.append(row)
    pad_rows.sort(key=lambda r: (-r["launches"], r["scope"], r["bucket"]))

    from . import events as _events

    samples = [
        {"t": round(e.t, 6), **e.attrs} for e in _events("perf-memory")
    ]
    peak_live = max((s.get("live_bytes", 0) for s in samples), default=0)
    limit = hbm_limit_bytes()
    memory: Dict[str, Any] = {
        "peak_live_bytes": int(peak_live),
        "samples": samples,
    }
    if limit:
        memory["hbm_limit_bytes"] = int(limit)
        memory["headroom_bytes"] = int(limit - peak_live)

    total_wall = _total_wall()
    totals: Dict[str, Any] = {
        "flops": round(tot_flops, 1),
        "bytes": round(tot_bytes, 1),
        # launch-honest twins (execution ledger): compile-time figures
        # above stay flat across re-launches, these scale with them
        "ledger_flops": round(tot_eff_flops, 1),
        "ledger_bytes": round(tot_eff_bytes, 1),
        "launches": int(tot_launches),
        "util_honest": bool(tot_launches > 0 and tot_uncosted == 0),
        "compiles": sum(e["compiles"] for e in scopes.values()),
        "wall_s": round(total_wall, 6),
        "pad_waste": _waste(pad_real, pad_padded),
        # per-axis twins: the headline sums element counts across axes,
        # so edge counts (m >> n >> k) numerically dominate it — a 25%
        # k-bucket waste is invisible there but plain in pad_waste_axes
        "pad_waste_axes": {
            axis: w
            for axis in ("n", "m", "k")
            if (w := _waste(axis_real[axis], axis_padded[axis]))
            is not None
        },
        # per-axis total slack (padded - real element counts): the
        # aggregate headroom twin of the per-row *_slack figures
        "pad_slack_axes": {
            axis: int(axis_padded[axis] - axis_real[axis])
            for axis in ("n", "m", "k")
            if axis_padded[axis]
        },
    }
    if total_wall > 0:
        # launch-honest: the effective (ledger-joined) byte/FLOP totals
        # drive the headline utilization; totals["bytes"]/["flops"]
        # remain the flat compile-time figures for comparison
        totals["hbm_util"] = round(
            tot_eff_bytes / total_wall / 1e9 / pk["gbps"], 4
        ) if pk["gbps"] else 0.0
        totals["flops_util"] = round(
            tot_eff_flops / total_wall / 1e9 / pk["gflops"], 4
        ) if pk["gflops"] else 0.0

    return {
        "enabled": on,
        "caveat": CAVEAT,
        "peaks": pk,
        "totals": totals,
        "roofline": roofline,
        "memory": memory,
        "pad_waste": pad_rows,
    }
