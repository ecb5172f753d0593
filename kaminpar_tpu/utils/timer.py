"""Hierarchical timer (TPU-native analog of kaminpar-common/timer.{h,cc}).

The reference keeps a global hierarchical timer singleton with SCOPED_TIMER
macros (kaminpar-common/timer.h:20-62).  Here we keep a lightweight tree of
named scopes; `scoped_timer` is a context manager.  A scope in which the
host reads back from the device declares it with `sync=True`.

Every scope is also a span on the profiler's clock: it enters a
`jax.profiler.TraceAnnotation` named `SPAN_PREFIX` + the scope's dotted
path, so a profiler trace holds the program's phases beside the device's
lines (perfbench/harness/phase_reduce.py joins the two).  Outside a
profiler session an annotation costs one atomic load.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

from .. import telemetry
from ..telemetry import compile_account

#: every program span in a profiler trace starts with this (the benchmark
#: does not import the program: perfbench/harness/phase_reduce.py and
#: PERF.md spell it too)
SPAN_PREFIX = "kaminpar/"
#: the one root span of a request (KaMinPar.compute_partition)
REQUEST_SPAN = SPAN_PREFIX + "request"


@dataclass
class TimerNode:
    name: str
    elapsed: float = 0.0
    count: int = 0
    children: Dict[str, "TimerNode"] = field(default_factory=dict)

    def child(self, name: str) -> "TimerNode":
        node = self.children.get(name)
        if node is None:
            node = TimerNode(name)
            self.children[name] = node
        return node


class Timer:
    """Hierarchical wall-clock timer tree.

    Mirrors the reference's global Timer (kaminpar-common/timer.h) but is an
    ordinary object; a module-level default instance stands in for the
    singleton.  Disabled timers are ~free.
    """

    def __init__(self, name: str = "root", enabled: bool = True) -> None:
        self.root = TimerNode(name)
        self._stack = [self.root]
        self._open_starts: list = []  # perf_counter stamps of open scopes
        self._open_spans: list = []  # profiler annotations of open scopes
        self.enabled = enabled

    def reset(self) -> None:
        """Clear the tree.  A no-op while scopes are open: the library may
        run nested inside another pipeline (e.g. shm KaMinPar as the
        distributed driver's initial partitioner), and clearing mid-scope
        would orphan the open stack — the same global-singleton caveat the
        reference documents (README.MD:253-256)."""
        if len(self._stack) > 1:
            return
        self.root = TimerNode(self.root.name)
        self._stack = [self.root]
        self._open_starts = []
        self._open_spans = []

    def idle(self) -> bool:
        """True when no scope is open — i.e. not nested inside another
        pipeline.  Callers that reset process-global observability state
        (telemetry, stats) gate on this, matching reset()'s own guard."""
        return len(self._stack) == 1

    @contextmanager
    def scope(self, name: str, sync: bool = False):
        """Time a named scope.  `sync=True` declares a readback scope:
        its body is where the host waits for the device, so the whole
        scope is sync time (the span's `sync_s`) and tpulint R1 does not
        apply inside."""
        if not self.enabled:
            yield
            return
        node = self._stack[-1].child(name)
        self._stack.append(node)
        path = ".".join(n.name for n in self._stack[1:])
        span = TraceAnnotation(SPAN_PREFIX + path)
        span.__enter__()
        self._open_spans.append(span)
        tel = telemetry.enabled()
        entry_state = _span_entry_state() if tel else None
        start = time.perf_counter()
        self._open_starts.append(start)
        try:
            yield
        finally:
            # an emergency unwind() may have force-closed this scope
            # while the generator was suspended — don't double-account
            if self._stack and self._stack[-1] is node:
                end = time.perf_counter()
                node.elapsed += end - start
                node.count += 1
                if tel:
                    telemetry.record_span(
                        name, path, start, end - start,
                        **_span_exit_attrs(
                            entry_state, end - start if sync else None
                        ),
                    )
                self._stack.pop()
                if self._open_starts:
                    self._open_starts.pop()
                self._open_spans.pop().__exit__(None, None, None)

    def unwind(self) -> int:
        """Force-close every open scope, recording its elapsed time and
        span — the emergency path for an interrupt that surfaces from
        deep inside XLA (SIGINT during a jitted while_loop): without it
        the stack stays open, ``idle()`` lies, and the emergency run
        report renders a scope tree with un-accounted open nodes.
        Returns the number of scopes closed."""
        return self.unwind_to(1)

    def unwind_to(self, depth: int) -> int:
        """Force-close open scopes until the stack is back at ``depth``
        entries (the memory governor's per-rung unwind: a failed attempt
        must not leave ITS scopes open under the facade's, but the
        facade's own outer scopes stay).  ``unwind()`` is
        ``unwind_to(1)``."""
        closed = 0
        end = time.perf_counter()
        while len(self._stack) > max(1, depth):
            node = self._stack[-1]
            start = self._open_starts.pop() if self._open_starts else end
            node.elapsed += end - start
            node.count += 1
            if telemetry.enabled():
                path = ".".join(n.name for n in self._stack[1:])
                telemetry.record_span(
                    node.name, path, start, end - start, interrupted=True
                )
            self._stack.pop()
            self._open_spans.pop().__exit__(None, None, None)
            closed += 1
        return closed

    def elapsed(self, *path: str) -> float:
        node = self.root
        for name in path:
            if name not in node.children:
                return 0.0
            node = node.children[name]
        return node.elapsed

    def render(self) -> str:
        lines = []

        def rec(node: TimerNode, depth: int) -> None:
            if depth > 0:
                lines.append(
                    f"{'  ' * depth}{node.name}: {node.elapsed:.4f} s"
                    + (f" ({node.count}x)" if node.count > 1 else "")
                )
            for child in node.children.values():
                rec(child, depth + 1)

        rec(self.root, 0)
        return "\n".join(lines)

    def render_machine(self) -> str:
        """One-line machine-readable dump: dotted-path=seconds pairs
        (the analog of the reference's machine-readable timer tree that
        backs its parseable TIME output, kaminpar-common/timer.h:135)."""
        parts = []

        def rec(node: TimerNode, path: str) -> None:
            for child in node.children.values():
                child_path = f"{path}.{child.name}" if path else child.name
                parts.append(f"{child_path}={child.elapsed:.6f}")
                rec(child, child_path)

        rec(self.root, "")
        return " ".join(parts)


def _span_entry_state() -> dict:
    """Snapshot the per-scope baselines for telemetry span attributes
    (only taken when telemetry is enabled; each section additionally
    gates on its own utility being enabled)."""
    state: dict = {}
    from . import heap_profiler, statistics

    if heap_profiler.profiling_enabled():
        import tracemalloc

        state["host_mem"] = tracemalloc.get_traced_memory()
    if statistics.enabled():
        state["counters"] = statistics.counters_snapshot()
    return state


def _span_exit_attrs(state: Optional[dict], sync_s: Optional[float]) -> dict:
    attrs: dict = {}
    if sync_s is not None:
        attrs["sync_s"] = round(sync_s, 6)
    if not state:
        return attrs
    from . import heap_profiler, statistics

    host_mem = state.get("host_mem")
    if host_mem is not None and heap_profiler.profiling_enabled():
        import tracemalloc

        cur0, peak0 = host_mem
        _, peak1 = tracemalloc.get_traced_memory()
        if peak1 > peak0:  # a new high-water mark was set inside the scope
            attrs["host_peak_bytes"] = int(peak1 - cur0)
        live = heap_profiler.live_device_bytes()
        if live:
            attrs["live_hbm_bytes"] = int(live)
    counters0 = state.get("counters")
    if counters0 is not None and statistics.enabled():
        delta = statistics.counters_delta(counters0)
        if delta:
            attrs["counters"] = delta
    return attrs


GLOBAL_TIMER = Timer()


@contextmanager
def request_span(**args):
    """The root profiler span of one request.  An annotation only, not a
    timer node: every scope of the request lies inside it on one thread,
    and that containment is what ties a trace's spans to the request.
    The compile account notes the same interval on `perf_counter` as the
    process's n-th request (telemetry/compile_account.request)."""
    with compile_account.request(), TraceAnnotation(REQUEST_SPAN, **args):
        yield


@contextmanager
def scoped_timer(
    name: str, timer: Optional[Timer] = None, sync: bool = False
):
    t = timer if timer is not None else GLOBAL_TIMER
    with t.scope(name, sync=sync):
        yield


def aggregate_across_processes(timer: Optional[Timer] = None):
    """Per-device timer aggregation (kaminpar-dist/timer.cc analog).

    The reference finalizes its dist timer by reducing each scope's
    elapsed time across PEs (MPI min/avg/max) so a real-mesh run exposes
    imbalance between hosts.  The JAX analog reduces each scope across
    *processes* (multi-host SPMD: one process per host drives its local
    devices; per-scope wall times differ between hosts exactly like the
    reference's per-PE times).

    Returns {dotted_path: {"min": s, "avg": s, "max": s, "count": n}}.
    On a single-process run (this dev box, the CPU test mesh) every
    min == avg == max — the shape callers rely on is identical, so code
    written against it works unchanged on a real multi-host mesh.
    """
    t = timer if timer is not None else GLOBAL_TIMER

    paths: list = []
    values: list = []
    counts: list = []

    def rec(node: TimerNode, path: str) -> None:
        for child in node.children.values():
            child_path = f"{path}.{child.name}" if path else child.name
            paths.append(child_path)
            values.append(child.elapsed)
            counts.append(child.count)
            rec(child, child_path)

    rec(t.root, "")

    import numpy as np

    local = np.asarray(values, dtype=np.float64)
    try:
        from .platform import process_count

        nproc = process_count()
    except Exception:
        nproc = 1
    if nproc > 1 and len(local):
        # all hosts must call this with the SAME scope tree (same code
        # path), mirroring the reference's collective finalize()
        from jax.experimental import multihost_utils

        gathered = np.asarray(
            multihost_utils.process_allgather(local)
        ).reshape(nproc, -1)
        mins, avgs, maxs = (
            gathered.min(0), gathered.mean(0), gathered.max(0)
        )
    else:
        mins = avgs = maxs = local
    return {
        p: {
            "min": float(mins[i]),
            "avg": float(avgs[i]),
            "max": float(maxs[i]),
            "count": int(counts[i]),
        }
        for i, p in enumerate(paths)
    }


def render_aggregated(agg: dict) -> str:
    """Human-readable min/avg/max table (timer.cc's finalized output)."""
    lines = []
    for path, s in agg.items():
        depth = path.count(".")
        name = path.rsplit(".", 1)[-1]
        lines.append(
            f"{'  ' * (depth + 1)}{name}: min={s['min']:.4f} "
            f"avg={s['avg']:.4f} max={s['max']:.4f} s"
            + (f" ({s['count']}x)" if s["count"] > 1 else "")
        )
    return "\n".join(lines)
