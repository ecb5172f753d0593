"""From the traced partition's profile to device seconds and idle seconds
per program phase.

The program writes its phases into the profiler's own trace: every scope
of its ``GLOBAL_TIMER`` is a host event named ``SPAN_PREFIX`` + the
scope's dotted path (``kaminpar/partitioning.uncoarsening.jet``), and one
event ``kaminpar/request`` (stats ``k``, ``n``, ``m``) spans the whole
``compute_partition``.  All of them lie on the Python thread's line,
properly nested inside the request.  The prefix is spelled in
``trace_reduce.py``, in ``kaminpar_tpu/utils/timer.py`` and in
``PERF.md``; the benchmark does not import the program.

The join (pinned by ``tests/data/small.xplane.pb``, a v5e trace): every
``XLA Modules`` event of the device plane carries a ``run_id`` stat, and
so does the host event ``DoEnqueueProgram`` of the same launch.  The
enqueue's start instant lies in exactly one innermost program span: the
launch's owner, whichever span is open when the device gets to run it.
A launch without the stat falls back to the i-th ``PjitFunction(<f>)``
host call for the i-th ``jit_<f>`` launch, where both sides count the
same; a launch that neither finds has no owner.

Only the first device plane is read.  All times are seconds.  A profile
without a device plane or without a request span reduces to None, and
every metric read from it is then left out."""

from __future__ import annotations

import heapq
import re
from bisect import bisect_right
from collections import Counter

from .timer_tree import REFINER_SCOPES
from .trace_reduce import (DEVICE_PLANE, HOST_PLANE, MODULE_LINE, OP_LINE,
                           SPAN_PREFIX, _events, _label_gap, union)

REQUEST_SPAN = SPAN_PREFIX + "request"
ENQUEUE_EVENT = "DoEnqueueProgram"
RUN_ID = "run_id"

#: scopes beside ``extend-partition`` that belong to the extend step
EXTEND_SCOPES = ("extend-partition", "extend-pull")
LAYERS = ("coarsening", "refinement", "extend", "driver")
TOLERANCE = 0.01  # of both sum identities

_PJIT = re.compile(r"^PjitFunction\((.*)\)$")
_MODULE = re.compile(r"^jit_(.*?)(\(\d+\))?$")


def layer_of(path: str) -> tuple:
    """``(layer, scope)`` of a span by its scope names, innermost match
    first; ``scope`` is the name that matched ("" for the driver)."""
    for name in reversed(path.split(".")):
        if name in REFINER_SCOPES:
            return "refinement", name
        if name == "coarsening":
            return "coarsening", name
        if name in EXTEND_SCOPES:
            return "extend", name
    return "driver", ""


def _stat(event, key: str):
    for name, value in event.stats:
        if name == key:
            return value
    return None


def _segments(spans: list) -> tuple:
    """The request's interval cut where the innermost open span changes:
    ``(starts, owners)``, segment i runs from ``starts[i]`` to
    ``starts[i + 1]`` and belongs to span ``owners[i]``.  ``spans`` as
    ``_events`` gives, the request first and everything inside it."""
    starts, owners, stack = [], [], []

    def cut(instant: float, owner: int) -> None:
        if starts and starts[-1] == instant:
            owners[-1] = owner
        else:
            starts.append(instant)
            owners.append(owner)

    for i, (start, _, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= start:
            closed = stack.pop()
            if stack:
                cut(spans[closed][1], stack[-1])
        cut(start, i)
        stack.append(i)
    while len(stack) > 1:
        closed = stack.pop()
        cut(spans[closed][1], stack[-1])
    starts.append(spans[0][1])
    return starts, owners


def _outer_calls(events: list, calls: dict) -> None:
    """Add to ``calls`` the start instants of one line's
    ``PjitFunction(<f>)`` host calls, by ``f``.  A call is two nested
    events; the outer one counts.  ``events`` as ``_events`` gives."""
    outer_end = float("-inf")
    for start, end, name in events:
        found = _PJIT.match(name)
        if found is None or end <= outer_end:
            continue
        outer_end = end
        calls.setdefault(found.group(1), []).append(start)


def _overlap(intervals: list, ends: list, start: float, end: float) -> float:
    """Seconds of ``[start, end]`` that the disjoint sorted ``intervals``
    cover; ``ends`` are their ends."""
    covered = 0.0
    for lo, hi in intervals[bisect_right(ends, start):]:
        if lo >= end:
            break
        covered += min(hi, end) - max(lo, start)
    return covered


def reduce_profile(profile, top: int = 10):
    """The phases of the first request in ``profile``, or None."""
    device, host_lines = None, []
    for plane in profile.planes:
        found = DEVICE_PLANE.match(plane.name)
        if found and (device is None or int(found.group(1)) < device[0]):
            device = (int(found.group(1)), plane)
        elif plane.name == HOST_PLANE:
            host_lines = list(plane.lines)
    lines = {line.name: line for line in device[1].lines} if device else {}
    if MODULE_LINE not in lines:
        return None

    # --- the host plane: the program's spans, jax's events, the enqueues ---
    spans, args, jax_events, enqueues, calls = None, {}, [], {}, {}
    for line in host_lines:
        events = _events(line)
        own = [e for e in events if e[2].startswith(SPAN_PREFIX)]
        jax_events.extend(e for e in events
                          if not e[2].startswith(SPAN_PREFIX))
        _outer_calls(events, calls)
        root = next((e for e in own if e[2] == REQUEST_SPAN), None)
        if spans is None and root is not None:
            spans = [e for e in own if e[0] >= root[0] and e[1] <= root[1]]
        for event in line.events:
            if event.name == ENQUEUE_EVENT:
                run_id = _stat(event, RUN_ID)
                if run_id is not None:
                    enqueues.setdefault(run_id, float(event.start_ns) * 1e-9)
            elif event.name == REQUEST_SPAN and not args:
                args = {str(k): str(v) for k, v in event.stats}
    if spans is None:
        return None
    for instants in calls.values():
        instants.sort()
    root_start, root_end, _ = spans[0]
    paths = [name[len(SPAN_PREFIX):] for _, _, name in spans]
    starts, owners = _segments(spans)

    def owner_at(instant: float):
        if not root_start <= instant < root_end:
            return None
        return owners[bisect_right(starts, instant) - 1]

    # --- launches: enqueue instant, owner, device seconds -----------------
    modules = sorted(
        ((float(ev.start_ns) * 1e-9,
          (float(ev.start_ns) + float(ev.duration_ns)) * 1e-9,
          _MODULE.sub(r"\1", str(ev.name)), _stat(ev, RUN_ID))
         for ev in lines[MODULE_LINE].events), key=lambda m: m[0])
    ops = _events(lines[OP_LINE]) if OP_LINE in lines else []
    busy = union([(e[0], e[1]) for e in (ops or modules)])
    busy_ends = [end for _, end in busy]
    launched = Counter(name for _, _, name, _ in modules)

    stats = [{"calls": 1, "launches": 0, "device_s": 0.0, "idle_s": 0.0}
             for _ in spans]
    unowned = {"launches": 0, "device_s": 0.0}  # in the request, no join
    outside = {"launches": 0, "device_s": 0.0}  # the benchmark's own work
    joined = {RUN_ID: 0, "pjit": 0}
    lag = None  # least (device start - enqueue start): the clocks' offset
    seen: dict = {}
    mine = []  # the module intervals of the request's launches
    for start, end, name, run_id in modules:
        index = seen[name] = seen.get(name, -1) + 1
        enqueued, how = enqueues.get(run_id), RUN_ID
        if enqueued is None and len(calls.get(name, ())) == launched[name]:
            enqueued, how = calls[name][index], "pjit"
        if enqueued is None:
            target = unowned if root_start <= start < root_end else outside
        else:
            owner = owner_at(enqueued)
            target = outside if owner is None else stats[owner]
            if owner is not None:
                joined[how] += 1
                lag = (start - enqueued if lag is None
                       else min(lag, start - enqueued))
        target["launches"] += 1
        target["device_s"] += _overlap(busy, busy_ends, start, end)
        if target is not outside:
            mine.append((start, end))

    # --- idle: the request's interval less everything the device ran ------
    idle, cursor = [], root_start
    for lo, hi in busy[bisect_right(busy_ends, root_start):]:
        if lo >= root_end:
            break
        if lo > cursor:
            idle.append((cursor, lo))
        cursor = max(cursor, hi)
    if cursor < root_end:
        idle.append((cursor, root_end))
    gaps = []
    for lo, hi in idle:
        shares: dict = {}
        i = bisect_right(starts, lo) - 1
        while i < len(owners) and starts[i] < hi:
            piece = min(hi, starts[i + 1]) - max(lo, starts[i])
            stats[owners[i]]["idle_s"] += piece
            shares[owners[i]] = shares.get(owners[i], 0.0) + piece
            i += 1
        gaps.append((hi - lo, lo, hi, max(shares, key=shares.get)))
    gaps = heapq.nlargest(top, gaps)

    # --- by scope path, and rolled up to the layers ------------------------
    by_path: dict = {}
    layers = {name: {"launches": 0, "device_s": 0.0, "idle_s": 0.0}
              for name in LAYERS}
    jet = {"launches": 0, "device_s": 0.0}
    for path, stat in zip(paths, stats):
        layer, scope = layer_of(path)
        targets = [by_path.setdefault(path, dict.fromkeys(stat, 0)),
                   layers[layer]]
        if scope == "jet":
            targets.append(jet)
        for target in targets:
            for key in target:
                target[key] += stat[key]

    request_device_s = sum(_overlap(busy, busy_ends, lo, hi)
                           for lo, hi in union(mine))
    busy_in_request_s = _overlap(busy, busy_ends, root_start, root_end)
    out = {
        "request": {"seconds": root_end - root_start, "args": args},
        "spans": by_path,
        "layers": layers,
        "jet": jet,
        "unowned": unowned,
        "outside_request": outside,
        "joined": joined,
        "launch_lag_s": lag,
        "request_device_s": request_device_s,
        "attributed_share": (
            100.0 * sum(stat["device_s"] for stat in stats[1:])
            / request_device_s if request_device_s else None),
        "busy_in_request_s": busy_in_request_s,
        "idle_in_request_s": root_end - root_start - busy_in_request_s,
        "gaps": [[length, _label_gap(jax_events, lo, hi), paths[owner]]
                 for length, lo, hi, owner in gaps],
    }
    check(out)
    return out


def check(phases: dict) -> None:
    """Both sum identities, each within ``TOLERANCE``: the layers' device
    seconds and the ownerless rest are the request's device seconds; the
    layers' idle seconds are the request's length less the device's busy
    seconds inside it."""
    layers = phases["layers"]
    device_s = (sum(layer["device_s"] for layer in layers.values())
                + phases["unowned"]["device_s"])
    idle_s = sum(layer["idle_s"] for layer in layers.values())
    for what, got, want in (
            ("device", device_s, phases["request_device_s"]),
            ("idle", idle_s, phases["idle_in_request_s"])):
        if abs(got - want) > TOLERANCE * max(abs(want), 1e-9):
            raise ValueError(
                f"phase_reduce: the layers' {what} seconds sum to {got!r}, "
                f"the request's are {want!r}")


def render(phases: dict) -> str:
    """The table a ``perf_opt`` issue quotes: own numbers per scope path
    (a launch and an idle instant belong to the innermost span), the
    layers, and the longest idle gaps."""
    args = phases["request"]["args"]
    lag = phases["launch_lag_s"]
    lines = [
        "phases of the traced request ("
        + ", ".join(f"{k}={v}" for k, v in args.items()) + f"): "
        f"{phases['request']['seconds']:.4f} s, device busy "
        f"{phases['busy_in_request_s']:.4f} s, idle "
        f"{phases['idle_in_request_s']:.4f} s",
        f"launches joined by run_id {phases['joined']['run_id']}, by "
        f"PjitFunction {phases['joined']['pjit']}, without owner "
        f"{phases['unowned']['launches']} "
        f"({phases['unowned']['device_s']:.4f} s); outside the request "
        f"{phases['outside_request']['launches']} "
        f"({phases['outside_request']['device_s']:.4f} s); least device "
        "start after its enqueue "
        + ("-" if lag is None else f"{1e3 * lag:+.3f} ms"),
        f"{'scope path (own numbers)':<58}{'calls':>6}{'launches':>9}"
        f"{'device_s':>10}{'idle_s':>9}"]
    for path, row in phases["spans"].items():
        lines.append(f"{path:<58}{row['calls']:>6}{row['launches']:>9}"
                     f"{row['device_s']:>10.4f}{row['idle_s']:>9.4f}")
    for name, row in phases["layers"].items():
        lines.append(f"{'layer ' + name:<58}{'':>6}{row['launches']:>9}"
                     f"{row['device_s']:>10.4f}{row['idle_s']:>9.4f}")
    lines.append("longest idle gaps: length, jax event, program span")
    for length, label, path in phases["gaps"]:
        lines.append(f"  {length:.4f} s  {label}  {path}")
    return "\n".join(lines)


def reduce_file(path: str, top: int = 10):
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), top=top)


def phases(run: dict):
    """The traced sample's phases, reduced once per run and kept in
    ``run["phases"]``; the first reader that asks prints the table."""
    if "phases" not in run:
        traced = next((s for s in run["samples"] if s.get("traced")), None)
        reduced = None
        if traced is not None and traced.get("xplane"):
            reduced = reduce_file(traced["xplane"])
        if reduced is not None:
            print("perfbench: " + render(reduced), flush=True)
        run["phases"] = reduced
    return run["phases"]


def layer_value(run: dict, layer: str, key: str):
    """``device_s`` or ``idle_s`` of one layer (``jet``: its scope alone),
    or None where the run has no phases."""
    reduced = phases(run)
    if reduced is None:
        return None
    return (reduced["jet"] if layer == "jet" else reduced["layers"][layer])[key]
