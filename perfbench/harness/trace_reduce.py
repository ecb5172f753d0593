"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but
``jax.profiler.ProfileData``.

What a TPU trace holds (looked at by hand on a v5e, PR 22; the recorded
sample under ``perfbench/tests/data`` pins it): one plane per chip,
``/device:TPU:<i>``, whose line ``XLA Modules`` has one event per
executable launched (``jit__jet_chunk(<fingerprint>)``) and whose line
``XLA Ops`` has one event per HLO instruction executed, named by the
instruction's whole text (``%fusion.216 = s32[16384]{..} fusion(..),
kind=kCustom, calls=%fused_computation.186``).  ``while`` and
``conditional`` enclose the events of their bodies, so an instruction's
own time is its duration less its children's.  ``Async XLA Ops``
(copy-start .. copy-done) overlaps those and is not read.  ``/host:CPU``
has one line per host thread.  No event carries a category stat.

Which instructions are sorts, gathers and scatters.  This compiler
(libtpu 0.0.34) leaves a sort a ``sort`` instruction and wraps every
gather and every scatter in a fusion of ``kind=kCustom`` (checked by
compiling a gather, a scatter-add, an argsort and a cumsum for a v5e,
PR 22; elementwise code is ``kind=kLoop``, a cumsum ``reduce-window``).
The trace does not say which of the two a custom fusion holds, so they
are one class.

All times are seconds.  A trace without a device plane reduces to None,
and every metric read from it is then left out."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"

_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k[A-Za-z]+)")
_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")
_FINGERPRINT = re.compile(r"\(\d+\)$")  # jit_f(<fingerprint>) -> jit_f
_LABEL_MIN_SHARE = 0.2
#: every scope of the program's ``GLOBAL_TIMER`` is a host event named
#: this + the scope's dotted path (``kaminpar_tpu/utils/timer.py``)
SPAN_PREFIX = "kaminpar/"
_SPAN_MIN_SHARE = 0.5


def instruction(text: str) -> dict:
    """``{"name", "opcode", "kind", "shape"}`` of an ``XLA Ops`` event's
    name.  A bare ``sort.7`` (no ``=``) is taken as name and opcode."""
    name, sep, rest = text.partition(" = ")
    name = name.strip().lstrip("%")
    if not sep:
        return {"name": name, "opcode": name.split(".", 1)[0].lower(),
                "kind": "", "shape": ""}
    opcode = _OPCODE.search(" " + rest)
    kind = _KIND.search(rest)
    shape = _SHAPE.search(rest)
    return {"name": name, "opcode": opcode.group(1) if opcode else "",
            "kind": kind.group(1) if kind else "",
            "shape": shape.group(1) if shape else ""}


def op_class(text: str) -> str:
    """``sort``, ``gather_scatter`` or ``other``."""
    ins = instruction(text)
    if ins["opcode"] == "sort":
        return "sort"
    if ins["opcode"].startswith(("gather", "scatter")) or (
            ins["opcode"] == "fusion" and ins["kind"] == "kCustom"):
        return "gather_scatter"
    return "other"


def op_label(module: str, text: str) -> str:
    """A short name for the breakdown: module, instruction, opcode with
    the fusion's kind, result shape."""
    ins = instruction(text)
    what = ins["opcode"] + (":" + ins["kind"] if ins["kind"] else "")
    return " ".join(filter(None, [f"{module}/{ins['name']}", what,
                                  ins["shape"]]))


def _events(line) -> list:
    """``(start_s, end_s, name)`` sorted by start, longest first among
    equal starts (so a parent precedes its children)."""
    out = []
    for ev in line.events:
        start = float(ev.start_ns) * 1e-9
        out.append((start, start + float(ev.duration_ns) * 1e-9,
                    str(ev.name)))
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def union(intervals: list) -> list:
    """Disjoint, sorted ``[start, end]`` covering the same instants."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def self_times(events: list) -> list:
    """Own seconds of each event of one line: its duration less that of
    the events directly nested in it.  ``events`` as ``_events`` gives."""
    own = [end - start for start, end, _ in events]
    stack: list = []  # indices of the open enclosing events
    for i, (start, end, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= end - start
        stack.append(i)
    return [max(0.0, s) for s in own]


def _module_of(modules: list, instant: float) -> str:
    """Name of the launch that runs at ``instant`` (binary search)."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][0] <= instant:
            lo = mid + 1
        else:
            hi = mid
    if lo and instant < modules[lo - 1][1]:
        return modules[lo - 1][2]
    return "?"


def _label_gap(host_events: list, start: float, end: float) -> str:
    """What the host was doing in the gap.  The innermost (shortest)
    program span that covers at least half of it, where there is one: a
    gap runs a few milliseconds past a long host call on both sides (a
    read-back's tail, an upload), so the enclosing span covers all of it
    and the call 99 %, and the call is the answer.  Otherwise the host
    event that covers most of the gap, among equals the shortest;
    ``host`` where none covers a fifth."""
    best, best_key = "host", None
    span, span_length = None, None
    for ev_start, ev_end, name in host_events:
        overlap = min(end, ev_end) - max(start, ev_start)
        if overlap <= 0:
            continue
        length = ev_end - ev_start
        if (name.startswith(SPAN_PREFIX)
                and overlap >= _SPAN_MIN_SHARE * (end - start)
                and (span is None or length < span_length)):
            span, span_length = name, length
        key = (overlap, -length)
        if best_key is None or key > best_key:
            best, best_key = name, key
    if span is not None:
        return span
    if best_key is None or best_key[0] < _LABEL_MIN_SHARE * (end - start):
        return "host"
    return best


def reduce_profile(profile, chips: int = 1, top: int = 10):
    """The numbers of one traced window, or None without a device plane.

    ``launches``, ``device_busy_s`` and the class seconds are averaged
    over the first ``chips`` device planes; the gaps and the top
    operations are those of the first."""
    device_planes, host_events = [], []
    for plane in profile.planes:
        found = DEVICE_PLANE.match(plane.name)
        if found:
            device_planes.append((int(found.group(1)), plane))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_events.extend(_events(line))
    device_planes.sort(key=lambda p: p[0])
    device_planes = device_planes[:chips]
    if not device_planes:
        return None

    per_chip = []
    for _, plane in device_planes:
        lines = {line.name: line for line in plane.lines}
        modules = [(start, end, _FINGERPRINT.sub("", name)) for start, end, name
                   in (_events(lines[MODULE_LINE])
                       if MODULE_LINE in lines else [])]
        ops = _events(lines[OP_LINE]) if OP_LINE in lines else []
        timed = ops or modules
        busy = union([(e[0], e[1]) for e in timed])
        class_s = {"sort": 0.0, "gather_scatter": 0.0, "other": 0.0}
        by_name: dict = {}
        for (start, _, text), own in zip(ops, self_times(ops)):
            class_s[op_class(text)] += own
            label = op_label(_module_of(modules, start), text)
            by_name[label] = by_name.get(label, 0.0) + own
        per_chip.append({
            "launches": len(modules), "ops": len(ops), "busy": busy,
            "busy_s": sum(end - start for start, end in busy),
            "class_s": class_s, "by_name": by_name,
        })
    if not any(chip["busy"] for chip in per_chip):
        return None

    first = per_chip[0]
    gaps = [(b[0] - a[1], a[1], b[0])
            for a, b in zip(first["busy"], first["busy"][1:])]
    gaps.sort(reverse=True)
    n = len(per_chip)
    return {
        "chips": n,
        "launches": sum(c["launches"] for c in per_chip) / n,
        "device_ops_count": sum(c["ops"] for c in per_chip) / n,
        "device_busy_s": sum(c["busy_s"] for c in per_chip) / n,
        "class_s": {key: sum(c["class_s"][key] for c in per_chip) / n
                    for key in first["class_s"]},
        "first_op_s": first["busy"][0][0] if first["busy"] else None,
        "last_op_s": first["busy"][-1][1] if first["busy"] else None,
        "device_ops": [[name, seconds] for name, seconds in sorted(
            first["by_name"].items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label_gap(host_events, start, end), length]
                      for length, start, end in gaps[:top]],
    }


def newest_xplane(trace_dir: str):
    """The newest ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def reduce_file(path: str, chips: int = 1, top: int = 10):
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), chips=chips, top=top)
