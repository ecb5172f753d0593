#!/usr/bin/env python3
"""Look at one profiler trace by hand: its planes, their lines, and per
line the event names that take most time, each with one event's stats.

    python3 perfbench/tools/dump_xplane.py <file.xplane.pb> [<out.json>]

Read this before changing ``harness/trace_reduce.py``: which plane is the
device, which line holds module executions, how the sorts, gathers and
scatters are named."""

from __future__ import annotations

import json
import sys


def dump(path: str, top: int = 25) -> dict:
    from jax.profiler import ProfileData

    out = {"file": path, "planes": []}
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            by_name: dict = {}
            count = 0
            for ev in line.events:
                count += 1
                entry = by_name.get(ev.name)
                if entry is None:
                    entry = by_name[ev.name] = {
                        "n": 0, "s": 0.0,
                        "stats": {str(k): str(v)[:200] for k, v in ev.stats}}
                entry["n"] += 1
                entry["s"] += float(ev.duration_ns) * 1e-9
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1]["s"])
            lines.append({"line": line.name, "events": count,
                          "names": len(by_name),
                          "top": [dict(name=name, **entry)
                                  for name, entry in ranked[:top]]})
        out["planes"].append({"plane": plane.name, "lines": lines})
    return out


def main() -> int:
    if len(sys.argv) not in (2, 3):
        sys.exit(__doc__)
    result = dump(sys.argv[1])
    for plane in result["planes"]:
        print(f"PLANE {plane['plane']}")
        for line in plane["lines"]:
            print(f"  LINE {line['line']!r}: {line['events']} events, "
                  f"{line['names']} names")
            for entry in line["top"][:8]:
                print(f"    {entry['s']:10.6f} s {entry['n']:7d}x "
                      f"{entry['name'][:90]}")
    if len(sys.argv) == 3:
        with open(sys.argv[2], "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
