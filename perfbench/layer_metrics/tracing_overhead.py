"""The traced partition's wall over the median wall of the same run's
untraced partitions, less one: what the profiler session costs with the
program's spans in it.  Left out where the trace holds no program span."""

from statistics import median

from perfbench.harness import phase_reduce

LAYER = "device"
UNIT = "%"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    walls = [s["wall_s"] for s in run["samples"] if not s.get("traced")]
    if phase_reduce.phases(run) is None or not walls:
        return None
    return 100.0 * (run["traced_wall_s"] / median(walls) - 1.0)
