"""Seconds of the traced request in which the device ran nothing while
an ``initial-partitioning`` span was open (its ``graph-download`` and the
host's partitioner below it): the device's idle share of initial
partitioning (``harness/phase_reduce.py``).  A part of ``driver_idle_s``,
which keeps it.  0 where the request opened no such span; left out
where the run has no phases."""

from perfbench.harness import phase_reduce

LAYER = "initial partitioning"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell

SCOPE = "initial-partitioning"


def read(run):
    reduced = phase_reduce.phases(run)
    if reduced is None:
        return None
    return sum(row["idle_s"] for path, row in reduced["spans"].items()
               if SCOPE in path.split("."))
