"""Seconds of the traced request in which the device ran nothing and the
innermost open program span belonged to the extend step
(``extend-partition``, ``extend-pull``; ``harness/phase_reduce.py``)."""

from perfbench.harness import phase_reduce

LAYER = "extend"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return phase_reduce.layer_value(run, "extend", "idle_s")
