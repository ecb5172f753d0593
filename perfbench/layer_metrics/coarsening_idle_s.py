"""Seconds of the traced request in which the device ran nothing and the
innermost open program span belonged to coarsening
(``harness/phase_reduce.py``)."""

from perfbench.harness import phase_reduce

LAYER = "coarsening"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return phase_reduce.layer_value(run, "coarsening", "idle_s")
