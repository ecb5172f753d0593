"""Seconds of the traced request in which the device ran nothing and the
innermost open program span was a refiner's
(``harness/phase_reduce.py``)."""

from perfbench.harness import phase_reduce

LAYER = "refinement"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return phase_reduce.layer_value(run, "refinement", "idle_s")
