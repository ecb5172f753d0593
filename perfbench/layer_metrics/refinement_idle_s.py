"""Seconds of the traced request in which the device ran nothing and the
innermost open program span was a refiner's or lay below one
(``harness/phase_reduce.py``); under ``strong`` nearly all of it is the
host FM's (``kway-fm``: ``fm_s`` and a few hundredths)."""

from perfbench.harness import phase_reduce

LAYER = "refinement"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return phase_reduce.layer_value(run, "refinement", "idle_s")
