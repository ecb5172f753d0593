"""Seconds of the traced request in which the device ran nothing and no
span of coarsening, refinement or extend was open: set-up, upload,
initial partitioning with its download of the coarsest graph, glue
between phases, the balance check, the final download, the output gate
(``harness/phase_reduce.py``)."""

from perfbench.harness import phase_reduce

LAYER = "driver"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return phase_reduce.layer_value(run, "driver", "idle_s")
