"""Device seconds of the launches enqueued under a ``jet`` span of the
traced request (``harness/phase_reduce.py``)."""

from perfbench.harness import phase_reduce

LAYER = "refinement"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return phase_reduce.layer_value(run, "jet", "device_s")
