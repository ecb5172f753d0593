"""Device seconds of the launches enqueued under ``extend-partition`` or
the ``extend-pull`` beside it, less those of a refiner span below, in the
traced request (``harness/phase_reduce.py``).  0 where k needs no
doubling."""

from perfbench.harness import phase_reduce

LAYER = "extend"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return phase_reduce.layer_value(run, "extend", "device_s")
