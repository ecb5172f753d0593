"""Device seconds of the launches enqueued under a ``coarsening`` span of
the traced request (``harness/phase_reduce.py``: the ``run_id`` join)."""

from perfbench.harness import phase_reduce

LAYER = "coarsening"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return phase_reduce.layer_value(run, "coarsening", "device_s")
