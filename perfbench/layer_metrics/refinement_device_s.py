"""Device seconds of the launches enqueued under a refiner span (``jet``,
``lp-refinement``, ``overload-balancer``, ``underload-balancer``,
``kway-fm``: the host FM's few microseconds of read-back and upload) of
the traced request, wherever it sits (``harness/phase_reduce.py``)."""

from perfbench.harness import phase_reduce

LAYER = "refinement"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return phase_reduce.layer_value(run, "refinement", "device_s")
