"""Device seconds in sort instructions inside the traced partition, from
the trace (``harness/trace_reduce.py``): the absolute twin of
``sort_share``, as ``gather_scatter_s`` is of ``gather_scatter_share``."""

LAYER = "kernels"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "device_trace"
CELLS = None  # every cell


def read(run):
    trace = run["trace"]
    return None if trace is None else trace["class_s"]["sort"]
