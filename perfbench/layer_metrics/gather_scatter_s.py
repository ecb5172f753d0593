"""Device seconds in gathers and scatters inside the traced partition:
own time of the ``kind=kCustom`` fusions this compiler wraps each of them
in (``harness/trace_reduce.py``).  The absolute twin of
``gather_scatter_share``, whose numerator and denominator fall together
when an irregular pass becomes a streaming one."""

LAYER = "kernels"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "device_trace"
CELLS = None  # every cell


def read(run):
    trace = run["trace"]
    return None if trace is None else trace["class_s"]["gather_scatter"]
