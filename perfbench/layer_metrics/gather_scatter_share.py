"""Share of device busy time in gathers and scatters, from the trace:
own time of the ``kind=kCustom`` fusions this compiler wraps each of them
in (``harness/trace_reduce.py`` says how that was checked)."""

LAYER = "kernels"
UNIT = "%"
MOVES = "partition_s"
SOURCE = "device_trace"
CELLS = None  # every cell


def read(run):
    trace = run["trace"]
    if trace is None or not trace["device_busy_s"]:
        return None
    return (100.0 * trace["class_s"]["gather_scatter"]
            / trace["device_busy_s"])
