"""Share of device busy time in sort instructions, from the trace."""

LAYER = "kernels"
UNIT = "%"
MOVES = "partition_s"
SOURCE = "device_trace"
CELLS = None  # every cell


def read(run):
    trace = run["trace"]
    if trace is None or not trace["device_busy_s"]:
        return None
    return 100.0 * trace["class_s"]["sort"] / trace["device_busy_s"]
