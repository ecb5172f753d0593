"""XLA module executions on the device inside the traced partition."""

LAYER = "driver"
UNIT = "count"
MOVES = "partition_s"
SOURCE = "device_trace"
CELLS = None  # every cell


def read(run):
    trace = run["trace"]
    return None if trace is None else trace["launches"]
