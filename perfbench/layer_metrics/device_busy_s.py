"""Union of the device-operation intervals inside the traced partition."""

LAYER = "device"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "device_trace"
CELLS = None  # every cell


def read(run):
    trace = run["trace"]
    return None if trace is None else trace["device_busy_s"]
