"""1 - device_busy_s / the traced partition's wall: where it is high the
host sets the pace."""

LAYER = "device"
UNIT = "%"
MOVES = "partition_s"
SOURCE = "device_trace"
CELLS = None  # every cell


def read(run):
    trace, wall = run["trace"], run["traced_wall_s"]
    if trace is None or not wall:
        return None
    return 100.0 * (1.0 - trace["device_busy_s"] / wall)
