"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window, in 10^6 bytes: what decides which graphs fit the chip; past its
16 GB the program's memory ladder engages and ``partition_s`` pays.  Not
an end-to-end metric because it takes one of two values by seed
(597.8 / 619.6 MB on ``rmat-s16.k16``, my chip run, PR 22), and no bound
fits both a set of runs that holds both values and one that does not."""

LAYER = "device"
UNIT = "MB"
MOVES = "partition_s"
SOURCE = "program_counter"
CELLS = None  # every cell


def read(run):
    return run["memory_peak_bytes"] / 1e6
