"""Device seconds of the launches enqueued under an ``lp-clustering``
span of the traced request or under a span below it
(``harness/phase_reduce.py``): label propagation clustering on every
level, whatever the rating engine.  Contraction is
``coarsening_device_s`` less this."""

from perfbench.harness import phase_reduce

LAYER = "coarsening"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell

SCOPE = "lp-clustering"


def read(run):
    reduced = phase_reduce.phases(run)
    if reduced is None:
        return None
    rows = [row for path, row in reduced["spans"].items()
            if SCOPE in path.split(".")]
    return sum(row["device_s"] for row in rows) if rows else None
