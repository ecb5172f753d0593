"""Device seconds of the launches enqueued under a ``rating-sort2`` span
of the traced request: LP clustering on the levels whose resolved rating
engine was ``sort2`` (``harness/phase_reduce.py``; the program opens one
``rating-<engine>`` scope directly under every ``lp-clustering``).  0
where every level took another engine; left out where the program writes
no engine span at all."""

from perfbench.harness import phase_reduce

LAYER = "coarsening"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell

PARENT = "lp-clustering"
ENGINE_PREFIX = "rating-"


def read(run):
    reduced = phase_reduce.phases(run)
    if reduced is None:
        return None
    engines = {}  # scope name -> device seconds
    for path, row in reduced["spans"].items():
        names = path.split(".")
        if (len(names) >= 2 and names[-2] == PARENT
                and names[-1].startswith(ENGINE_PREFIX)):
            engines[names[-1]] = engines.get(names[-1], 0.0) + row["device_s"]
    return engines.get(ENGINE_PREFIX + "sort2", 0.0) if engines else None
