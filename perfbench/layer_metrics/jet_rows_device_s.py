"""Device seconds of the launches enqueued under a ``jet-rows`` span of
the traced request: Jet on the levels whose iteration prunes its
candidates to a row buffer and runs the afterburner over that buffer,
the large-graph side of the program's ``1 << 22`` edge-slot gate
(``harness/phase_reduce.py``; the program opens one ``jet-<path>`` scope
directly under every ``jet``: ``jet-rows``, ``jet-edges`` or ``jet-lp``).
The edge-wide iterations are ``jet_device_s`` less this.  0 where no
call took the rows path; left out where the program writes no path span
at all."""

from perfbench.harness import phase_reduce

LAYER = "refinement"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell

PARENT = "jet"
PATH_PREFIX = "jet-"


def read(run):
    reduced = phase_reduce.phases(run)
    if reduced is None:
        return None
    paths = {}  # scope name -> device seconds
    for path, row in reduced["spans"].items():
        names = path.split(".")
        if (len(names) >= 2 and names[-2] == PARENT
                and names[-1].startswith(PATH_PREFIX)):
            paths[names[-1]] = paths.get(names[-1], 0.0) + row["device_s"]
    return paths.get(PATH_PREFIX + "rows", 0.0) if paths else None
