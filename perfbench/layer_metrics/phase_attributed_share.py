"""Of the device seconds of the launches enqueued inside the traced
request's ``kaminpar/request`` span, the share whose launch found an
owner below it.  Guards the join (``run_id``, ``DoEnqueueProgram``) and
later code that launches outside every scope."""

from perfbench.harness import phase_reduce

LAYER = "device"
UNIT = "%"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    phases = phase_reduce.phases(run)
    return None if phases is None else phases["attributed_share"]
