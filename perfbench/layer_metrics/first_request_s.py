"""Wall of the process's first ``compute_partition`` (the warm-up
partition) on the program's own clock: ``perf_counter`` at the two ends
of its ``kaminpar/request`` span, as the compile account notes them.  A
one-shot user's whole partitioning cost.  Left out where the program
keeps no such account."""

from perfbench.layer_metrics import _setup_account

LAYER = "driver"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return _setup_account.read(_setup_account.first_request_s)
