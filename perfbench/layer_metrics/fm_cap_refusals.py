"""Commits of the host k-way FM that the block-weight cap refused in a
request: a batch's prefix met a block that an earlier batch of its round
had filled (``native/fm.cpp``'s worker pool; one thread never meets
one).  Median over the window's requests of the count summed over
a request's FM calls; 0 where no request called FM; left out where the
program keeps no FM account."""

from perfbench.layer_metrics import _fm_account

LAYER = "refinement"
UNIT = "count"
MOVES = "cut"
SOURCE = "program_counter"
CELLS = None  # every cell

COUNTER = "cap_refusals"


def read(run):
    return _fm_account.window_median(COUNTER)
