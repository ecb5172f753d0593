"""Moves of the host k-way FM that a commit undid in a request: the
tail of a batch's prefix past its best exact sub-prefix, where an earlier
batch of its round took a node, filled a block or changed the gains
(``native/fm.cpp``'s worker pool; one thread undoes none).  Median over the window's requests of the count summed
over a request's FM calls; 0 where no request called FM; left out where
the program keeps no FM account."""

from perfbench.layer_metrics import _fm_account

LAYER = "refinement"
UNIT = "count"
MOVES = "cut"
SOURCE = "program_counter"
CELLS = None  # every cell

COUNTER = "undone_moves"


def read(run):
    return _fm_account.window_median(COUNTER)
