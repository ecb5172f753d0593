"""Coarse levels a request's coarsener built: the hierarchy's depth
under ``deep`` and ``kway``, each level one round of LP clustering and
contraction, and one more refiner pass on the way up.  Median over the
window's requests; left out where the program keeps no shape account."""

from perfbench.layer_metrics import _shape_account

LAYER = "coarsening"
UNIT = "count"
MOVES = "partition_s"
SOURCE = "program_counter"
CELLS = None  # every cell

COUNTER = "coarsening_levels"


def read(run):
    return _shape_account.window_median(COUNTER)
