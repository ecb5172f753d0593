"""Refiner calls of a request (Jet, the two balancers) whose conn table
took the ``segment_sum`` scatter rather than the stream
(``ops/segments.conn_table_streams``, decided from k and the level's
padded shapes).  Median over the window's requests; 0 where every call
streamed; left out where the program keeps no shape account."""

from perfbench.layer_metrics import _shape_account

LAYER = "refinement"
UNIT = "count"
MOVES = "partition_s"
SOURCE = "program_counter"
CELLS = None  # every cell

COUNTER = "conn_scatter_calls"


def read(run):
    return _shape_account.window_median(COUNTER)
