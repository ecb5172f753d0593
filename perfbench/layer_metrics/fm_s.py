"""Host seconds of the ``kway-fm`` timer nodes under partitioning,
wherever they sit: the host k-way FM refiner of the ``strong`` preset,
with its read-back of the level (``graph-download``), the engine
(``fm-native`` or ``fm-numpy``) and the labels going back up
(``partition-upload``); the device runs nothing meanwhile.  Median over
the run's untraced partitions; 0.0 where no request called FM.
``refinement_s`` holds these seconds too (``kway-fm`` is one of
``harness/timer_tree.REFINER_SCOPES``)."""

from perfbench.harness import timer_tree

LAYER = "refinement"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell

SCOPE = "kway-fm"


def read(run):
    return timer_tree.median_total(run["trees"], (SCOPE,))
