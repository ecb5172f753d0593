"""Timer node partitioning.coarsening (LP clustering and contraction),
median over the run's untraced partitions."""

from perfbench.harness import timer_tree

LAYER = "coarsening"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return timer_tree.median_at(run["trees"], "partitioning.coarsening")
