"""Timer node partitioning.initial-partitioning (host), median over the
run's untraced partitions."""

from perfbench.harness import timer_tree

LAYER = "initial partitioning"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return timer_tree.median_at(run["trees"],
                                "partitioning.initial-partitioning")
