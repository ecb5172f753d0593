"""Timer nodes extend-partition, children included (ip-native, the
extractor), less any refiner node under one (none today), so that
refinement_s and extend_s never count a second twice.  0 where k needs
no doubling.  Median over the run's untraced partitions."""

from perfbench.harness import timer_tree

LAYER = "extend"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def _read(tree):
    if timer_tree.at(tree, "partitioning") is None:
        return None
    total = 0.0
    for node in timer_tree.find(tree, ("extend-partition",)):
        total += node["elapsed_s"] - timer_tree.total_s(
            node, timer_tree.REFINER_SCOPES, under="")
    return total


def read(run):
    return timer_tree.median_over(run["trees"], _read)
