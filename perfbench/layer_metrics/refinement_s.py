"""Sum of the refiners' timer nodes (jet, lp-refinement, overload-balancer,
underload-balancer, and kway-fm, the host FM of ``strong``) wherever they
sit under partitioning, median over the run's untraced partitions."""

from perfbench.harness import timer_tree

LAYER = "refinement"
UNIT = "s"
MOVES = "partition_s"
SOURCE = "program_span"
CELLS = None  # every cell


def read(run):
    return timer_tree.median_total(run["trees"], timer_tree.REFINER_SCOPES)
