"""Reading the program's ``GLOBAL_TIMER`` scope tree.

The tree is on with program telemetry off and is rebuilt by every
``compute_partition``.  Dispatch is asynchronous: a phase's device work
is charged to the scope in which the host next reads a result back, so a
phase wall is a host-clock span, not device time."""

from __future__ import annotations

from statistics import median

#: the scopes of the refinement layer, wherever they sit in the tree;
#: ``kway-fm`` is the host k-way FM of ``strong`` (the device idles in it)
REFINER_SCOPES = ("jet", "lp-refinement", "overload-balancer",
                  "underload-balancer", "kway-fm")


def snapshot(node) -> dict:
    """A ``TimerNode`` as plain data:
    ``{"elapsed_s", "count", "children": {name: ...}}``."""
    return {"elapsed_s": float(node.elapsed), "count": int(node.count),
            "children": {name: snapshot(child)
                         for name, child in node.children.items()}}


def at(tree: dict, path: str):
    """The node at a dotted path below ``tree`` ("" is ``tree``), or None."""
    node = tree
    for name in filter(None, path.split(".")):
        node = node["children"].get(name)
        if node is None:
            return None
    return node


def find(tree: dict, names, under: str = "partitioning") -> list:
    """Every node below ``under`` whose name is in ``names``, outermost
    only (a match's own subtree is not searched again)."""
    start = at(tree, under)
    found = []

    def walk(node: dict) -> None:
        for name, child in node["children"].items():
            if name in names:
                found.append(child)
            else:
                walk(child)

    if start is not None:
        walk(start)
    return found


def total_s(tree: dict, names, **kw) -> float:
    return sum(node["elapsed_s"] for node in find(tree, names, **kw))


def median_over(trees: list, fn):
    """Median of ``fn(tree)`` over the trees, leaving out the trees for
    which it is None; None where nothing is left."""
    values = [fn(tree) for tree in trees]
    values = [v for v in values if v is not None]
    return median(values) if values else None


def median_at(trees: list, path: str):
    """Median seconds of the node at ``path``; None where no tree has it."""
    def one(tree):
        node = at(tree, path)
        return None if node is None else node["elapsed_s"]
    return median_over(trees, one)


def median_total(trees: list, names):
    """Median over the trees of the summed seconds of the nodes named
    ``names`` under ``partitioning``: 0 where a partition ran none of
    them, None where no tree holds a partition."""
    def one(tree):
        return None if at(tree, "partitioning") is None else total_s(tree, names)
    return median_over(trees, one)


def render(tree: dict, depth: int = 0) -> str:
    lines = []
    for name, child in tree["children"].items():
        lines.append(f"{'  ' * depth}{name}: {child['elapsed_s']:.4f} s"
                     + (f" ({child['count']}x)" if child["count"] > 1 else ""))
        sub = render(child, depth + 1)
        if sub:
            lines.append(sub)
    return "\n".join(lines)
