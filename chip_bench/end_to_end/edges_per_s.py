"""Traversed edges of every source row delivered in the window, over the
window. A row's traversed edges are the out-edges of the nodes it reached
(Graph500's count), taken from the reference's adjacency."""
from chip_bench.stats import rate


def read(ctx):
    return rate(ctx["traversed_edges"], ctx["seconds"]) or None
