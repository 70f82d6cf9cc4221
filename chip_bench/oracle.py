"""The plain reference: exact BFS levels from numpy, independent of the
program.

The reference builds its own adjacency from the generated edge list and
runs a level-synchronous BFS per source (a copy of ``chip_smoke.py``'s
``numpy_bfs``, with the frontier de-duplicated by a boolean mark instead of
a sort). It takes nothing the program made.

The control breaks the configuration's guarantee (exact levels for every
source) the way a later change to the padded push might be tempted to:
each node's adjacency row is cut to its first ``CONTROL_ROW_CAP`` slots,
the cap the repository's paper dry-run puts on degrees.
"""
from __future__ import annotations

import numpy as np

CONTROL_ROW_CAP = 64


class Reference:
    """Adjacency and BFS of one deployment's edge list."""

    def __init__(self, n_nodes: int, src: np.ndarray, dst: np.ndarray,
                 row_cap: int | None = None):
        key = np.unique(np.asarray(src, np.int64) * n_nodes + dst)
        rows, cols = key // n_nodes, key % n_nodes
        if row_cap is not None:
            starts = np.searchsorted(rows, np.arange(n_nodes))
            keep = np.arange(len(rows)) - starts[rows] < row_cap
            rows, cols = rows[keep], cols[keep]
        self.n_nodes = n_nodes
        self.indptr = np.zeros(n_nodes + 1, np.int64)
        np.cumsum(np.bincount(rows, minlength=n_nodes), out=self.indptr[1:])
        self.indices = cols.astype(np.int64)
        self.degrees = np.diff(self.indptr)
        self._component = None

    def bfs(self, src: int) -> np.ndarray:
        """Level-synchronous BFS; -1 marks unreached nodes."""
        levels = np.full(self.n_nodes, -1, np.int32)
        levels[src] = 0
        frontier = np.asarray([src], np.int64)
        depth = 0
        while frontier.size:
            depth += 1
            starts = self.indptr[frontier]
            counts = self.indptr[frontier + 1] - starts
            offs = np.arange(int(counts.sum())) - np.repeat(
                np.cumsum(counts) - counts, counts)
            nbrs = self.indices[np.repeat(starts, counts) + offs]
            mark = np.zeros(self.n_nodes, bool)
            mark[nbrs] = True
            mark &= levels < 0
            frontier = np.flatnonzero(mark)
            levels[frontier] = depth
        return levels

    def traversed_edges(self, levels: np.ndarray) -> int:
        """Out-edges of the nodes a row reached (Graph500's count)."""
        return int(self.degrees[np.asarray(levels) >= 0].sum())

    def reached_edges(self, sources) -> int:
        """``traversed_edges`` of each source's exact row, summed. On a
        symmetric graph a source reaches exactly its component, so the
        count is read per component, found once."""
        if self._component is None:
            self._find_components()
        return int(self._component_edges[self._component[
            np.asarray(sources, np.int64)]].sum())

    def _find_components(self) -> None:
        rows = np.repeat(np.arange(self.n_nodes), self.degrees)
        if not np.array_equal(np.sort(rows * self.n_nodes + self.indices),
                              np.sort(self.indices * self.n_nodes + rows)):
            raise ValueError("reached_edges needs a symmetric graph")
        label = np.full(self.n_nodes, -1, np.int64)
        edges = []
        for v in range(self.n_nodes):
            if label[v] < 0:
                reached = self.bfs(v) >= 0
                label[reached] = len(edges)
                edges.append(int(self.degrees[reached].sum()))
        self._component = label
        self._component_edges = np.asarray(edges, np.int64)


def mismatches(reference: Reference, sources, rows) -> int:
    """Level entries of ``rows`` (one per source) that differ from the
    reference; a row of the wrong shape counts every entry as wrong."""
    rows = np.asarray(rows)
    if rows.shape != (len(sources), reference.n_nodes):
        return len(sources) * reference.n_nodes
    return int(sum(np.count_nonzero(row != reference.bfs(int(s)))
                   for s, row in zip(sources, rows)))
