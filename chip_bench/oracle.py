"""The plain reference: exact BFS levels from numpy, independent of the
program.

The reference builds its own adjacency from the generated edge list. Its
plain form, ``bfs``, is a level-synchronous BFS per source (a copy of
``chip_smoke.py``'s ``numpy_bfs``, with the frontier de-duplicated by a
boolean mark instead of a sort). The comparison runs its bit-parallel form,
``levels``: up to ``GROUP`` sources a pass, one bit of a ``uint64`` each,
pulled over the reference's own reverse adjacency; tests hold it to
``bfs`` row for row. The comparisons share their passes among
``THREADS`` threads: numpy releases the interpreter lock in the gathers,
reductions and bit operations, and each pass's count is its own, so the
sums do not depend on the threads. It takes nothing the program made.

The control breaks the configuration's guarantee (exact levels for every
source) the way a later change to the padded push might be tempted to:
each node's adjacency row is cut to its first ``CONTROL_ROW_CAP`` slots,
the cap the repository's paper dry-run puts on degrees.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CONTROL_ROW_CAP = 64
# sources of one pass of ``Reference.levels``: one bit of a uint64 each
GROUP = 64
THREADS = min(8, os.cpu_count() or 1)


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
        # reverse adjacency of the same (cut) arcs: in-neighbours by node
        in_degrees = np.bincount(cols, minlength=n_nodes)
        self._in_nbrs = rows[np.argsort(cols, kind="stable")]
        self._pulled = np.flatnonzero(in_degrees)
        self._pull_starts = (np.cumsum(in_degrees) - in_degrees)[self._pulled]
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

    def levels(self, sources) -> np.ndarray:
        """``bfs`` of every source, as an int32 [k, n] array: ``GROUP``
        sources a pass."""
        sources = np.asarray(sources, np.int64).ravel()
        out = np.empty((len(sources), self.n_nodes), np.int32)
        for g in range(0, len(sources), GROUP):
            out[g:g + GROUP] = self._levels_of_group(sources[g:g + GROUP])
        return out

    def _levels_of_group(self, sources: np.ndarray) -> np.ndarray:
        """Bit j of ``seen[v]`` says source j has reached v. Each level ORs
        the frontier's masks over every node's in-neighbours and keeps the
        bits not seen before. ``set_for[j, v]`` counts the levels (the
        sources' own, level 0, included) at whose end bit j of ``seen[v]``
        was set: a node first reached at level l counts ``depth + 1 - l``,
        an unreached one 0."""
        n, k = self.n_nodes, len(sources)
        seen = np.zeros(n, np.uint64)
        bits = np.left_shift(np.uint64(1), np.arange(k, dtype=np.uint64))
        np.bitwise_or.at(seen, sources, bits)
        frontier = seen
        set_for = np.zeros((-(-k // 8) * 8, n), np.uint8)
        depth = 0
        while True:
            if depth == np.iinfo(np.uint8).max:  # the next count passes uint8
                set_for = set_for.astype(np.int32)
            _add_bits(set_for, seen)
            reached = np.zeros(n, np.uint64)
            if self._pulled.size:
                reached[self._pulled] = np.bitwise_or.reduceat(
                    frontier[self._in_nbrs], self._pull_starts)
            frontier = reached & ~seen
            if not frontier.any():
                break
            seen = seen | frontier
            depth += 1
        level_of = np.arange(depth + 1, -1, -1, dtype=np.int32)
        level_of[0] = -1
        return level_of[set_for[:k]]

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


def _add_bits(counts: np.ndarray, words: np.ndarray) -> None:
    """``counts[j] += bit j of words``, for the rows ``counts`` has."""
    octets = np.ascontiguousarray(
        words.astype("<u8", copy=False).view(np.uint8).reshape(-1, 8).T)
    octets = octets[: len(counts) // 8]  # octet b holds bits 8b .. 8b + 7
    for bit in range(8):
        counts[bit::8] += (octets >> np.uint8(bit)) & np.uint8(1)


def mismatches(reference: Reference, sources, rows) -> int:
    """Level entries of ``rows`` (one per source) that differ from the
    reference; a row of the wrong shape counts every entry as wrong."""
    return mismatches_by_query(reference, [sources], [rows])[0]


def mismatches_by_query(reference: Reference, sources: list,
                        rows: list) -> list[int]:
    """``mismatches`` of each query (its sources, its rows), with the
    reference's rows made ``GROUP`` source rows a pass across queries."""
    n = reference.n_nodes
    wrong = [0] * len(sources)
    todo = []  # (query, row of it, source)
    for q, (s, r) in enumerate(zip(sources, rows)):
        s = np.asarray(s).ravel()
        if np.shape(r) != (len(s), n):
            wrong[q] = len(s) * n
        else:
            todo.extend((q, j, v) for j, v in enumerate(s))

    def check(g):
        block = todo[g:g + GROUP]
        want = reference.levels([v for _, _, v in block])
        return [(q, int(np.count_nonzero(np.asarray(rows[q][j]) != row)))
                for (q, j, _), row in zip(block, want)]

    for counts in _each_group(check, len(todo)):
        for q, count in counts:
            wrong[q] += count
    return wrong


def control_mismatches(reference: Reference, control: Reference,
                       sources: list) -> int:
    """Level entries in which ``control``'s rows of every source of
    ``sources`` (one array per query) differ from the reference's."""
    flat = np.asarray([v for s in sources for v in np.ravel(s)], np.int64)
    return sum(_each_group(lambda g: int(np.count_nonzero(
        control.levels(flat[g:g + GROUP])
        != reference.levels(flat[g:g + GROUP]))), len(flat)))


def _each_group(fn, n: int) -> list:
    """``fn(g)`` for g = 0, GROUP, 2 GROUP, ... below ``n``, in that order,
    ``THREADS`` at a time."""
    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(fn, range(0, n, GROUP)))
