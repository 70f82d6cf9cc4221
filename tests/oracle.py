"""Pure-numpy oracles for the recursive query engine."""
from __future__ import annotations

import collections

import numpy as np

from repro.graph.csr import CSRGraph


def bfs_levels(csr: CSRGraph, sources) -> np.ndarray:
    levels = np.full(csr.n_nodes, -1, dtype=np.int32)
    q = collections.deque()
    for s in np.atleast_1d(sources):
        s = int(s)
        if 0 <= s < csr.n_nodes and levels[s] < 0:
            levels[s] = 0
            q.append(s)
    while q:
        u = q.popleft()
        for v in csr.neighbors(u):
            v = int(v)
            if levels[v] < 0:
                levels[v] = levels[u] + 1
                q.append(v)
    return levels


def topk_dists(csr: CSRGraph, sources, k: int = 4) -> np.ndarray:
    """Weighted top-k loopy-path distances, [n, k] float32 sorted
    ascending (inf = fewer than k walks reach the node).

    Mirror of the engine's monotone full-Jacobi relax in float32: each
    round recomputes every node's k best from the seed row plus every
    in-edge candidate (parallel edges are distinct candidates), so the
    fixpoint is bit-identical to the ell_min_topk kernel's."""
    n = csr.n_nodes
    w = (
        csr.weights
        if csr.weights is not None
        else np.ones(csr.n_edges, np.float32)
    )
    ins: list[list] = [[] for _ in range(n)]
    for u in range(n):
        lo, hi = int(csr.indptr[u]), int(csr.indptr[u + 1])
        for v, wt in zip(csr.indices[lo:hi], w[lo:hi]):
            ins[int(v)].append((u, np.float32(wt)))
    seed = np.full((n, k), np.inf, np.float32)
    for s in np.atleast_1d(sources):
        s = int(s)
        if 0 <= s < n:
            seed[s, 0] = 0.0
    dists = seed.copy()
    while True:
        new = np.empty_like(dists)
        for v in range(n):
            cand = [seed[v]]
            for u, wt in ins[v]:
                cand.append((dists[u] + wt).astype(np.float32))
            new[v] = np.sort(np.concatenate(cand))[:k]
        if np.array_equal(new, dists):
            return dists
        dists = new


def ppr_mass(
    csr: CSRGraph, sources, alpha: float = 0.15, eps: float = 1e-4
) -> tuple[np.ndarray, np.ndarray, int]:
    """Personalized-PageRank residual diffusion: (mass, residual,
    iterations), all float32, mirroring the engine's synchronous push
    loop operation-for-operation (un-normalized unit seeds; nodes whose
    residual is <= eps hold their residual; out-degree-0 rows leak
    their pushed share, which is what guarantees termination)."""
    n = csr.n_nodes
    alpha = np.float32(alpha)
    eps = np.float32(eps)
    deg = np.maximum(
        (csr.indptr[1:] - csr.indptr[:-1]).astype(np.float32), 1.0
    ).astype(np.float32)
    residual = np.zeros(n, np.float32)
    for s in np.atleast_1d(sources):
        s = int(s)
        if 0 <= s < n:
            residual[s] = np.float32(1.0)
    mass = np.zeros(n, np.float32)
    frontier = np.where(residual > eps, residual, np.float32(0.0))
    it = 0
    while frontier.any():
        share = (
            (np.float32(1.0 - alpha) * frontier) / deg
        ).astype(np.float32)
        pushed = np.zeros(n, np.float32)
        for u in range(n):
            if share[u] != 0.0:
                lo, hi = int(csr.indptr[u]), int(csr.indptr[u + 1])
                np.add.at(pushed, csr.indices[lo:hi], share[u])
        residual = residual - frontier + pushed
        mass = mass + alpha * frontier
        frontier = np.where(residual > eps, residual, np.float32(0.0))
        it += 1
    return mass, residual, it


def pattern_counts(csr: CSRGraph, sources) -> tuple[np.ndarray, np.ndarray]:
    """(wedges, closed) int32 walk counts from the pooled sources: the
    number of length-2 and length-3 walks ending at each node (parallel
    edges are distinct walks) — exact matrix-power arithmetic. Sources
    seed a {0,1} indicator (duplicates collapse), like the engine."""
    n = csr.n_nodes
    x = np.zeros(n, np.int64)
    for s in np.atleast_1d(sources):
        s = int(s)
        if 0 <= s < n:
            x[s] = 1

    def push(v):
        out = np.zeros(n, np.int64)
        for u in range(n):
            if v[u]:
                lo, hi = int(csr.indptr[u]), int(csr.indptr[u + 1])
                np.add.at(out, csr.indices[lo:hi], v[u])
        return out

    hop1 = push(x)
    wedges = push(hop1)
    closed = push(wedges)
    return wedges.astype(np.int32), closed.astype(np.int32)


def sssp(csr: CSRGraph, sources) -> np.ndarray:
    """Bellman-Ford distances (weights required)."""
    import heapq

    assert csr.weights is not None
    dist = np.full(csr.n_nodes, np.inf, dtype=np.float64)
    heap = []
    for s in np.atleast_1d(sources):
        dist[int(s)] = 0.0
        heapq.heappush(heap, (0.0, int(s)))
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        lo, hi = csr.indptr[u], csr.indptr[u + 1]
        for v, w in zip(csr.indices[lo:hi], csr.weights[lo:hi]):
            nd = d + float(w)
            if nd < dist[int(v)] - 1e-12:
                dist[int(v)] = nd
                heapq.heappush(heap, (nd, int(v)))
    return dist


def push_step(csr: CSRGraph, op: str, values: np.ndarray,
              normalize: bool = False) -> np.ndarray:
    """One plain push over every edge u -> v of ``csr``: ``values[u]``
    combined into ``out[v]``. ``op``: "or" (bool or [n, L] uint8 lanes),
    "min_parent" (min u whose ``values[u]`` is set, per lane when 2-D;
    2**31 - 1 where none), "min_dist" (min of ``values[u] + w(u, v)``,
    unit weights when the CSR has none) or "sum" (``values[u]``, divided
    by u's out-degree with ``normalize``)."""
    n = csr.n_nodes
    src, dst = csr.edge_list()
    src, dst = src.astype(np.int64), dst.astype(np.int64)
    values = np.asarray(values)
    if op == "or":
        out = np.zeros(values.shape, values.dtype)
        np.maximum.at(out, dst, values[src])
        return out
    if op == "min_parent":
        ids = np.arange(n, dtype=np.int64).reshape((n,) + (1,) * (values.ndim - 1))
        cand = np.where(values != 0, ids, 2**31 - 1)
        out = np.full(values.shape, 2**31 - 1, np.int64)
        np.minimum.at(out, dst, cand[src])
        return out
    if op == "min_dist":
        w = (np.ones(len(src), np.float32) if csr.weights is None
             else csr.weights)
        out = np.full(n, np.inf, np.float32)
        np.minimum.at(out, dst, (values[src] + w).astype(np.float32))
        return out
    if op == "sum":
        v = values.astype(np.float64)
        if normalize:
            v = v / np.maximum(csr.degrees, 1)
        out = np.zeros(n, np.float64)
        np.add.at(out, dst, v[src])
        return out
    raise ValueError(op)
