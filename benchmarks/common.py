"""Shared benchmark utilities: frontier traces, timing, CSV rows.

Methodology note: the paper's headline tables measure multi-thread
scalability on a 32-vcore Xeon. Here thread-scaling numbers come from a
discrete-event simulation of the morsel dispatching policies
(benchmarks/sched_sim.py) driven by per-frontier work traces from the real
graphs/engine; absolute work claims (scan sharing, visit factors, frontier
shapes) are counted directly on the engine. These modules run on whatever
backend JAX finds and record no device identity, so none of their wall
times is a TPU measurement; ``chip_smoke.py`` is the path that runs on the
chip.
"""
from __future__ import annotations

import collections
import time

import numpy as np

ROWS: list[tuple] = []


def emit(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}")


def time_fn(fn, *args, reps: int = 3, warmup: int = 1) -> float:
    """Median wall-time in microseconds (jax results block via tree leaves)."""
    import jax

    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def bfs_levels_np(csr, source: int) -> np.ndarray:
    """Vectorized numpy BFS: levels[-1] = unreached."""
    levels = np.full(csr.n_nodes, -1, dtype=np.int32)
    levels[source] = 0
    frontier = np.asarray([source], dtype=np.int64)
    l = 0
    indptr, indices = csr.indptr, csr.indices
    while frontier.size:
        starts = indptr[frontier]
        counts = indptr[frontier + 1] - starts
        total = int(counts.sum())
        if total == 0:
            break
        base = np.repeat(starts, counts)
        offs = np.arange(total) - np.repeat(
            np.cumsum(counts) - counts, counts
        )
        nbrs = indices[base + offs]
        new = np.unique(nbrs[levels[nbrs] < 0])
        if new.size == 0:
            break
        l += 1
        levels[new] = l
        frontier = new
    return levels


def frontier_trace(csr, source: int):
    """Per-level (n_active_nodes, edge_scan_work) for one IFE run.

    edge_scan_work = sum of out-degrees of the level's frontier — the
    paper's unit of frontier-morsel work (adjacency scans).
    """
    levels = bfs_levels_np(csr, source)
    degs = csr.degrees
    out = []
    lmax = levels.max()
    for l in range(lmax + 1):
        mask = levels == l
        out.append((int(mask.sum()), int(degs[mask].sum())))
    return out, levels


def union_trace(csr, sources) -> list:
    """MS-BFS union work: at iteration l, the nodes active in ANY lane.

    All lanes advance in lockstep (paper §3.4), so the shared edge scan per
    iteration covers the union frontier once instead of once per lane.
    """
    all_levels = np.stack([bfs_levels_np(csr, int(s)) for s in sources])
    degs = csr.degrees
    lmax = int(all_levels.max())
    out = []
    for l in range(lmax + 1):
        union = (all_levels == l).any(axis=0)
        out.append((int(union.sum()), int(degs[union].sum())))
    return out
