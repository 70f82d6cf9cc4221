"""Edge generators of the benchmark's deployments, kept with the benchmark.

These are copies of the program's ``powerlaw`` and ``erdos_renyi``
generators (same draws, same seeds), returning the edge list instead of a
CSR so that the program and the reference each build their own adjacency
from it. The program's generators may change; this yardstick may not.

A deployment's structure comes from its fixed ``graph_seed``; the run's
``--seed`` only relabels the nodes by a permutation. Every seed then sees
the same degree sequence, the same padded shapes and the same work, in
another order.
"""
from __future__ import annotations

import numpy as np


def powerlaw_edges(n_nodes: int, avg_degree: float, alpha: float, seed: int,
                   symmetric: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """Zipf-distributed endpoints: the social-network proxy."""
    rng = np.random.default_rng(seed)
    m = int(n_nodes * avg_degree)
    ranks = np.arange(1, n_nodes + 1, dtype=np.float64)
    probs = ranks ** (-alpha / 2.0)
    probs /= probs.sum()
    perm = rng.permutation(n_nodes)
    src = perm[rng.choice(n_nodes, size=m, p=probs)]
    dst = perm[rng.choice(n_nodes, size=m, p=probs)]
    if symmetric:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    return src, dst


def erdos_renyi_edges(n_nodes: int, avg_degree: float, seed: int,
                      symmetric: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """G(n, m) with m = n * avg_degree directed draws."""
    rng = np.random.default_rng(seed)
    m = int(n_nodes * avg_degree)
    src = rng.integers(0, n_nodes, size=m, dtype=np.int64)
    dst = rng.integers(0, n_nodes, size=m, dtype=np.int64)
    if symmetric:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    return src, dst


GENERATORS = {
    "powerlaw": lambda c: powerlaw_edges(
        c["n_nodes"], c["avg_degree_per_direction"], c["alpha"],
        c["graph_seed"], c["symmetric"]),
    "erdos_renyi": lambda c: erdos_renyi_edges(
        c["n_nodes"], c["avg_degree_per_direction"], c["graph_seed"],
        c["symmetric"]),
}


def structural_edges(config: dict) -> tuple[np.ndarray, np.ndarray]:
    """The deployment's directed edge list (duplicates included) in its
    fixed structural node ids."""
    return GENERATORS[config["generator"]](config)


def relabelling(config: dict, seed: int) -> np.ndarray:
    """The run's node ids: structural node u becomes ``relabel[u]``."""
    return np.random.default_rng([seed, 0]).permutation(config["n_nodes"])
