"""The IFE operator (paper Listing 1/3): iterative frontier extension.

``run_ife`` is the single-chip serial engine (paper Listing 1). It is the unit
that morsel dispatching policies replicate/partition:

- 1T1S vmaps it over a per-device batch of sources (source morsels);
- nT1S/nTkS replace ``local_extend`` + MERGE with sharded extension and a
  frontier-union collective (see core/dispatcher.py);
- nTkMS runs it with a multi-source (lane) edge compute.

The FRONTIER_EXTENSION / OUTPUT phases of the paper's operator map to
``run_ife`` (extension, a ``lax.while_loop``) and the output-consumption
helpers below (``histogram_lengths``, ``reconstruct_paths``), which pipeline
results to downstream query operators.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..graph.csr import EllGraph
from .edge_compute import EDGE_COMPUTES, NO_PARENT
from .extend import ExtendCtx, ExtendSpec, as_operands, as_spec, make_backend


class IFEResult(NamedTuple):
    state: Any  # final edge-compute state pytree
    iterations: jax.Array  # int32, number of frontier extensions performed


def merge_identity(merge: str, contribution):
    return contribution


def run_ife(
    graph,
    sources: jax.Array,
    edge_compute: str = "sp_lengths",
    max_iters: int | None = None,
    extend="ell_push",
) -> IFEResult:
    """Run one IFE subroutine (one source morsel) to convergence.

    ``sources``: [k] int32 — for dense edge computes these all seed one shared
    frontier (a multi-source *query*); for msbfs_* computes sources[l] seeds
    lane l. Out-of-range ids are inert (empty lanes).

    ``graph``: a padded ``EllGraph`` (the seed engine: each iteration is
    the edge compute's own ``local_extend`` scatter over it; ``extend``
    must stay the push) or a ``GraphOperands`` bundle (see
    ``core.extend.build_operands``), whose ``extend`` selects the
    extension backend ("ell_push" | "ell_pull" | "block_mxu" | "dopt" or
    an ``ExtendSpec``); all choices are bit-identical in final state.
    """
    ec = EDGE_COMPUTES[edge_compute]
    spec = as_spec(extend)
    if isinstance(graph, EllGraph):
        if spec != ExtendSpec():
            raise ValueError(
                f"a bare EllGraph runs the push only (extend={extend!r}); "
                "build the graph with core.extend.build_operands"
            )
        return _run_loop(
            ec, graph.n_nodes, sources, max_iters,
            lambda state: ec.local_extend(graph, state),
        )
    ops = as_operands(graph)
    if spec.needs_rev and ops.rev is None:
        raise ValueError(f"extend={spec.backend!r} needs reverse operands; "
                         "build the graph with core.extend.build_operands")
    if spec.needs_binned and ops.rev_binned is None:
        raise ValueError(
            f"extend={spec.backend!r}/{spec.direction} needs degree-binned "
            "reverse operands; build the graph with "
            "core.extend.build_operands"
        )
    if spec.needs_blocks and ops.blocks is None:
        raise ValueError("extend='block_mxu' needs block operands; "
                         "build the graph with core.extend.build_operands")
    be = make_backend(spec)
    n = ops.n_nodes
    ctx = ExtendCtx(n_out=n)
    return _run_loop(
        ec, n, sources, max_iters,
        lambda state: ec.extend(be, ops, state, ctx),
    )


def _run_loop(ec, n: int, sources, max_iters, extend_fn) -> IFEResult:
    cap = jnp.int32(n if max_iters is None else max_iters)
    state0 = ec.init(n, sources)

    def cond(carry):
        state, it = carry
        return jnp.any(state.frontier != 0) & (it < cap)

    def body(carry):
        state, it = carry
        state = ec.apply(state, extend_fn(state), it)
        return state, it + 1

    state, iters = jax.lax.while_loop(cond, body, (state0, jnp.int32(0)))
    return IFEResult(state=state, iterations=iters)


@partial(jax.jit, static_argnames=("edge_compute", "max_iters", "extend"))
def run_ife_jit(graph, sources, edge_compute="sp_lengths", max_iters=None,
                extend="ell_push"):
    return run_ife(graph, sources, edge_compute, max_iters, extend)


def run_ife_batch(
    graph,
    source_batch: jax.Array,
    edge_compute: str = "sp_lengths",
    max_iters: int | None = None,
    extend="ell_push",
) -> IFEResult:
    """vmap over independent source morsels: [m] int32 -> batched states.

    This is the 1T1S inner loop: each morsel is an independent IFE run with
    unsynchronized private state (paper §3.1 'fast data structures without
    synchronization primitives').
    """
    fn = lambda s: run_ife(graph, s[None], edge_compute, max_iters, extend)
    return jax.vmap(fn)(source_batch)


def run_ife_scan(
    graph,
    source_batch: jax.Array,
    edge_compute: str = "sp_lengths",
    max_iters: int | None = None,
    extend="ell_push",
) -> IFEResult:
    """Sequential (lax.map) variant of run_ife_batch — the true 1T1S semantics
    (one morsel at a time per worker), used when per-source state does not fit
    m-way vmapped. Same results, lower peak memory, serial."""
    fn = lambda s: run_ife(graph, s[None], edge_compute, max_iters, extend)
    return jax.lax.map(fn, source_batch)


# ---------------------------------------------------------------------------
# OUTPUT phase (paper §4.1): consume IFE results.
# ---------------------------------------------------------------------------

def histogram_lengths(levels: jax.Array, max_len: int = 64) -> jax.Array:
    """RETURN len(p) consumption: histogram of path lengths (ignores -1/255)."""
    lv = levels.astype(jnp.int32).reshape(-1)
    valid = (lv >= 0) & (lv < max_len)
    return jnp.zeros((max_len,), jnp.int32).at[lv].add(
        valid.astype(jnp.int32), mode="drop"
    )


def reconstruct_paths(
    parents: jax.Array, dests: jax.Array, max_len: int
) -> jax.Array:
    """RETURN p consumption: walk parent pointers from each destination.

    parents: [n] int32 (NO_PARENT where unreached / at source).
    dests: [d] int32. Returns [d, max_len] int32 node ids padded with -1,
    ordered dest -> source.
    """

    def step(carry, _):
        cur = carry
        nxt = jnp.where(
            cur >= 0,
            parents.at[cur].get(mode="fill", fill_value=int(NO_PARENT)),
            NO_PARENT,
        )
        nxt = jnp.where(nxt == NO_PARENT, -1, nxt)
        return nxt, cur

    _, path = jax.lax.scan(step, dests.astype(jnp.int32), None, length=max_len)
    return jnp.swapaxes(path, 0, 1)


def validate_parents(
    levels: jax.Array, parents: jax.Array, sources: jax.Array
) -> jax.Array:
    """Invariant: every reached non-source v has a parent with
    level(parent) == level(v) - 1. Returns bool."""
    n = levels.shape[0]
    is_src = jnp.zeros((n,), jnp.bool_).at[sources].set(True, mode="drop")
    reached = (levels > 0) & ~is_src
    p = jnp.clip(parents, 0, n - 1)
    ok = jnp.where(reached, levels[p] == levels - 1, True)
    has_parent = jnp.where(reached, parents != NO_PARENT, True)
    return jnp.all(ok & has_parent)
