"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For 1000+-node depth scaling: stages live on a ``pipe`` mesh axis; the
schedule runs M microbatches through S stages in S+M-1 ticks. Each tick every
stage applies its layer block to its current microbatch, then activations
shift one stage forward via ``ppermute`` (compute/communication overlap is
XLA's async collective-permute on real ICI).

The stage function is user-provided (any (params, x) -> x), so the same
runner pipelines transformer groups, GNN blocks, or anything stackable.
Correctness contract (tested): output == serially applying all S stages to
every microbatch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_apply(
    mesh: Mesh,
    stage_params,  # pytree with leading dim S (stacked per-stage params)
    xs,  # [M, ...] microbatches
    stage_fn,  # (params_for_stage, x) -> x
    axis: str = "pipe",
):
    """Runs all M microbatches through S pipeline stages."""
    S = mesh.shape[axis]
    M = xs.shape[0]

    def worker(params_local, xs_local):
        # params_local: this stage's params (leading dim 1); xs_local: all
        # microbatches (replicated input; stage 0 feeds them in).
        params_me = jax.tree.map(lambda p: p[0], params_local)
        stage = lax.axis_index(axis)
        n_ticks = S + M - 1
        buf = jnp.zeros_like(xs_local[0])  # current activation
        outs = jnp.zeros_like(xs_local)

        def tick(t, carry):
            buf, outs = carry
            mb_in = t  # microbatch entering stage 0 at tick t
            feed = xs_local[jnp.clip(mb_in, 0, M - 1)]
            x = jnp.where(stage == 0, feed, buf)
            y = stage_fn(params_me, x)
            # active iff this stage holds microbatch (t - stage) in [0, M)
            mb_here = t - stage
            active = (mb_here >= 0) & (mb_here < M)
            y = jnp.where(active, y, buf)
            # last stage writes its finished microbatch
            write_idx = jnp.clip(mb_here, 0, M - 1)
            outs = jnp.where(
                active & (stage == S - 1),
                outs.at[write_idx].set(y),
                outs,
            )
            # shift activations forward one stage
            buf_next = lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)]
            )
            return buf_next, outs

        _, outs = lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # only the last stage's outs are valid; broadcast via masked psum
        outs = lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        worker,
        mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, xs)
