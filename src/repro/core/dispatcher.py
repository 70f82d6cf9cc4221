"""Morsel dispatcher (paper §4.3) — policy → shard_map program.

The paper's ``grabSrcMorselIfNecessary`` hands morsels to threads dynamically;
SPMD TPUs get a *static* schedule instead: source morsels are a sharded array
(one shard per source-axis coordinate), frontier morsels are the graph row
partition, and each device runs the IFE while_loop over its local morsels
(``lax.map`` = the paper's "sticky" worker: it finishes a source morsel before
grabbing the next). Collectives run only over the graph axes, so source groups
iterate independently — divergent per-morsel trip counts across source shards
are safe by construction.

Two engine flavors realize the paper's *hybrid* policy at runtime (§5.4,
driven by ``repro.runtime.scheduler``):

- ``build_engine(..., sync="shard")`` — phase 1: nTkS where the convergence
  check reduces over the graph axes only, so a source-shard group whose
  morsels have all converged exits its while_loop immediately instead of
  burning inert iterations until the globally slowest morsel finishes.
- ``build_resume_engine`` — phase 2: surviving (unconverged) morsels are
  re-dispatched with their saved state under nT1S frontier parallelism:
  every device cooperates on one morsel's frontier at a time, picking up
  at the iteration counter where phase 1 stopped.
- ``build_gang_resume_engine`` — batched phase 2: when more than one morsel
  survives, the survivors are ganged into a single multi-frontier resume
  (one while_loop, per-survivor convergence masks, frontiers lane-packed so
  one adjacency scan serves the gang) instead of draining one-at-a-time
  under ``lax.map``; works in both the replicated and sharded state layouts.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graph.csr import CSRGraph, EllGraph, ShardedBlocks
from .collectives import gang_merge_scatter, merge_contribution, merge_scatter
from .edge_compute import EDGE_COMPUTES
from .extend import (
    STATS_WIDTH,
    ExtendCtx,
    ExtendSpec,
    GraphOperands,
    as_operands,
    as_spec,
    build_operands,
    frontier_stats,
    make_backend,
    operand_stream,
)
from .ife import IFEResult
from .policies import MorselPolicy


def _axes_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) if axes else 1


def _flat_axis_index(axes: tuple[str, ...]):
    """Flattened coordinate over ``axes`` (major-to-minor = tuple order,
    matching how PartitionSpec((a0, a1)) tiles a dimension)."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def pad_sources(
    sources: np.ndarray, shards: int, lanes: int, inert_id: int
) -> np.ndarray:
    """[(s,)] -> [n_morsels_padded, lanes]; pad entries get ``inert_id``
    (>= n_nodes ⇒ empty lanes, zero-iteration morsels)."""
    s = np.asarray(sources, dtype=np.int32).reshape(-1)
    n_morsels = -(-len(s) // lanes)
    n_morsels = -(-n_morsels // shards) * shards
    out = np.full((n_morsels * lanes,), inert_id, dtype=np.int32)
    out[: len(s)] = s
    return out.reshape(n_morsels, lanes)


@dataclasses.dataclass(frozen=True)
class QueryEngine:
    """A compiled recursive-query executor for one (mesh, policy, graph-shape,
    edge-compute, extension-backend) combination — the paper's IFE physical
    operator."""

    mesh: Mesh
    policy: MorselPolicy
    edge_compute: str
    n_nodes_padded: int
    max_iters: int
    fn: Any  # jitted shard_map program
    extend: ExtendSpec = ExtendSpec()

    def _coerce(self, graph):
        """Accept an EllGraph or any GraphOperands bundle and hand ``fn``
        exactly the operand structure its in_specs declare (push engines
        keep the historical bare-EllGraph calling convention)."""
        return strip_operands(self.extend, as_operands(graph))

    def __call__(self, graph, *args) -> IFEResult:
        """Static/phase-1 engines: ``engine(graph, source_morsels)``.
        Resume engines: ``engine(graph, state0, it0)``."""
        return self.fn(self._coerce(graph), *args)


def strip_operands(spec: ExtendSpec, ops: GraphOperands):
    """Exactly the operands ``spec`` scans (push engines keep the
    historical bare-EllGraph calling convention) — the structure shard_map
    in_specs are derived from, so treedefs always match."""
    if not (spec.needs_rev or spec.needs_binned or spec.needs_blocks):
        return ops.fwd
    if spec.needs_rev and ops.rev is None:
        raise ValueError(
            f"engine extend={spec.backend}/{spec.direction} needs reverse "
            "operands; use prepare_graph(..., extend=spec)"
        )
    if spec.needs_binned and ops.rev_binned is None:
        raise ValueError(
            f"engine extend={spec.backend}/{spec.direction} needs "
            "degree-binned reverse operands; use "
            "prepare_graph(..., extend=spec)"
        )
    if spec.needs_binned_pack and ops.rev_binned_pack is None:
        raise ValueError(
            f"engine extend={spec.backend}/{spec.direction} needs the "
            "fused-kernel binned operand pack; use "
            "prepare_graph(..., extend=spec)"
        )
    if spec.needs_blocks and ops.blocks is None:
        raise ValueError(
            "engine extend=block_mxu needs block operands; use "
            "prepare_graph(..., extend=spec)"
        )
    return GraphOperands(
        fwd=ops.fwd,
        rev=ops.rev if spec.needs_rev else None,
        rev_binned=ops.rev_binned if spec.needs_binned else None,
        rev_binned_pack=(
            ops.rev_binned_pack if spec.needs_binned_pack else None
        ),
        blocks=ops.blocks if spec.needs_blocks else None,
    )


def _operand_specs(spec: ExtendSpec, ga: tuple[str, ...], operands=None):
    """shard_map in_specs for the operand bundle an engine scans.

    Every operand leaf shards its leading (row / stacked-shard) axis over
    the graph axes and replicates the rest. When the actual ``operands``
    bundle is given the spec pytree is derived from its stripped
    structure leaf-by-leaf — required for binned slabs, whose bucket
    count (treedef) is graph-dependent; the hand-built fallback keeps the
    historical operand-free ``build_engine`` calling convention alive for
    specs with graph-independent treedefs."""
    row_leaf = lambda x: P(ga if ga else None, *(None,) * (x.ndim - 1))
    if operands is not None:
        return jax.tree.map(row_leaf, strip_operands(spec, as_operands(operands)))
    if spec.needs_binned:
        raise ValueError(
            "binned-pull engines need the operand bundle to derive "
            "shard_map specs (slab count is graph-dependent); pass "
            "operands=... to build_engine/build_resume_engine"
        )
    ell = EllGraph(
        indices=P(ga if ga else None, None),
        degrees=P(ga if ga else None),
        weights=None,
    )
    if not (spec.needs_rev or spec.needs_blocks):
        return ell
    blocks = None
    if spec.needs_blocks:
        blocks = ShardedBlocks(
            blocks=P(ga if ga else None, None, None, None),
            block_rows=P(ga if ga else None, None),
            block_cols=P(ga if ga else None, None),
        )
    return GraphOperands(
        fwd=ell, rev=ell if spec.needs_rev else None, blocks=blocks
    )


def _stats_bin_widths(ops: GraphOperands):
    """Per-local-row binned slab widths for the stats tap's pull-cost
    columns, derived from the CALL-TIME operands (inv is data, not shape:
    a same-structure graph may bin rows differently); ``None`` (the tap
    records ``-1``) when the engine scans no binned slabs."""
    if ops.rev_binned is None:
        return None
    bn = ops.rev_binned
    wvec = jnp.concatenate([
        jnp.full((s.shape[-2],), s.shape[-1], jnp.float32)
        for s in bn.slabs
    ])  # slab width per binned position (this shard's slice)
    return wvec[bn.inv[0]]


def build_engine(
    mesh: Mesh,
    policy: MorselPolicy,
    edge_compute: str,
    n_nodes_padded: int,
    max_iters: int | None = None,
    state_layout: str = "replicated",
    sync: str = "global",
    extend="ell_push",
    operands=None,
    collect_stats: bool = False,
) -> QueryEngine:
    """``operands``: the graph's GraphOperands bundle (or any graph whose
    stripped structure matches what the engine will be called with). Needed
    to derive shard_map specs for graph-dependent operand treedefs (binned
    pull slabs); optional for the other backends.

    ``collect_stats``: the online-policy sample tap. The engine's fn
    returns ``(IFEResult, stats)`` where ``stats[m, cap, STATS_WIDTH]``
    holds each morsel's per-iteration ``extend.frontier_stats`` record —
    the Beamer predicate's inputs (n_f, m_f, m_u) plus the binned-pull
    scan cost and measured-cost columns (-1 when the operand bundle
    carries no binned slabs) — written into the while_loop carry at the
    state about to extend (row ``it`` is the it-th iteration's sample;
    rows at/after the morsel's trip count stay zero). A pure readout:
    result state is bit-identical to the untapped engine. The adaptive
    scheduler drains these samples into its in-flight
    ``DirectionThresholds`` refit. The resume/gang builders take the
    same flag, so a survivor's post-budget tail feeds the learners too.

    ``state_layout``:

    - "replicated" — paper-faithful: every device holds the FULL per-node
      state of the morsels it works on ("every thread sees the whole next
      frontier"); graph-axis merge is an OR/MIN all-reduce.
    - "sharded" — beyond-paper memory optimization (DESIGN.md §6): each
      device holds only its graph partition's state rows; the merge is an
      OR/MIN *reduce-scatter* (half the wire bytes of allgather+fold, and
      per-device state drops from O(n) to O(n/K) — what lets Graph500-28
      scale MS-BFS morsels fit a 16 GB chip).

    ``sync``:

    - "global" — the loop condition (the paper's checkIfFrontierFinished
      pipeline break) is reduced over source AND graph axes: every device
      runs the same trip count; source shards whose morsels converged early
      burn inert iterations (empty frontier => no-op) until the slowest
      morsel finishes.
    - "shard" — the condition is reduced over the graph axes only. Each
      source-shard group exits as soon as ITS morsels converge. Divergent
      trip counts across source groups are only deadlock-free when every
      collective in the body rendezvous per replica group
      (psum/pmin/all_gather do; a ppermute ring does NOT — it lowers to
      one CollectivePermute spanning every device), so this builder
      degrades any ring flavor (``or_impl="ring"`` unions, the min/sum
      reduce-scatter merges of the sharded layout) to allgather. This is
      phase 1 of the adaptive hybrid: the saved inert iterations are
      handed to ``build_resume_engine`` instead of wasted.
    """
    ec = EDGE_COMPUTES[edge_compute]
    spec = as_spec(extend)
    ga = policy.graph_axes
    sa = policy.source_axes
    cap = int(max_iters if max_iters is not None else n_nodes_padded)
    n = n_nodes_padded
    sharded = state_layout == "sharded" and bool(ga)
    if sync not in ("global", "shard"):
        raise ValueError(f"unknown sync mode: {sync}")
    if not ga:
        sync_axes = ()
    elif sync == "global":
        sync_axes = tuple(sa) + tuple(ga)
    else:
        sync_axes = tuple(ga)
    # sync="shard" lets source-shard groups exit the while_loop at
    # different trip counts. psum/pmin/all_gather rendezvous per replica
    # group, so the divergence is safe — but ppermute lowers to ONE
    # CollectivePermute spanning every device, and the group still
    # iterating deadlocks waiting for the group that already exited. Any
    # ring collective inside the body (or_impl="ring" unions, the
    # min/sum reduce-scatter merges of the sharded layout) must degrade
    # to its allgather flavor here.
    divergent = sync == "shard" and any(
        int(mesh.shape[a]) > 1 for a in sa
    )
    or_impl = (
        "allgather"
        if divergent and policy.or_impl == "ring"
        else policy.or_impl
    )
    scatter_impl = "allgather" if divergent else "ring"

    def worker(graph_in, sources_local: jax.Array):
        ops = as_operands(graph_in)
        be = make_backend(spec)
        rows_local = ops.fwd.indices.shape[0]
        offset = (
            _flat_axis_index(ga) * rows_local if ga else None
        )
        ctx = ExtendCtx(
            n_out=n,
            row_offset=None if sharded else offset,
            row_base=offset if sharded else None,
            axes=tuple(ga),
            or_impl=or_impl,
            sharded=sharded,
        )
        bw = _stats_bin_widths(ops) if collect_stats else None

        def one_morsel(srcs):
            if sharded:
                # init only this shard's rows; out-of-shard sources become
                # the inert id rows_local (mode="drop" scatters vanish)
                local_srcs = jnp.where(
                    (srcs >= offset) & (srcs < offset + rows_local),
                    srcs - offset,
                    rows_local,
                )
                state0 = ec.init(rows_local, local_srcs)
            else:
                state0 = ec.init(n, srcs)

            def cond(carry):
                state, it = carry[0], carry[1]
                active = jnp.any(state.frontier != 0)
                if sync_axes:
                    active = (
                        lax.psum(active.astype(jnp.int32), sync_axes) > 0
                    )
                return active & (it < cap)

            def body(carry):
                state, it = carry[0], carry[1]
                if collect_stats:
                    rec = frontier_stats(ops, state, ctx, bin_widths=bw)
                    stats = lax.dynamic_update_slice_in_dim(
                        carry[2], rec[None, :], it, axis=0
                    )
                contribution = ec.extend(be, ops, state, ctx)
                if sharded:
                    merged = merge_scatter(
                        ec.MERGE, contribution, ga, or_impl,
                        impl=scatter_impl,
                    )
                else:
                    merged = merge_contribution(
                        ec.MERGE, contribution, ga, or_impl
                    )
                out = (ec.apply(state, merged, it), it + 1)
                return out + ((stats,) if collect_stats else ())

            init = (state0, jnp.int32(0))
            if collect_stats:
                init = init + (
                    jnp.zeros((cap, STATS_WIDTH), jnp.float32),
                )
            carry = lax.while_loop(cond, body, init)
            res = IFEResult(state=carry[0], iterations=carry[1])
            return (res, carry[2]) if collect_stats else res

        return lax.map(one_morsel, sources_local)

    g_specs = _operand_specs(spec, ga, operands)
    src_spec = P(sa if sa else None, None)
    if sharded:
        # state rows live on the graph axes: leaves are [morsel, rows, ...]
        lanes = getattr(ec, "LANES", 0)
        probe = jax.eval_shape(
            lambda: ec.init(8, jnp.zeros((max(lanes, 1),), jnp.int32))
        )
        state_spec = jax.tree.map(
            lambda _: P(sa if sa else None, ga), probe
        )
        out_spec = IFEResult(
            state=state_spec, iterations=P(sa if sa else None)
        )
    else:
        out_spec = P(sa if sa else None)
    if collect_stats:
        # stats stack over morsels like iterations: [m, cap, STATS_WIDTH]
        out_spec = (out_spec, P(sa if sa else None))
    fn = jax.jit(
        jax.shard_map(
            worker,
            mesh=mesh,
            in_specs=(g_specs, src_spec),
            out_specs=out_spec,
            check_vma=False,
        )
    )
    return QueryEngine(
        mesh=mesh,
        policy=policy,
        edge_compute=edge_compute,
        n_nodes_padded=n,
        max_iters=cap,
        fn=fn,
        extend=spec,
    )


def build_resume_engine(
    mesh: Mesh,
    policy: MorselPolicy,
    edge_compute: str,
    n_nodes_padded: int,
    max_iters: int | None = None,
    extend="ell_push",
    operands=None,
    collect_stats: bool = False,
) -> QueryEngine:
    """Phase-2 (re-dispatch) engine of the adaptive hybrid.

    Takes morsels *mid-flight*: instead of source ids it consumes a stacked
    replicated state pytree (leaves ``[m, n_pad, ...]``) plus per-morsel
    iteration counters ``it0 [m]``, and continues each morsel's IFE loop from
    ``it0`` under ``policy``'s (typically nT1S: graph over ALL mesh axes)
    frontier parallelism. Because BFS-style edge computes are deterministic
    functions of (state, iteration), resuming is bit-identical to having run
    the whole query under one engine. Morsels whose frontier is already
    empty are inert (zero-trip while_loop), so callers may pad the morsel
    batch freely to stabilize trace shapes.

    ``collect_stats``: same tap as ``build_engine`` — ``fn`` returns
    ``(IFEResult, stats)`` with ``stats[m, cap, STATS_WIDTH]``; each
    resumed iteration's record lands at its ABSOLUTE iteration row
    (``it``, which starts at ``it0``), so rows below ``it0`` stay zero
    and phase-1/phase-2 samples for a morsel never collide.

    The returned engine's ``fn`` signature is ``fn(graph, state0, it0)``.
    """
    ec = EDGE_COMPUTES[edge_compute]
    spec = as_spec(extend)
    ga = policy.graph_axes
    sa = policy.source_axes
    if sa:
        raise ValueError(
            "resume engine re-dispatches under frontier parallelism; "
            f"policy must not shard sources (got source_axes={sa})"
        )
    cap = int(max_iters if max_iters is not None else n_nodes_padded)
    sync_axes = tuple(ga)

    def worker(graph_in, state0, it0):
        ops = as_operands(graph_in)
        be = make_backend(spec)
        rows_local = ops.fwd.indices.shape[0]
        offset = _flat_axis_index(ga) * rows_local if ga else None
        ctx = ExtendCtx(
            n_out=n_nodes_padded,
            row_offset=offset,
            axes=tuple(ga),
            or_impl=policy.or_impl,
        )
        bw = _stats_bin_widths(ops) if collect_stats else None

        def one_morsel(args):
            state_m, it_m = args

            def cond(carry):
                state, it = carry[0], carry[1]
                active = jnp.any(state.frontier != 0)
                if sync_axes:
                    active = (
                        lax.psum(active.astype(jnp.int32), sync_axes) > 0
                    )
                return active & (it < cap)

            def body(carry):
                state, it = carry[0], carry[1]
                if collect_stats:
                    rec = frontier_stats(ops, state, ctx, bin_widths=bw)
                    stats = lax.dynamic_update_slice_in_dim(
                        carry[2], rec[None, :], it, axis=0
                    )
                contribution = ec.extend(be, ops, state, ctx)
                merged = merge_contribution(
                    ec.MERGE, contribution, ga, policy.or_impl
                )
                out = (ec.apply(state, merged, it), it + 1)
                return out + ((stats,) if collect_stats else ())

            init = (state_m, it_m)
            if collect_stats:
                init = init + (
                    jnp.zeros((cap, STATS_WIDTH), jnp.float32),
                )
            carry = lax.while_loop(cond, body, init)
            res = IFEResult(state=carry[0], iterations=carry[1])
            return (res, carry[2]) if collect_stats else res

        return lax.map(one_morsel, (state0, it0))

    g_specs = _operand_specs(spec, ga, operands)
    # state/it0 replicated in, outputs replicated (post-merge state is
    # identical on every device of the graph group)
    out_spec = IFEResult(state=P(), iterations=P())
    if collect_stats:
        out_spec = (out_spec, P())
    fn = jax.jit(
        jax.shard_map(
            worker,
            mesh=mesh,
            in_specs=(g_specs, P(), P()),
            out_specs=out_spec,
            check_vma=False,
        )
    )
    return QueryEngine(
        mesh=mesh,
        policy=policy,
        edge_compute=edge_compute,
        n_nodes_padded=n_nodes_padded,
        max_iters=cap,
        fn=fn,
        extend=spec,
    )


def build_gang_resume_engine(
    mesh: Mesh,
    policy: MorselPolicy,
    edge_compute: str,
    n_nodes_padded: int,
    max_iters: int | None = None,
    extend="ell_push",
    operands=None,
    state_layout: str = "replicated",
    collect_stats: bool = False,
) -> QueryEngine:
    """Gang-scheduled phase-2 (re-dispatch) engine of the adaptive hybrid.

    Where ``build_resume_engine`` drains survivors one-morsel-at-a-time
    (``lax.map`` is a sequential scan: morsel s+1's while_loop starts only
    after morsel s converges — frontier-level serialization, the exact
    failure mode the hybrid policy exists to avoid), this engine resumes
    the WHOLE survivor batch under ONE while_loop:

    - State arrives stacked ``[S_pad, ...]`` (pow2-padded by the caller for
      stable trace shapes; all-zero pad morsels are inert) plus per-morsel
      iteration counters ``it0 [S_pad]``.
    - Each iteration runs ONE batched multi-frontier extension
      (``ec.gang_extend``): dense survivors are repacked as MS-BFS lanes
      (``core.msbfs.gang_pack_lanes``) so a single shared adjacency scan
      serves the gang, and lane morsels fold into one ``[rows, S*64]``
      tensor.
    - Per-survivor convergence masks keep the batch bit-identical to the
      serial resume: a morsel is *live* while its own frontier is globally
      non-empty AND its own counter is under the cap; state updates and
      counter increments apply only to live morsels (early finishers go
      inert — their state freezes — instead of blocking or overrunning),
      and the loop exits when no morsel is live. Total phase-2 iteration
      slots drop from sum(survivor trips) to max(survivor trips).

    ``state_layout="sharded"`` resumes with state rows sharded over the
    policy's graph axes (all mesh axes under ``hybrid_phases``): the
    per-iteration merge is the OR/MIN reduce-scatter
    (``collectives.gang_merge_scatter``), which is what lets DESIGN.md §6
    billion-node morsels get a phase 2 at all. Callers hand state over via
    ``collectives.gang_handoff``.

    ``collect_stats``: same tap as ``build_engine`` — ``fn`` returns
    ``(IFEResult, stats)`` with ``stats[S_pad, cap, STATS_WIDTH]``.
    Records are written per live morsel at its own ABSOLUTE iteration
    row (counters start at ``it0``); inert/converged morsels' rows are
    left untouched, so the gang tap is sample-identical to draining the
    survivors one-at-a-time through the serial resume tap.

    The returned engine's ``fn`` signature is ``fn(graph, state0, it0)``.
    """
    ec = EDGE_COMPUTES[edge_compute]
    spec = as_spec(extend)
    ga = policy.graph_axes
    sa = policy.source_axes
    if sa:
        raise ValueError(
            "gang resume engine re-dispatches under frontier parallelism; "
            f"policy must not shard sources (got source_axes={sa})"
        )
    cap = int(max_iters if max_iters is not None else n_nodes_padded)
    n = n_nodes_padded
    sharded = state_layout == "sharded" and bool(ga)
    sync_axes = tuple(ga)

    def worker(graph_in, state0, it0):
        ops = as_operands(graph_in)
        be = make_backend(spec)
        rows_local = ops.fwd.indices.shape[0]
        offset = _flat_axis_index(ga) * rows_local if ga else None
        ctx = ExtendCtx(
            n_out=n,
            row_offset=None if sharded else offset,
            row_base=offset if sharded else None,
            axes=tuple(ga),
            or_impl=policy.or_impl,
            sharded=sharded,
        )
        bw = _stats_bin_widths(ops) if collect_stats else None

        def live(state, it):
            # [S_pad] bool: morsels whose own frontier is still globally
            # non-empty and whose own counter is under the cap
            f = state.frontier
            act = (f != 0).reshape(f.shape[0], -1).any(axis=1)
            if sync_axes:
                act = lax.psum(act.astype(jnp.int32), sync_axes) > 0
            return act & (it < cap)

        def cond(carry):
            state, it = carry[0], carry[1]
            return jnp.any(live(state, it))

        def body(carry):
            state, it = carry[0], carry[1]
            mask = live(state, it)
            if collect_stats:
                # one record per live gang member at its OWN absolute
                # iteration row (frontier_stats psums over the graph
                # axes internally, so recs are replicated like iters)
                recs = jax.vmap(
                    lambda st: frontier_stats(ops, st, ctx, bin_widths=bw)
                )(state)
                s_ix = jnp.arange(recs.shape[0])
                idx = jnp.clip(it, 0, cap - 1)
                stats = carry[2].at[s_ix, idx].set(
                    jnp.where(mask[:, None], recs, carry[2][s_ix, idx])
                )
            contribution = ec.gang_extend(be, ops, state, ctx)
            if sharded:
                merged = gang_merge_scatter(
                    ec.MERGE, contribution, ga, policy.or_impl
                )
            else:
                merged = merge_contribution(
                    ec.MERGE, contribution, ga, policy.or_impl
                )
            applied = jax.vmap(ec.apply)(state, merged, it)
            bmask = lambda x: mask.reshape((-1,) + (1,) * (x.ndim - 1))
            new_state = jax.tree.map(
                lambda new, old: jnp.where(bmask(new), new, old),
                applied, state,
            )
            out = (new_state, it + mask.astype(it.dtype))
            return out + ((stats,) if collect_stats else ())

        init = (state0, it0)
        if collect_stats:
            init = init + (
                jnp.zeros((it0.shape[0], cap, STATS_WIDTH), jnp.float32),
            )
        carry = lax.while_loop(cond, body, init)
        res = IFEResult(state=carry[0], iterations=carry[1])
        return (res, carry[2]) if collect_stats else res

    g_specs = _operand_specs(spec, ga, operands)
    if sharded:
        # state rows live on the graph axes: leaves are [gang, rows, ...]
        lanes = getattr(ec, "LANES", 0)
        probe = jax.eval_shape(
            lambda: ec.init(8, jnp.zeros((max(lanes, 1),), jnp.int32))
        )
        state_spec = jax.tree.map(lambda _: P(None, ga), probe)
        in_state, out_spec = state_spec, IFEResult(
            state=state_spec, iterations=P()
        )
    else:
        in_state, out_spec = P(), IFEResult(state=P(), iterations=P())
    if collect_stats:
        out_spec = (out_spec, P())
    fn = jax.jit(
        jax.shard_map(
            worker,
            mesh=mesh,
            in_specs=(g_specs, in_state, P()),
            out_specs=out_spec,
            check_vma=False,
        )
    )
    return QueryEngine(
        mesh=mesh,
        policy=policy,
        edge_compute=edge_compute,
        n_nodes_padded=n,
        max_iters=cap,
        fn=fn,
        extend=spec,
    )


def prepare_graph(
    csr: CSRGraph,
    mesh: Mesh,
    policy: MorselPolicy,
    max_deg: int | None = None,
    pad_shards: int | None = None,
    extend="ell_push",
    version: int = 0,
    stream: bool | None = None,
) -> tuple[GraphOperands, int]:
    """Host-side: CSR → padded, device-placed extension operands for this
    policy's mesh: the forward ELL always, plus the reverse ELL, the
    degree-binned reverse slabs, and/or the per-shard block adjacency when
    the ``extend`` spec scans them (all derived from the same truncated
    edge set — backend bit-parity).

    Rows pad to a multiple of shards×pad_block (32, or the MXU tile size
    for block operands) so the sharded-state engine's bit-packed ring
    reduce-scatter stays word-aligned per shard and block tiles divide
    every shard.

    ``pad_shards``: pad rows for this many shards (lcm'd with the policy's
    own shard count) instead of the policy's alone. The adaptive scheduler
    passes ``mesh.size`` so the phase-1 (nTkS, graph over a subset of axes)
    and phase-2 (nT1S, graph over all axes) graphs share one ``n_pad`` and
    state arrays can flow between the two engines unchanged.

    ``stream``: build operands one policy shard at a time and place each
    shard directly on its devices instead of materializing the whole host
    structure first — peak host memory drops to ~1/shards of the wholesale
    build, and under multi-process JAX each process builds only the shards
    its addressable devices own (``None`` = auto: stream exactly when the
    policy splits the graph over more than one device — the wholesale
    build assembles the whole padded structure on the default device
    before resharding it). Falls back to the wholesale build when the
    policy has no graph axes (replicated operands). The placed arrays are
    bitwise-identical to the wholesale path's either way."""
    spec = as_spec(extend)
    k_policy = _axes_size(mesh, policy.graph_axes)
    shards = k_policy
    if pad_shards is not None:
        shards = int(np.lcm(shards, int(pad_shards)))
    if stream is None:
        stream = k_policy > 1
    if stream and policy.graph_axes and k_policy > 1:
        return _prepare_graph_streamed(
            csr, mesh, policy, spec, max_deg, shards, k_policy, version
        )
    # rows pad for the lcm shard count, but binned slabs are built directly
    # at the policy's own shard count (per-shard binning can't reshape)
    ops, n_pad = build_operands(
        csr, spec, max_deg=max_deg, shards=shards, binned_shards=k_policy
    )
    ga = policy.graph_axes
    row_sharding = NamedSharding(mesh, P(ga if ga else None, None))
    deg_sharding = NamedSharding(mesh, P(ga if ga else None))

    def put_ell(g: EllGraph) -> EllGraph:
        return EllGraph(
            indices=jax.device_put(g.indices, row_sharding),
            degrees=jax.device_put(g.degrees, deg_sharding),
            weights=None
            if g.weights is None
            else jax.device_put(g.weights, row_sharding),
        )

    k_shards = k_policy
    rev_binned = None
    rev_binned_pack = None
    leaf_sharding = lambda x: NamedSharding(
        mesh, P(ga if ga else None, *(None,) * (x.ndim - 1))
    )
    if ops.rev_binned is not None:
        bn = ops.rev_binned
        assert bn.rows_local * k_shards == n_pad, (bn.rows_local, k_shards)
        rev_binned = jax.tree.map(
            lambda x: jax.device_put(x, leaf_sharding(x)), bn
        )
    if ops.rev_binned_pack is not None:
        # same stacked-shard leading-axis layout as the jnp slabs
        rev_binned_pack = jax.tree.map(
            lambda x: jax.device_put(x, leaf_sharding(x)),
            ops.rev_binned_pack,
        )
    blocks = None
    if ops.blocks is not None:
        sb = ops.blocks
        if k_shards != shards:
            # operands were padded for more shards than this policy uses
            # (pad_shards lcm) — regroup the stacked tiles per policy shard
            sb = ShardedBlocks(
                blocks=jnp.reshape(
                    sb.blocks,
                    (k_shards, -1, *sb.blocks.shape[2:]),
                ),
                block_rows=_regroup_block_rows(sb, k_shards, n_pad),
                block_cols=jnp.reshape(sb.block_cols, (k_shards, -1)),
            )
        blocks = ShardedBlocks(
            blocks=jax.device_put(
                sb.blocks,
                NamedSharding(mesh, P(ga if ga else None, None, None, None)),
            ),
            block_rows=jax.device_put(
                sb.block_rows, NamedSharding(mesh, P(ga if ga else None, None))
            ),
            block_cols=jax.device_put(
                sb.block_cols, NamedSharding(mesh, P(ga if ga else None, None))
            ),
        )
    ops = GraphOperands(
        fwd=put_ell(ops.fwd),
        rev=None if ops.rev is None else put_ell(ops.rev),
        rev_binned=rev_binned,
        rev_binned_pack=rev_binned_pack,
        blocks=blocks,
        version=version,
    )
    return ops, n_pad


def _regroup_block_rows(sb: ShardedBlocks, k_shards: int, n_pad: int):
    """Re-base local row-block ids when folding ``shards`` stacked shard
    groups into ``k_shards`` coarser policy shards."""
    fine = sb.block_rows.shape[0]
    group = fine // k_shards
    rb_fine = (n_pad // fine) // sb.block_size
    offs = (jnp.arange(fine, dtype=jnp.int32) % group) * rb_fine
    rows = sb.block_rows + offs[:, None]
    return jnp.reshape(rows, (k_shards, -1))


def _device_shard_map(mesh: Mesh, ga, k_policy: int) -> dict:
    """Addressable device → policy-shard index, derived from how a
    ``P(ga)`` sharding chunks a virtual ``[k_policy]`` axis. The grouping
    is leaf-shape independent: every operand leaf shards its axis 0 over
    the same graph axes into ``k_policy`` equal contiguous chunks, so
    chunk ``k``'s device group is the same for all of them."""
    probe = NamedSharding(mesh, P(ga))
    idx_map = probe.addressable_devices_indices_map((k_policy,))
    out = {}
    for d, idx in idx_map.items():
        sl = idx[0]
        out[d] = 0 if sl.start is None else int(sl.start)
    return out


def _prepare_graph_streamed(
    csr: CSRGraph,
    mesh: Mesh,
    policy: MorselPolicy,
    spec: ExtendSpec,
    max_deg: int | None,
    shards: int,
    k_policy: int,
    version: int,
) -> tuple[GraphOperands, int]:
    """Shard-at-a-time, multi-host-aware operand placement.

    Plans the build once (``operand_stream``), then for each policy shard
    owned by an *addressable* device builds only that shard's host leaves,
    places them on its devices, and frees them before the next shard —
    host peak is one shard's bytes, and under multi-process JAX each
    process touches only its local shards. Global arrays are assembled
    from the per-device buffers (``jax.make_array_from_single_device_
    arrays``) under exactly the shardings the wholesale path uses, so
    engines see identical operands."""
    st = operand_stream(
        csr, spec, max_deg=max_deg, shards=shards, binned_shards=k_policy
    )
    n_pad = st.n_pad
    ga = policy.graph_axes
    dev_shard = _device_shard_map(mesh, ga, k_policy)
    local = sorted(set(dev_shard.values()))
    bufs: dict = {}  # leaf name -> list of single-device arrays
    shapes: dict = {}  # leaf name -> global shape
    for k in local:
        piece = st.build_shard(k)
        for name, arr in piece.items():
            shapes.setdefault(
                name, (arr.shape[0] * k_policy, *arr.shape[1:])
            )
            blist = bufs.setdefault(name, [])
            for d, kk in dev_shard.items():
                if kk == k:
                    blist.append(jax.device_put(arr, d))
        del piece  # free this shard's host leaves before the next build
    leaves = {}
    for name, blist in bufs.items():
        shape = shapes[name]
        ndim = len(shape)
        sharding = NamedSharding(mesh, P(ga, *(None,) * (ndim - 1)))
        leaves[name] = jax.make_array_from_single_device_arrays(
            shape, sharding, blist
        )
    return st.assemble(leaves, version=version), n_pad


def run_recursive_query(
    mesh: Mesh,
    csr: CSRGraph,
    sources,
    policy: MorselPolicy,
    edge_compute: str = "sp_lengths",
    max_iters: int | None = None,
    max_deg: int | None = None,
    state_layout: str = "replicated",
    extend="ell_push",
) -> IFEResult:
    """End-to-end: the paper Fig 3 IFETask. Returns states stacked over
    morsels: leaves have leading dim n_morsels (global). ``extend`` selects
    the frontier-extension backend ("ell_push" | "ell_pull" | "pull_binned"
    | "pull_binned_fused" | "block_mxu" | "dopt"/ExtendSpec) — results are
    bit-identical across all of them."""
    spec = as_spec(extend)
    g, n_pad = prepare_graph(csr, mesh, policy, max_deg, extend=spec)
    src_shards = _axes_size(mesh, policy.source_axes)
    morsels = pad_sources(np.asarray(sources), src_shards, policy.lanes, n_pad)
    sa = policy.source_axes
    morsels = jax.device_put(
        jnp.asarray(morsels), NamedSharding(mesh, P(sa if sa else None, None))
    )
    engine = build_engine(
        mesh, policy, edge_compute, n_pad, max_iters,
        state_layout=state_layout, extend=spec, operands=g,
    )
    return engine(g, morsels)
