"""Pluggable, density-adaptive frontier-extension backends.

The paper's economy argument is "amount of scans": a morsel policy wins by
touching less adjacency data per iteration. This module makes the *physical
scan layout* of the extension step a per-engine choice (EmptyHeaded's
density-adaptive set layouts; Kuzu's per-operator physical scan selection),
with three backends sharing one contract plus a Beamer-style
direction-optimizing switch:

- ``ell_push``  — forward-ELL scatter (the original path): every local row
  broadcasts its frontier bit down its out-neighbor list. Scan cost is the
  whole ``[rows, max_deg]`` tensor regardless of frontier density.
- ``ell_pull``  — gather over the *reverse* ELL with visited-suppression:
  each unvisited v scans its in-neighbor list and ORs the frontier bits it
  finds — the classic bottom-up win when frontiers are large, because the
  rows that still need scanning (unvisited) shrink every iteration. The
  reverse ELL is one slab padded to ``max_in_deg``, so on heavy-tailed
  graphs (power-law: rev max_deg ≫ mean) each scan still pays
  ``n × max_in_deg`` slots.
- ``pull_binned`` — the same pull contract over **degree-binned reverse
  slabs** (``graph.csr.BinnedRevEll``): reverse rows are permuted into
  pow2-bounded degree buckets, each bucket padded only to its own width,
  and the per-slab gather results are un-permuted back to row order. A
  full scan costs ~``sum(in_deg)`` slots instead of ``n × max_in_deg`` —
  the EmptyHeaded lesson (degree-specialized physical layouts) applied to
  the bottom-up direction, which is what makes pull (and therefore the
  Beamer switch) profitable on skewed graphs.
- ``pull_binned_fused`` — the same contract and the same binned slabs,
  realized by the fused Pallas kernel (``kernels.binned_pull``): per-slab
  gathers, reductions, the un-permute, and the visited suppression in one
  VMEM pass per row tile, with ``pl.when``-gated skipping of fully-visited
  tiles. Bit-identical to ``pull_binned``; the raw-speed realization.
- ``block_mxu`` — the saturating-matmul path over the per-shard block-sparse
  adjacency (``ShardedBlocks``), upgraded to skip frontier-empty source
  row-block *stripes* (a per-row-block activity bitmap masks contributions;
  the Pallas kernel skips the same blocks via scalar-prefetch indices).

``direction="auto"`` realizes Beamer's alpha/beta direction optimization as
a per-iteration ``lax.cond`` between push and pull with fixed shapes, so it
composes with ``jit`` / ``while_loop`` / ``shard_map`` in both the
replicated and sharded state layouts. ``ExtendSpec.pull`` selects the
bottom-up flavor of the switch — ``"ell"`` (padded reverse ELL) or
``"binned"`` (degree-binned slabs; the ``"dopt_binned"`` alias and the
default ``recommend_backend`` path). The decision is a pure, stateless
function of (frontier, visited): pull when the frontier's out-edge mass
exceeds the unexplored edge mass / alpha AND the frontier holds more than
n / beta nodes — alpha/beta default to Beamer's CPU constants and can be
replaced per (dataset-family, degree-bucket) by
``core.policies.fit_direction_thresholds``. Collectives (global-frontier
union, stat psums) are hoisted *outside* the cond so both branches are
collective-free and every device in a sync group takes the same branch.

All backends produce bit-identical final states: push and pull enumerate the
same edge set (reverse operands are derived from the *truncated* forward
graph — see ``graph.csr.truncate_csr``), OR/min merges are order-invariant,
and visited-suppression only changes contribution values that
``ec.apply``'s ``& ~visited`` masks away.

Backends consume a ``GraphOperands`` bundle (forward ELL + optional reverse
ELL + optional degree-binned reverse slabs + optional per-shard blocks)
built once host-side by ``core.dispatcher.prepare_graph`` /
``build_operands``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.binned_pull.ops import (
    BinnedPullPack,
    binned_pull as _fused_pull,
    build_pack as build_binned_pack,
)
from ..graph.csr import (
    BinnedPlan,
    BinnedRevEll,
    CSRGraph,
    EllGraph,
    ShardedBlocks,
    binned_plan,
    binned_rev_csr,
    binned_rev_shard,
    ell_from_csr,
    ell_shard,
    sharded_blocks_from_csr,
    sharded_blocks_nb,
    sharded_blocks_shard,
    truncate_csr,
)
from ..graph.partition import pad_ell, padded_n, reverse_shard
from .collectives import min_allreduce, or_allreduce
from .edge_compute import (
    NO_PARENT,
    _deg_chunk,
    _local_rows,
    chunk_fold,
    ell_min_dist,
    ell_min_parent,
    ell_min_parent_lanes,
    ell_min_topk,
    ell_push_sum,
    ell_reach_dense,
    ell_reach_lanes,
)

BACKENDS = (
    "ell_push", "ell_pull", "pull_binned", "pull_binned_fused", "block_mxu"
)


@dataclasses.dataclass(frozen=True)
class ExtendSpec:
    """Static configuration of the extension step (hashable: engine-cache
    key material and jit static argument)."""

    backend: str = "ell_push"  # one of BACKENDS
    direction: str = "fixed"  # fixed | auto (Beamer push/pull switch)
    alpha: float = 14.0  # pull when m_frontier > m_unexplored / alpha
    beta: float = 24.0  # ... and n_frontier > n / beta
    block: int = 128  # tile size of the block_mxu operand
    pull: str = "binned"  # auto's bottom-up flavor:
    #                       binned slabs | fused-kernel slabs | padded ell

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown extension backend: {self.backend}")
        if self.direction not in ("fixed", "auto"):
            raise ValueError(f"unknown direction mode: {self.direction}")
        if self.pull not in ("binned", "binned_fused", "ell"):
            raise ValueError(f"unknown pull flavor: {self.pull}")
        if self.direction == "auto" and self.backend != "ell_push":
            # the auto switch IS the backend choice (push vs pull); pinning
            # another backend alongside it would be silently ignored
            raise ValueError(
                "direction='auto' switches between push and pull (flavor "
                "chosen by the `pull` field); it cannot be combined with "
                f"backend={self.backend!r}"
            )

    @property
    def needs_rev(self) -> bool:
        """Scans the single padded reverse-ELL slab."""
        return self.backend == "ell_pull" or (
            self.direction == "auto" and self.pull == "ell"
        )

    @property
    def needs_binned(self) -> bool:
        """Scans the degree-binned reverse slabs (the fused kernel keeps
        them too: ``frontier_stats``' pull-slot accounting reads the
        unpadded slab widths)."""
        return self.backend in ("pull_binned", "pull_binned_fused") or (
            self.direction == "auto"
            and self.pull in ("binned", "binned_fused")
        )

    @property
    def needs_binned_pack(self) -> bool:
        """Scans the kernel-ready row-padded repack of the binned slabs."""
        return self.backend == "pull_binned_fused" or (
            self.direction == "auto" and self.pull == "binned_fused"
        )

    @property
    def needs_blocks(self) -> bool:
        return self.direction == "fixed" and self.backend == "block_mxu"

    @property
    def pad_block(self) -> int:
        """Row-padding unit the operands need (block tiles must divide the
        per-shard row count; 32 keeps the bit-packed ring word-aligned)."""
        return self.block if self.needs_blocks else 32


#: convenience aliases accepted anywhere an ExtendSpec is
_ALIASES = {
    "dopt": ExtendSpec(direction="auto"),
    "auto": ExtendSpec(direction="auto"),
    "dopt_ell": ExtendSpec(direction="auto", pull="ell"),
    "dopt_binned": ExtendSpec(direction="auto", pull="binned"),
    "dopt_fused": ExtendSpec(direction="auto", pull="binned_fused"),
}


def as_spec(extend) -> ExtendSpec:
    """Normalize a backend name / alias / spec / None to an ExtendSpec."""
    if extend is None:
        return ExtendSpec()
    if isinstance(extend, ExtendSpec):
        return extend
    if isinstance(extend, str):
        if extend in _ALIASES:
            return _ALIASES[extend]
        return ExtendSpec(backend=extend)
    raise TypeError(f"cannot interpret extend={extend!r}")


@dataclasses.dataclass(frozen=True)
class GraphOperands:
    """The physical scan operands of one graph (or one graph shard).

    ``fwd`` is always present; ``rev`` / ``rev_binned`` / ``blocks`` are
    materialized only when the engine's ExtendSpec needs them (treedefs
    must match shard_map in_specs exactly, so engines carry precisely the
    operands they scan).

    ``version`` is the mutable-graph bookkeeping tag: the dispatcher
    stamps its monotonically increasing ``operands_version`` here when a
    ``GraphDelta`` folds new buffers into the bundle. It is a pytree
    *meta* field, so it must never reach a traced program — a distinct
    version would be a distinct treedef and force a retrace, defeating
    the whole warm-engine design. ``dispatcher.strip_operands`` (the
    mandatory coercion in front of every engine call) rebuilds the bundle
    without it, so traced code only ever sees ``version=0``.
    """

    fwd: EllGraph
    rev: Optional[EllGraph] = None
    rev_binned: Optional[BinnedRevEll] = None
    rev_binned_pack: Optional[BinnedPullPack] = None
    blocks: Optional[ShardedBlocks] = None
    version: int = 0

    @property
    def n_nodes(self) -> int:
        return self.fwd.n_nodes


jax.tree_util.register_dataclass(
    GraphOperands,
    data_fields=["fwd", "rev", "rev_binned", "rev_binned_pack", "blocks"],
    meta_fields=["version"],
)


def as_operands(graph) -> GraphOperands:
    if isinstance(graph, GraphOperands):
        return graph
    return GraphOperands(fwd=graph)


def build_operands(
    csr: CSRGraph,
    extend="ell_push",
    max_deg: int | None = None,
    shards: int = 1,
    block: int | None = None,
    binned_shards: int | None = None,
    version: int = 0,
) -> tuple[GraphOperands, int]:
    """Host-side operand construction (single-host variant; the mesh-aware
    path in ``dispatcher.prepare_graph`` adds device placement).

    Pads rows to a multiple of ``shards * pad_block`` and derives reverse /
    binned / block operands from the *truncated* forward graph so every
    backend scans the identical edge set. ``binned_shards`` overrides the
    shard count the binned slabs are built for (binning is per shard, so
    ``prepare_graph`` bins at the policy's own shard count even when rows
    pad for a larger ``pad_shards`` lcm). Returns (operands, n_pad).
    """
    spec = as_spec(extend)
    pad_block = block or spec.pad_block
    eff = effective_csr(csr, max_deg)
    fwd = pad_ell(ell_from_csr(eff), shards, block=pad_block)
    n_pad = fwd.n_nodes
    rev = None
    if spec.needs_rev:
        rev = pad_ell(ell_from_csr(eff.reverse()), shards, block=pad_block)
        assert rev.n_nodes == n_pad, (rev.n_nodes, n_pad)
    rev_binned = None
    rev_binned_pack = None
    if spec.needs_binned:
        k = shards if binned_shards is None else int(binned_shards)
        rev_binned = binned_rev_csr(eff, n_pad, k)
        if spec.needs_binned_pack:
            rev_binned_pack = build_binned_pack(rev_binned, n_pad)
    blocks = None
    if spec.needs_blocks:
        blocks = sharded_blocks_from_csr(eff, n_pad, shards, spec.block)
    return (
        GraphOperands(
            fwd=fwd,
            rev=rev,
            rev_binned=rev_binned,
            rev_binned_pack=rev_binned_pack,
            blocks=blocks,
            version=version,
        ),
        n_pad,
    )


def effective_csr(csr: CSRGraph, max_deg: int | None) -> CSRGraph:
    """The edge set every backend scans under a ``max_deg`` cap: the cap is
    the ELL row width (max_deg rounded up to the ELL pad multiple) —
    matching the historical ``ell_from_csr(csr, max_deg)`` semantics so
    capped queries return the same results as the seed engine."""
    cap = None if max_deg is None else -(-int(max_deg) // 8) * 8
    return truncate_csr(csr, cap)


def _round8(cap: int) -> int:
    return -(-cap // 8) * 8 if cap > 0 else 0


@dataclasses.dataclass(frozen=True)
class OperandStream:
    """Shard-at-a-time operand construction (the streamed half of
    ``build_operands``).

    ``operand_stream`` runs the global O(n) planning passes once (row
    padding, ELL widths, the binned-slab plan, the common block tile
    count); ``build_shard(k)`` then materializes only policy shard ``k``'s
    leaves as host numpy arrays — peak host memory is one shard's operand
    bytes plus the resident CSR, instead of the whole padded structure.
    Every leaf's axis 0 is the sharded axis (rows for ELL leaves, the
    stacked shard axis for binned/pack/block leaves), and a shard's piece
    is exactly ``global_shape[0] // k_shards`` entries of it, so the
    caller can place pieces per device and assemble global arrays
    (``dispatcher.prepare_graph(stream=True)``) or concatenate them into
    the wholesale host structure. Bitwise-identical to ``build_operands``
    by construction — see the per-shard builders' docstrings for why.
    """

    csr: CSRGraph  # effective (truncated) forward graph
    spec: ExtendSpec
    n_pad: int
    k_shards: int  # policy shard count — the build granularity
    fine_shards: int  # row-padding (lcm) shard count; blocks built fine
    cap_fwd: int
    cap_rev: Optional[int] = None
    plan: Optional[BinnedPlan] = None
    nb: Optional[int] = None

    @property
    def rows_local(self) -> int:
        return self.n_pad // self.k_shards

    def build_shard(self, k: int) -> dict:
        """Policy shard ``k``'s operand leaves: flat dict name → host
        numpy array (the key set is identical across shards)."""
        rl = self.rows_local
        lo, hi = k * rl, (k + 1) * rl
        leaves = {}
        idx, degs, w = ell_shard(self.csr, lo, hi, self.cap_fwd, self.n_pad)
        leaves["fwd.indices"], leaves["fwd.degrees"] = idx, degs
        if w is not None:
            leaves["fwd.weights"] = w
        rev_local = None
        if self.spec.needs_rev or self.spec.needs_binned:
            rev_local = reverse_shard(self.csr, lo, hi)
        if self.spec.needs_rev:
            idx, degs, w = ell_shard(rev_local, 0, rl, self.cap_rev,
                                     self.n_pad)
            leaves["rev.indices"], leaves["rev.degrees"] = idx, degs
            if w is not None:
                leaves["rev.weights"] = w
        if self.spec.needs_binned:
            bn = binned_rev_shard(self.plan, k, rev_local)
            leaves["bn.perm"], leaves["bn.inv"] = bn.perm, bn.inv
            for b, s in enumerate(bn.slabs):
                leaves[f"bn.slab{b}"] = s
            if bn.slab_weights is not None:
                for b, s in enumerate(bn.slab_weights):
                    leaves[f"bn.w{b}"] = s
            if self.spec.needs_binned_pack:
                pk = build_binned_pack(bn, self.n_pad, as_numpy=True)
                leaves["pack.inv_pad"] = pk.inv_pad
                leaves["pack.perm_pad"] = pk.perm_pad
                for b, s in enumerate(pk.slabs):
                    leaves[f"pack.slab{b}"] = s
                if pk.slab_weights is not None:
                    for b, s in enumerate(pk.slab_weights):
                        leaves[f"pack.w{b}"] = s
        if self.spec.needs_blocks:
            group = self.fine_shards // self.k_shards
            B = self.spec.block
            sb = sharded_blocks_shard(
                self.csr, self.n_pad, self.fine_shards, self.nb,
                k * group, (k + 1) * group, B,
            )
            # fold the fine subshards into one policy shard, re-basing the
            # local row-block ids exactly like ``_regroup_block_rows``
            rb_fine = (self.n_pad // self.fine_shards) // B
            offs = (np.arange(group, dtype=np.int32) * rb_fine)[:, None]
            leaves["blocks.blocks"] = sb.blocks.reshape(1, -1, B, B)
            leaves["blocks.rows"] = (
                (sb.block_rows + offs).reshape(1, -1).astype(np.int32)
            )
            leaves["blocks.cols"] = sb.block_cols.reshape(1, -1)
        return leaves

    def assemble(self, g: dict, version: int = 0) -> GraphOperands:
        """Rebuild ``GraphOperands`` from assembled global leaves (same
        key set ``build_shard`` emits; values may be jax or numpy)."""

        def ell(p):
            if f"{p}.indices" not in g:
                return None
            return EllGraph(
                indices=g[f"{p}.indices"],
                degrees=g[f"{p}.degrees"],
                weights=g.get(f"{p}.weights"),
            )

        bn = None
        pack = None
        if "bn.inv" in g:
            nb = len(self.plan.widths)
            bn = BinnedRevEll(
                slabs=tuple(g[f"bn.slab{b}"] for b in range(nb)),
                perm=g["bn.perm"],
                inv=g["bn.inv"],
                slab_weights=(
                    tuple(g[f"bn.w{b}"] for b in range(nb))
                    if "bn.w0" in g
                    else None
                ),
            )
            if "pack.inv_pad" in g:
                nnz = nb - 1
                pack = BinnedPullPack(
                    slabs=tuple(
                        g[f"pack.slab{b}"] for b in range(nnz)
                    ),
                    inv_pad=g["pack.inv_pad"],
                    perm_pad=g["pack.perm_pad"],
                    slab_weights=(
                        tuple(g[f"pack.w{b}"] for b in range(nnz))
                        if "pack.w0" in g
                        else None
                    ),
                )
        blocks = None
        if "blocks.blocks" in g:
            blocks = ShardedBlocks(
                blocks=g["blocks.blocks"],
                block_rows=g["blocks.rows"],
                block_cols=g["blocks.cols"],
            )
        return GraphOperands(
            fwd=ell("fwd"),
            rev=ell("rev"),
            rev_binned=bn,
            rev_binned_pack=pack,
            blocks=blocks,
            version=version,
        )


def operand_stream(
    csr: CSRGraph,
    extend="ell_push",
    max_deg: int | None = None,
    shards: int = 1,
    block: int | None = None,
    binned_shards: int | None = None,
) -> OperandStream:
    """Plan a streamed (shard-at-a-time) operand build — the counterpart
    of ``build_operands`` whose per-shard results are bitwise-identical to
    the wholesale build's slices. Same parameter semantics: rows pad for
    ``shards`` (the lcm count), binned slabs build at ``binned_shards``
    (the policy's own shard count), which is also the streaming
    granularity."""
    spec = as_spec(extend)
    pad_block = block or spec.pad_block
    eff = effective_csr(csr, max_deg)
    n = eff.n_nodes
    fine = max(int(shards), 1)
    k = fine if binned_shards is None else int(binned_shards)
    assert fine % k == 0, (fine, k)
    n_pad = padded_n(n, fine, pad_block)
    cap_fwd = _round8(int(eff.degrees.max()) if n else 0)
    cap_rev = None
    plan = None
    nb = None
    if spec.needs_rev or spec.needs_binned:
        rev_degs = (
            np.bincount(eff.indices, minlength=n)
            if n
            else np.zeros(0, np.int64)
        )
        if spec.needs_rev:
            cap_rev = _round8(int(rev_degs.max()) if n else 0)
        if spec.needs_binned:
            plan = binned_plan(rev_degs, n_pad, k)
    if spec.needs_blocks:
        nb = sharded_blocks_nb(eff, n_pad, fine, spec.block)
    return OperandStream(
        csr=eff,
        spec=spec,
        n_pad=n_pad,
        k_shards=k,
        fine_shards=fine,
        cap_fwd=cap_fwd,
        cap_rev=cap_rev,
        plan=plan,
        nb=nb,
    )


@dataclasses.dataclass(frozen=True)
class ExtendCtx:
    """Per-trace extension context (fields may be traced values).

    Layout contract mirrors ``edge_compute``: replicated state passes
    ``row_offset`` (slice the global array to this shard's rows) and global
    state tensors; sharded state passes local-row tensors with
    ``row_base`` = global id of the first local row. ``axes`` are the graph
    mesh axes collectives may span; ``sharded`` selects the local-row state
    convention.
    """

    n_out: int
    row_offset: object = None  # traced int or None (replicated layout)
    row_base: object = None  # traced int or None (sharded layout)
    axes: tuple = ()
    or_impl: str = "allgather"
    sharded: bool = False

    @property
    def start(self):
        """Global row id of the first local row (0 on a single shard)."""
        if self.row_offset is not None:
            return self.row_offset
        if self.row_base is not None:
            return self.row_base
        return None


def _place_rows(local: jax.Array, ctx: ExtendCtx, fill) -> jax.Array:
    """Embed a local-rows result into the global [n_out, ...] contribution
    (identity on a single full-width shard)."""
    start = ctx.start
    if start is None:
        return local
    out = jnp.full((ctx.n_out, *local.shape[1:]), fill, local.dtype)
    return lax.dynamic_update_slice(
        out, local, (start,) + (0,) * (local.ndim - 1)
    )


def _local_state(x: jax.Array, rows: int, ctx: ExtendCtx) -> jax.Array:
    """This shard's rows of a state tensor (sharded state is already local)."""
    if ctx.sharded or ctx.row_offset is None:
        return x
    return lax.dynamic_slice_in_dim(x, ctx.row_offset, rows, axis=0)


# ---------------------------------------------------------------------------
# ell_push — forward scatter (the original primitives, unchanged math).
# ---------------------------------------------------------------------------


def _min_topk_pull(ops, dists, src_mask, ctx):
    """Shared top-k relax: a full-Jacobi gather over the reverse ELL — the
    only physical form (a scatter cannot sorted-merge k slots), so every
    backend routes here. The slot table is globalized first (sharded rows
    place-with-inf + min-allreduce, the same inverse pattern as pull
    min_dist); contributions come back row-placed for the 'min' merge."""
    if ops.rev is None:
        raise ValueError(
            "top-k relax scans the reverse ELL; build operands with "
            "extend='ell_pull' (needs_rev)"
        )
    rev = ops.rev
    rows = rev.indices.shape[0]
    gd = _global_min(dists, ctx, jnp.float32(jnp.inf))
    seed = jnp.where(
        _local_state(src_mask, rows, ctx), 0.0, jnp.inf
    ).astype(jnp.float32)
    return _place_rows(ell_min_topk(rev, gd, seed), ctx, jnp.float32(jnp.inf))


class PushBackend:
    name = "ell_push"

    @staticmethod
    def reach_dense(ops, frontier, visited, ctx):
        return ell_reach_dense(ops.fwd, frontier, ctx.row_offset, ctx.n_out)

    @staticmethod
    def push_sum(ops, values, ctx, normalize=False):
        return ell_push_sum(
            ops.fwd, values, ctx.row_offset, ctx.n_out, normalize
        )

    min_topk = staticmethod(_min_topk_pull)

    @staticmethod
    def reach_lanes(ops, lanes, visited, ctx):
        return ell_reach_lanes(ops.fwd, lanes, ctx.row_offset, ctx.n_out)

    @staticmethod
    def min_parent(ops, frontier, visited, ctx):
        return ell_min_parent(
            ops.fwd, frontier, ctx.row_offset, ctx.n_out, ctx.row_base
        )

    @staticmethod
    def min_parent_lanes(ops, lanes, visited, ctx):
        return ell_min_parent_lanes(
            ops.fwd, lanes, ctx.row_offset, ctx.n_out, ctx.row_base
        )

    @staticmethod
    def min_dist(ops, dist, frontier, ctx):
        return ell_min_dist(
            ops.fwd, dist, frontier, ctx.row_offset, ctx.n_out
        )

    # or_min edge computes fetch both contributions in one call so backends
    # with per-call setup cost (collectives, direction predicate) pay it once
    @staticmethod
    def reach_parent_dense(ops, frontier, visited, ctx):
        return (
            PushBackend.reach_dense(ops, frontier, visited, ctx),
            PushBackend.min_parent(ops, frontier, visited, ctx),
        )

    @staticmethod
    def reach_parent_lanes(ops, lanes, visited, ctx):
        return (
            PushBackend.reach_lanes(ops, lanes, visited, ctx),
            PushBackend.min_parent_lanes(ops, lanes, visited, ctx),
        )


# ---------------------------------------------------------------------------
# ell_pull — reverse gather with visited-suppression.
# ---------------------------------------------------------------------------


def _global_or(x: jax.Array, ctx: ExtendCtx) -> jax.Array:
    """Global activation tensor from a state tensor. Replicated layout: the
    input is already global. Sharded layout: place local rows and OR-union
    across the graph axes (this is pull's inverse communication pattern —
    frontier bits travel instead of contributions)."""
    if not ctx.sharded:
        return x
    placed = _place_rows(x, ctx, jnp.zeros((), x.dtype))
    return or_allreduce(placed, ctx.axes, ctx.or_impl)


def _global_min(x: jax.Array, ctx: ExtendCtx, fill) -> jax.Array:
    if not ctx.sharded:
        return x
    return min_allreduce(_place_rows(x, ctx, fill), ctx.axes)


def _pull_gather_any(rev: EllGraph, gf: jax.Array) -> jax.Array:
    """[n_out] bool -> [rows] bool: row v active iff any in-neighbor is."""
    got = gf.at[rev.indices].get(mode="fill", fill_value=False)
    return got.any(axis=1)


def _pull_gather_lanes(rev: EllGraph, gl: jax.Array) -> jax.Array:
    """[n_out, L] uint8 -> [rows, L] uint8, degree-chunked like the push
    scatter so the gather temp stays bounded."""
    rows, D = rev.indices.shape
    L = gl.shape[-1]
    if D == 0:  # zero-width slab (edgeless/zero-cap): reductions over a
        return jnp.zeros((rows, L), gl.dtype)  # size-0 axis have no identity
    chunk = _deg_chunk(rows, L)
    if chunk >= D:
        got = gl.at[rev.indices].get(mode="fill", fill_value=0)
        return got.max(axis=1)

    def step(start, width, acc):
        idx = lax.dynamic_slice_in_dim(rev.indices, start, width, 1)
        got = gl.at[idx].get(mode="fill", fill_value=0)
        return jnp.maximum(acc, got.max(axis=1))

    acc0 = jnp.zeros((rows, L), gl.dtype)
    return chunk_fold(D, chunk, step, acc0)


def _pull_min_parent_lanes(rev: EllGraph, gl: jax.Array) -> jax.Array:
    rows, D = rev.indices.shape
    L = gl.shape[-1]
    if D == 0:
        return jnp.full((rows, L), NO_PARENT, jnp.int32)
    chunk = _deg_chunk(rows, 4 * L)

    def step(start, width, acc):
        idx = (
            rev.indices
            if width == D
            else lax.dynamic_slice_in_dim(rev.indices, start, width, 1)
        )
        act = gl.at[idx].get(mode="fill", fill_value=0)  # [rows, c, L]
        cand = jnp.where(
            act != 0, idx[:, :, None].astype(jnp.int32), NO_PARENT
        )
        return jnp.minimum(acc, cand.min(axis=1))

    acc0 = jnp.full((rows, L), NO_PARENT, jnp.int32)
    if chunk >= D:
        return step(0, D, acc0)
    return chunk_fold(D, chunk, step, acc0)


class PullBackend:
    name = "ell_pull"

    # -- collective-free cores (global activation tensors precomputed) ------

    @staticmethod
    def _reach_dense(ops, gf, visited, ctx):
        rev = ops.rev
        rows = rev.indices.shape[0]
        reached = _pull_gather_any(rev, gf)
        if visited is not None:
            reached &= ~_local_state(visited, rows, ctx)
        return _place_rows(reached, ctx, False)

    @staticmethod
    def _reach_lanes(ops, gl, visited, ctx):
        rev = ops.rev
        rows = rev.indices.shape[0]
        reached = _pull_gather_lanes(rev, gl)
        if visited is not None:
            vloc = _local_state(visited, rows, ctx)
            reached = jnp.where(vloc != 0, 0, reached)
        return _place_rows(reached, ctx, 0)

    @staticmethod
    def _min_parent(ops, gf, visited, ctx):
        rev = ops.rev
        rows = rev.indices.shape[0]
        if rev.indices.shape[1] == 0:
            cand = jnp.full((rows,), NO_PARENT, jnp.int32)
        else:
            got = gf.at[rev.indices].get(mode="fill", fill_value=False)
            cand = jnp.where(got, rev.indices, NO_PARENT).min(axis=1)
        if visited is not None:
            cand = jnp.where(
                _local_state(visited, rows, ctx), NO_PARENT, cand
            )
        return _place_rows(cand, ctx, NO_PARENT)

    @staticmethod
    def _min_parent_lanes(ops, gl, visited, ctx):
        rev = ops.rev
        rows = rev.indices.shape[0]
        cand = _pull_min_parent_lanes(rev, gl)
        if visited is not None:
            vloc = _local_state(visited, rows, ctx)
            cand = jnp.where(vloc != 0, NO_PARENT, cand)
        return _place_rows(cand, ctx, NO_PARENT)

    @staticmethod
    def _min_dist(ops, gdu, ctx):
        rev = ops.rev
        rows = rev.indices.shape[0]
        if rev.indices.shape[1] == 0:
            return _place_rows(
                jnp.full((rows,), jnp.inf, jnp.float32), ctx,
                jnp.float32(jnp.inf),
            )
        w = (
            rev.weights
            if rev.weights is not None
            else jnp.ones_like(rev.indices, dtype=jnp.float32)
        )
        got = gdu.at[rev.indices].get(mode="fill", fill_value=jnp.inf)
        cand = (got + w).min(axis=1)
        return _place_rows(cand, ctx, jnp.float32(jnp.inf))

    # -- public contract ----------------------------------------------------

    @staticmethod
    def reach_dense(ops, frontier, visited, ctx):
        return PullBackend._reach_dense(
            ops, _global_or(frontier, ctx), visited, ctx
        )

    @staticmethod
    def reach_lanes(ops, lanes, visited, ctx):
        return PullBackend._reach_lanes(
            ops, _global_or(lanes, ctx), visited, ctx
        )

    @staticmethod
    def min_parent(ops, frontier, visited, ctx):
        return PullBackend._min_parent(
            ops, _global_or(frontier, ctx), visited, ctx
        )

    @staticmethod
    def min_parent_lanes(ops, lanes, visited, ctx):
        return PullBackend._min_parent_lanes(
            ops, _global_or(lanes, ctx), visited, ctx
        )

    @staticmethod
    def min_dist(ops, dist, frontier, ctx):
        du = jnp.where(frontier, dist, jnp.inf)
        return PullBackend._min_dist(
            ops, _global_min(du, ctx, jnp.float32(jnp.inf)), ctx
        )

    @staticmethod
    def reach_parent_dense(ops, frontier, visited, ctx):
        gf = _global_or(frontier, ctx)  # one union serves both scans
        return (
            PullBackend._reach_dense(ops, gf, visited, ctx),
            PullBackend._min_parent(ops, gf, visited, ctx),
        )

    @staticmethod
    def reach_parent_lanes(ops, lanes, visited, ctx):
        gl = _global_or(lanes, ctx)
        return (
            PullBackend._reach_lanes(ops, gl, visited, ctx),
            PullBackend._min_parent_lanes(ops, gl, visited, ctx),
        )

    # additive push has no pull realization worth keeping (gather-sum over
    # rev scans the same edge set at the same cost); top-k is pull-native
    push_sum = staticmethod(PushBackend.push_sum)
    min_topk = staticmethod(_min_topk_pull)


# ---------------------------------------------------------------------------
# pull_binned — the pull gather over degree-binned reverse slabs.
# ---------------------------------------------------------------------------


def _binned_map(bn: BinnedRevEll, per_slab, neutral):
    """Run ``per_slab(slab_idx, slab)`` over every nonempty slab, produce
    the ``neutral(rows_b)`` value for zero-width/zero-row slabs, and
    un-permute the concatenated per-binned-row results back to original
    local-row order. ``per_slab`` maps ``[rows_b, width_b]`` indices to a
    ``[rows_b, ...]`` reduction; padding rows/slots carry the sentinel
    index so gathers fill with the reduction's neutral element."""
    parts = []
    for b, slab in enumerate(bn.slabs):
        s = slab[0]  # shard-local slice: [rows_b, width_b]
        if s.shape[0] == 0 or s.shape[1] == 0:
            parts.append(neutral(s.shape[0]))
        else:
            parts.append(per_slab(b, s))
    cat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    return cat[bn.inv[0]]


def _slab_gather_lanes(s: jax.Array, gl: jax.Array) -> jax.Array:
    """[rows_b, width_b] slab indices × [n_out, L] lanes -> [rows_b, L]
    OR-reduction, degree-chunked so the gather temp stays under the
    ``_deg_chunk`` budget even on the hub bucket's widest slab."""
    rows, D = s.shape
    L = gl.shape[-1]
    chunk = _deg_chunk(rows, L)
    if chunk >= D:
        return gl.at[s].get(mode="fill", fill_value=0).max(axis=1)

    def step(start, width, acc):
        idx = lax.dynamic_slice_in_dim(s, start, width, 1)
        got = gl.at[idx].get(mode="fill", fill_value=0)
        return jnp.maximum(acc, got.max(axis=1))

    return chunk_fold(D, chunk, step, jnp.zeros((rows, L), gl.dtype))


def _slab_min_parent_lanes(s: jax.Array, gl: jax.Array) -> jax.Array:
    """Per-lane min-parent over one binned slab, degree-chunked like
    ``_slab_gather_lanes`` (candidate temp is [rows_b, chunk, L] int32)."""
    rows, D = s.shape
    L = gl.shape[-1]
    chunk = _deg_chunk(rows, 4 * L)

    def step(start, width, acc):
        idx = (
            s if width == D else lax.dynamic_slice_in_dim(s, start, width, 1)
        )
        act = gl.at[idx].get(mode="fill", fill_value=0)
        cand = jnp.where(
            act != 0, idx[:, :, None].astype(jnp.int32), NO_PARENT
        )
        return jnp.minimum(acc, cand.min(axis=1))

    acc0 = jnp.full((rows, L), NO_PARENT, jnp.int32)
    if chunk >= D:
        return step(0, D, acc0)
    return chunk_fold(D, chunk, step, acc0)


class BinnedPullBackend:
    """The ``ell_pull`` contract over ``BinnedRevEll`` slabs.

    Identical math to PullBackend — same reverse edge set (both derive
    from the truncated forward graph), same OR/min merges, same
    visited-suppression — so final states stay bit-identical; only the
    scan layout changes: each degree bucket is padded to its own width,
    so a full scan costs ~sum(in_deg) slots instead of n·max_in_deg.
    """

    name = "pull_binned"

    # -- collective-free cores (global activation tensors precomputed) ------

    @staticmethod
    def _reach_dense(ops, gf, visited, ctx):
        bn = ops.rev_binned
        rows = bn.rows_local
        reached = _binned_map(
            bn,
            lambda b, s: gf.at[s]
            .get(mode="fill", fill_value=False)
            .any(axis=1),
            lambda r: jnp.zeros((r,), jnp.bool_),
        )
        if visited is not None:
            reached &= ~_local_state(visited, rows, ctx)
        return _place_rows(reached, ctx, False)

    @staticmethod
    def _reach_lanes(ops, gl, visited, ctx):
        bn = ops.rev_binned
        rows = bn.rows_local
        L = gl.shape[-1]
        reached = _binned_map(
            bn,
            lambda b, s: _slab_gather_lanes(s, gl),
            lambda r: jnp.zeros((r, L), gl.dtype),
        )
        if visited is not None:
            vloc = _local_state(visited, rows, ctx)
            reached = jnp.where(vloc != 0, 0, reached)
        return _place_rows(reached, ctx, 0)

    @staticmethod
    def _min_parent(ops, gf, visited, ctx):
        bn = ops.rev_binned
        rows = bn.rows_local
        cand = _binned_map(
            bn,
            lambda b, s: jnp.where(
                gf.at[s].get(mode="fill", fill_value=False), s, NO_PARENT
            ).min(axis=1),
            lambda r: jnp.full((r,), NO_PARENT, jnp.int32),
        )
        if visited is not None:
            cand = jnp.where(
                _local_state(visited, rows, ctx), NO_PARENT, cand
            )
        return _place_rows(cand, ctx, NO_PARENT)

    @staticmethod
    def _min_parent_lanes(ops, gl, visited, ctx):
        bn = ops.rev_binned
        rows = bn.rows_local
        L = gl.shape[-1]

        cand = _binned_map(
            bn,
            lambda b, s: _slab_min_parent_lanes(s, gl),
            lambda r: jnp.full((r, L), NO_PARENT, jnp.int32),
        )
        if visited is not None:
            vloc = _local_state(visited, rows, ctx)
            cand = jnp.where(vloc != 0, NO_PARENT, cand)
        return _place_rows(cand, ctx, NO_PARENT)

    @staticmethod
    def _min_dist(ops, gdu, ctx):
        bn = ops.rev_binned

        def per_slab(b, s):
            w = (
                bn.slab_weights[b][0]
                if bn.slab_weights is not None
                else jnp.ones(s.shape, jnp.float32)
            )
            got = gdu.at[s].get(mode="fill", fill_value=jnp.inf)
            return (got + w).min(axis=1)

        cand = _binned_map(
            bn, per_slab, lambda r: jnp.full((r,), jnp.inf, jnp.float32)
        )
        return _place_rows(cand, ctx, jnp.float32(jnp.inf))

    # -- public contract ----------------------------------------------------

    @staticmethod
    def reach_dense(ops, frontier, visited, ctx):
        return BinnedPullBackend._reach_dense(
            ops, _global_or(frontier, ctx), visited, ctx
        )

    @staticmethod
    def reach_lanes(ops, lanes, visited, ctx):
        return BinnedPullBackend._reach_lanes(
            ops, _global_or(lanes, ctx), visited, ctx
        )

    @staticmethod
    def min_parent(ops, frontier, visited, ctx):
        return BinnedPullBackend._min_parent(
            ops, _global_or(frontier, ctx), visited, ctx
        )

    @staticmethod
    def min_parent_lanes(ops, lanes, visited, ctx):
        return BinnedPullBackend._min_parent_lanes(
            ops, _global_or(lanes, ctx), visited, ctx
        )

    @staticmethod
    def min_dist(ops, dist, frontier, ctx):
        du = jnp.where(frontier, dist, jnp.inf)
        return BinnedPullBackend._min_dist(
            ops, _global_min(du, ctx, jnp.float32(jnp.inf)), ctx
        )

    @staticmethod
    def reach_parent_dense(ops, frontier, visited, ctx):
        gf = _global_or(frontier, ctx)  # one union serves both scans
        return (
            BinnedPullBackend._reach_dense(ops, gf, visited, ctx),
            BinnedPullBackend._min_parent(ops, gf, visited, ctx),
        )

    @staticmethod
    def reach_parent_lanes(ops, lanes, visited, ctx):
        gl = _global_or(lanes, ctx)
        return (
            BinnedPullBackend._reach_lanes(ops, gl, visited, ctx),
            BinnedPullBackend._min_parent_lanes(ops, gl, visited, ctx),
        )

    push_sum = staticmethod(PushBackend.push_sum)
    min_topk = staticmethod(_min_topk_pull)


# ---------------------------------------------------------------------------
# pull_binned_fused — the binned pull realized by the fused Pallas kernel.
# ---------------------------------------------------------------------------


class FusedBinnedPullBackend:
    """``pull_binned`` realized by the fused slab-major Pallas kernel.

    Same binned reverse edge set, same reductions, same suppression —
    bit-identical final states — but gathers, reductions, un-permute and
    suppression happen in one VMEM pass per row tile
    (``kernels.binned_pull``), with fully-visited row tiles skipped via the
    scalar-prefetched activity bitmap. Scans ``ops.rev_binned_pack``, the
    row-padded kernel repack of the same ``BinnedRevEll``.
    """

    name = "pull_binned_fused"

    # -- collective-free cores (global activation tensors precomputed) ------

    @staticmethod
    def _reach_dense(ops, gf, visited, ctx):
        pk = ops.rev_binned_pack
        vloc = (
            None
            if visited is None
            else _local_state(visited, pk.rows_local, ctx)
        )
        reached = _fused_pull(
            pk, gf.astype(jnp.uint8), vloc, op="reach"
        )
        return _place_rows(reached != 0, ctx, False)

    @staticmethod
    def _reach_lanes(ops, gl, visited, ctx):
        pk = ops.rev_binned_pack
        vloc = (
            None
            if visited is None
            else _local_state(visited, pk.rows_local, ctx)
        )
        reached = _fused_pull(pk, gl, vloc, op="reach_lanes")
        return _place_rows(reached.astype(gl.dtype), ctx, 0)

    @staticmethod
    def _min_parent(ops, gf, visited, ctx):
        pk = ops.rev_binned_pack
        vloc = (
            None
            if visited is None
            else _local_state(visited, pk.rows_local, ctx)
        )
        cand = _fused_pull(
            pk, gf.astype(jnp.uint8), vloc, op="min_parent"
        )
        return _place_rows(cand, ctx, NO_PARENT)

    @staticmethod
    def _min_parent_lanes(ops, gl, visited, ctx):
        pk = ops.rev_binned_pack
        vloc = (
            None
            if visited is None
            else _local_state(visited, pk.rows_local, ctx)
        )
        cand = _fused_pull(pk, gl, vloc, op="min_parent_lanes")
        return _place_rows(cand, ctx, NO_PARENT)

    @staticmethod
    def _min_dist(ops, gdu, ctx):
        pk = ops.rev_binned_pack
        cand = _fused_pull(pk, gdu, None, op="min_dist")
        return _place_rows(cand, ctx, jnp.float32(jnp.inf))

    # -- public contract ----------------------------------------------------

    @staticmethod
    def reach_dense(ops, frontier, visited, ctx):
        return FusedBinnedPullBackend._reach_dense(
            ops, _global_or(frontier, ctx), visited, ctx
        )

    @staticmethod
    def reach_lanes(ops, lanes, visited, ctx):
        return FusedBinnedPullBackend._reach_lanes(
            ops, _global_or(lanes, ctx), visited, ctx
        )

    @staticmethod
    def min_parent(ops, frontier, visited, ctx):
        return FusedBinnedPullBackend._min_parent(
            ops, _global_or(frontier, ctx), visited, ctx
        )

    @staticmethod
    def min_parent_lanes(ops, lanes, visited, ctx):
        return FusedBinnedPullBackend._min_parent_lanes(
            ops, _global_or(lanes, ctx), visited, ctx
        )

    @staticmethod
    def min_dist(ops, dist, frontier, ctx):
        du = jnp.where(frontier, dist, jnp.inf)
        return FusedBinnedPullBackend._min_dist(
            ops, _global_min(du, ctx, jnp.float32(jnp.inf)), ctx
        )

    @staticmethod
    def reach_parent_dense(ops, frontier, visited, ctx):
        gf = _global_or(frontier, ctx)  # one union serves both scans
        return (
            FusedBinnedPullBackend._reach_dense(ops, gf, visited, ctx),
            FusedBinnedPullBackend._min_parent(ops, gf, visited, ctx),
        )

    @staticmethod
    def reach_parent_lanes(ops, lanes, visited, ctx):
        gl = _global_or(lanes, ctx)
        return (
            FusedBinnedPullBackend._reach_lanes(ops, gl, visited, ctx),
            FusedBinnedPullBackend._min_parent_lanes(ops, gl, visited, ctx),
        )

    push_sum = staticmethod(PushBackend.push_sum)
    min_topk = staticmethod(_min_topk_pull)


# ---------------------------------------------------------------------------
# block_mxu — saturating matmul over per-shard blocks with stripe skipping.
# ---------------------------------------------------------------------------


def block_stripe_activity(lane_blocks: jax.Array) -> jax.Array:
    """[rb, B, L] -> [rb] bool: which source row-block stripes hold any
    frontier bit. The Pallas kernel uses the same bitmap to skip inactive
    blocks via scalar-prefetch indices; here it masks contributions (and is
    the measured 'touched blocks' economy in benchmarks)."""
    return (lane_blocks != 0).any(axis=(1, 2))


class BlockBackend:
    """OR-reach on the MXU block path; candidate-parent / weighted-relax
    scans have no saturating-0/1 formulation and stay on the push ELL
    (same merged values either way, so results remain bit-identical)."""

    name = "block_mxu"

    @staticmethod
    def reach_lanes(ops, lanes, visited, ctx):
        sb = ops.blocks
        blocks = sb.blocks[0]
        brows = sb.block_rows[0]
        bcols = sb.block_cols[0]
        B = sb.block_size
        rows = ops.fwd.indices.shape[0]
        local = _local_state(lanes, rows, ctx)
        L = local.shape[-1]
        lane_blocks = local.reshape(rows // B, B, L)
        act = block_stripe_activity(lane_blocks)
        src = jnp.take(lane_blocks, brows, axis=0)  # [nb, B, L]
        partial = lax.dot_general(
            blocks.astype(jnp.int32),
            src.astype(jnp.int32),
            dimension_numbers=(((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        )  # [nb, B(dst), L]
        hit = ((partial > 0) & act[brows][:, None, None]).astype(jnp.uint8)
        G = ctx.n_out // B
        out = jnp.zeros((G, B, L), jnp.uint8)
        out = out.at[bcols].max(hit, mode="drop")  # sentinel col G drops
        return out.reshape(ctx.n_out, L)

    @staticmethod
    def reach_dense(ops, frontier, visited, ctx):
        lanes = frontier[:, None].astype(jnp.uint8)
        return BlockBackend.reach_lanes(ops, lanes, visited, ctx)[:, 0] != 0

    @staticmethod
    def push_sum(ops, values, ctx, normalize=False):
        """Additive count/mass propagation as a non-saturating block matmul:
        ``out[v] = Σ_u values[u]·A[u, v]`` — the pattern-count hop chain on
        the MXU. Bit-identical to the push-ELL scatter for integer values
        (addition is exact either way); float values may differ in the last
        ulp from the scatter order, so float diffusion routes to ell_push.
        """
        sb = ops.blocks
        if sb is None:
            return PushBackend.push_sum(ops, values, ctx, normalize)
        blocks = sb.blocks[0]
        brows = sb.block_rows[0]
        bcols = sb.block_cols[0]
        B = sb.block_size
        rows = ops.fwd.indices.shape[0]
        local = _local_state(values, rows, ctx)
        if normalize:
            local = local / jnp.maximum(ops.fwd.degrees, 1).astype(
                local.dtype
            )
        src = jnp.take(local.reshape(rows // B, B), brows, axis=0)
        partial = lax.dot_general(
            blocks.astype(local.dtype),
            src[:, :, None],
            dimension_numbers=(((1,), (1,)), ((0,), (0,))),
            preferred_element_type=local.dtype,
        )[:, :, 0]  # [nb, B(dst)]
        G = ctx.n_out // B
        out = jnp.zeros((G, B), local.dtype)
        out = out.at[bcols].add(partial, mode="drop")  # sentinel col drops
        return out.reshape(ctx.n_out)

    min_parent = staticmethod(PushBackend.min_parent)
    min_parent_lanes = staticmethod(PushBackend.min_parent_lanes)
    min_dist = staticmethod(PushBackend.min_dist)
    min_topk = staticmethod(_min_topk_pull)

    @staticmethod
    def reach_parent_dense(ops, frontier, visited, ctx):
        return (
            BlockBackend.reach_dense(ops, frontier, visited, ctx),
            PushBackend.min_parent(ops, frontier, visited, ctx),
        )

    @staticmethod
    def reach_parent_lanes(ops, lanes, visited, ctx):
        return (
            BlockBackend.reach_lanes(ops, lanes, visited, ctx),
            PushBackend.min_parent_lanes(ops, lanes, visited, ctx),
        )


# ---------------------------------------------------------------------------
# direction="auto" — Beamer alpha/beta switch between push and pull.
# ---------------------------------------------------------------------------


def _predicate_locals(ops, frontier, visited, ctx: ExtendCtx):
    """This shard's contributions to the Beamer predicate's inputs:
    ``(n_f, m_f, m_u, unvis)`` — active-row count, frontier out-edge
    mass, unexplored out-edge mass (all pre-psum local partials, float32)
    plus the local unvisited-row mask (None when the edge compute keeps
    no visited set — nothing is ever suppressed, so m_u degrades to
    total minus frontier mass)."""
    g = ops.fwd
    rows = g.indices.shape[0]
    floc = _local_state(frontier, rows, ctx)
    act = (floc != 0) if floc.ndim == 1 else (floc != 0).any(axis=-1)
    deg = g.degrees.astype(jnp.float32)
    n_f = act.sum(dtype=jnp.float32)
    m_f = jnp.sum(deg * act)
    if visited is not None:
        vloc = _local_state(visited, rows, ctx)
        vis = (vloc != 0) if vloc.ndim == 1 else (vloc != 0).any(-1)
        unvis = ~vis
        m_u = jnp.sum(deg * unvis)
    else:
        unvis = None
        m_u = deg.sum() - m_f
    return n_f, m_f, m_u, unvis


#: columns of one ``frontier_stats`` sample (and of the ``collect_stats``
#: carry rows the engine builders write)
STATS_WIDTH = 6
#: bytes one int32 adjacency slot streams through an extension scan
#: (4 B neighbor id + 1 B activation read/write) — the analytic factor the
#: measured-cost lane multiplies slot counts by
BYTES_PER_SLOT = 5.0


def frontier_stats(ops, state, ctx: ExtendCtx, bin_widths=None):
    """One per-iteration sample for the online direction-threshold
    learner: ``[n_f, m_f, m_u, pull_slots_binned, wall_ms, pull_bytes]``
    (float32, reduced over ``ctx.axes``) of the state ABOUT to extend —
    the inputs of the Beamer predicate plus the slots a degree-binned
    pull would scan at this state (the widths of the still-unvisited
    rows; full capacity when the edge compute keeps no visited set).
    ``bin_widths`` is this shard's per-local-row slab width vector; when
    the engine's operands carry no binned slabs the cost columns are the
    sentinel ``-1`` and the record is skipped by
    ``fit_direction_thresholds``.

    The measured-cost lane: ``pull_bytes`` is the device-computable
    analytic stream volume (``BYTES_PER_SLOT`` × slots); ``wall_ms`` is a
    *host-filled* column — it stays at the ``-1`` sentinel on device and
    the dispatcher's :class:`BackendCostProbe` converts slot columns to
    per-backend wall estimates when a ``cost="measured"`` consumer asks
    (device-time via a profiler hook on real TPU, ``time.perf_counter``
    under interpret/CPU).

    This is the sample tap ``build_engine(collect_stats=True)`` (and the
    resume/gang builders') writes into the while_loop carry: a pure
    readout of (frontier, visited), so instrumented engines stay
    bit-identical in result state. Semantics match
    benchmarks/direction_opt.py's host-side accounting record-for-record.
    """
    frontier = state.frontier
    visited = getattr(state, "visited", None)
    n_f, m_f, m_u, unvis = _predicate_locals(ops, frontier, visited, ctx)
    if bin_widths is None:
        pull = jnp.float32(0.0)
    elif unvis is None:
        pull = bin_widths.sum()
    else:
        pull = jnp.sum(bin_widths * unvis)
    stats = jnp.stack(
        [n_f, m_f, m_u, pull, jnp.float32(0.0), pull * BYTES_PER_SLOT]
    )
    if ctx.axes:
        stats = lax.psum(stats, ctx.axes)
    stats = stats.at[4].set(-1.0)  # wall: host-filled, never device-summed
    if bin_widths is None:
        stats = stats.at[3].set(-1.0).at[5].set(-1.0)
    return stats


class AutoBackend:
    """Per-iteration push/pull choice under fixed shapes.

    The predicate is a pure function of (frontier, visited) reduced over the
    graph axes, so every device of a sync group agrees; the pull branch's
    global activation tensors are computed *before* the ``lax.cond`` so the
    branches themselves hold no collectives (deadlock-free under shard_map).
    """

    name = "dopt"

    def __init__(self, spec: ExtendSpec):
        self.alpha = spec.alpha
        self.beta = spec.beta
        # bottom-up flavor of the switch: degree-binned slabs (default),
        # the fused kernel over the same slabs, or the single padded
        # reverse ELL — same math, different scan
        self.pull_be = {
            "binned": BinnedPullBackend,
            "binned_fused": FusedBinnedPullBackend,
            "ell": PullBackend,
        }[spec.pull]

    def _use_pull(self, ops, frontier, visited, ctx):
        n_f, m_f, m_u, _ = _predicate_locals(ops, frontier, visited, ctx)
        stats = jnp.stack([n_f, m_f, m_u])
        if ctx.axes:
            stats = lax.psum(stats, ctx.axes)
        n_f, m_f, m_u = stats[0], stats[1], stats[2]
        return (m_f * self.alpha > m_u) & (n_f * self.beta > ctx.n_out)

    def _switch(self, ops, frontier, visited, ctx, pull_fn, push_fn):
        pred = self._use_pull(ops, frontier, visited, ctx)
        return lax.cond(pred, pull_fn, push_fn)

    def reach_dense(self, ops, frontier, visited, ctx):
        gf = _global_or(frontier, ctx)
        return self._switch(
            ops, frontier, visited, ctx,
            lambda: self.pull_be._reach_dense(ops, gf, visited, ctx),
            lambda: PushBackend.reach_dense(ops, frontier, visited, ctx),
        )

    def reach_lanes(self, ops, lanes, visited, ctx):
        gl = _global_or(lanes, ctx)
        return self._switch(
            ops, lanes, visited, ctx,
            lambda: self.pull_be._reach_lanes(ops, gl, visited, ctx),
            lambda: PushBackend.reach_lanes(ops, lanes, visited, ctx),
        )

    def min_parent(self, ops, frontier, visited, ctx):
        gf = _global_or(frontier, ctx)
        return self._switch(
            ops, frontier, visited, ctx,
            lambda: self.pull_be._min_parent(ops, gf, visited, ctx),
            lambda: PushBackend.min_parent(ops, frontier, visited, ctx),
        )

    def min_parent_lanes(self, ops, lanes, visited, ctx):
        gl = _global_or(lanes, ctx)
        return self._switch(
            ops, lanes, visited, ctx,
            lambda: self.pull_be._min_parent_lanes(ops, gl, visited, ctx),
            lambda: PushBackend.min_parent_lanes(ops, lanes, visited, ctx),
        )

    def min_dist(self, ops, dist, frontier, ctx):
        du = jnp.where(frontier, dist, jnp.inf)
        gdu = _global_min(du, ctx, jnp.float32(jnp.inf))
        return self._switch(
            ops, frontier, None, ctx,
            lambda: self.pull_be._min_dist(ops, gdu, ctx),
            lambda: PushBackend.min_dist(ops, dist, frontier, ctx),
        )

    # additive push and top-k relax have one physical form each (scatter-add
    # resp. reverse gather) — no direction decision to make
    def push_sum(self, ops, values, ctx, normalize=False):
        return PushBackend.push_sum(ops, values, ctx, normalize)

    def min_topk(self, ops, dists, src_mask, ctx):
        return _min_topk_pull(ops, dists, src_mask, ctx)

    # one union + one predicate + one cond for or_min edge computes
    def reach_parent_dense(self, ops, frontier, visited, ctx):
        gf = _global_or(frontier, ctx)
        return self._switch(
            ops, frontier, visited, ctx,
            lambda: (
                self.pull_be._reach_dense(ops, gf, visited, ctx),
                self.pull_be._min_parent(ops, gf, visited, ctx),
            ),
            lambda: PushBackend.reach_parent_dense(
                ops, frontier, visited, ctx
            ),
        )

    def reach_parent_lanes(self, ops, lanes, visited, ctx):
        gl = _global_or(lanes, ctx)
        return self._switch(
            ops, lanes, visited, ctx,
            lambda: (
                self.pull_be._reach_lanes(ops, gl, visited, ctx),
                self.pull_be._min_parent_lanes(ops, gl, visited, ctx),
            ),
            lambda: PushBackend.reach_parent_lanes(ops, lanes, visited, ctx),
        )


_FIXED = {
    "ell_push": PushBackend,
    "ell_pull": PullBackend,
    "pull_binned": BinnedPullBackend,
    "pull_binned_fused": FusedBinnedPullBackend,
    "block_mxu": BlockBackend,
}


def make_backend(spec: ExtendSpec):
    """ExtendSpec -> backend object implementing the primitive contract."""
    if spec.direction == "auto":
        return AutoBackend(spec)
    return _FIXED[spec.backend]


class BackendCostProbe:
    """Measured per-slot extension cost — the ``cost="measured"`` lane.

    ``rates(ops, n_pad)`` times one jitted ``reach_dense`` step per backend
    the operand bundle supports (push always; jnp binned pull and the fused
    kernel when their operands are present) against a half-full frontier,
    and divides by each backend's full-scan slot count. The resulting
    ms/slot rates convert the slot columns of ``frontier_stats`` samples
    into per-iteration wall estimates without perturbing the engines — the
    probe runs out-of-band on the same device-placed operands.

    Timing source: ``device_timer(fn, *args) -> ms`` when given (on real
    TPU, a profiler hook reading device time / DMA bytes); otherwise the
    host fallback — ``block_until_ready`` + ``time.perf_counter`` median of
    ``reps``, which is what interpret/CPU CI exercises.
    """

    #: probed backends → the slot count their full scan pays
    def __init__(self, reps: int = 3, device_timer=None):
        self.reps = int(reps)
        self.device_timer = device_timer

    def measure_ms(self, fn, *args) -> float:
        if self.device_timer is not None:
            return float(self.device_timer(fn, *args))
        jax.block_until_ready(fn(*args))  # compile outside the timing
        walls = []
        for _ in range(self.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            walls.append((time.perf_counter() - t0) * 1e3)
        walls.sort()
        return walls[len(walls) // 2]

    def rates(self, ops, n_pad: int) -> dict:
        """``{backend: {"ms_per_slot", "bytes_per_slot", "probe_ms",
        "slots"}}`` for every backend ``ops`` can run. Bytes are the
        analytic ``BYTES_PER_SLOT`` stream volume; wall is measured.

        Operands stacked for K > 1 graph shards are probed on shard 0
        alone — the slice one device scans inside the engines — since the
        backends' binned views read only their first shard."""
        ops = as_operands(ops)
        binned = (
            ops.rev_binned if ops.rev_binned is not None
            else ops.rev_binned_pack
        )
        k = 1 if binned is None else n_pad // binned.rows_local
        if k > 1:
            ops = jax.tree.map(lambda x: x[: x.shape[0] // k], ops)
        ctx = ExtendCtx(n_out=n_pad, row_offset=0)
        frontier = (
            jnp.arange(n_pad) < max(n_pad // 2, 1)
        )  # half-full: both directions do real work
        visited = jnp.zeros(n_pad, jnp.bool_)
        probes = {"ell_push": (PushBackend, int(ops.fwd.indices.size))}
        if ops.rev_binned is not None:
            probes["pull_binned"] = (
                BinnedPullBackend, ops.rev_binned.capacity_slots
            )
        if ops.rev_binned_pack is not None:
            probes["pull_binned_fused"] = (
                FusedBinnedPullBackend, ops.rev_binned_pack.capacity_slots
            )
        out = {}
        for name, (be, slots) in probes.items():
            # operands go in as arguments: a closure would bake the padded
            # ELL into the program as constants (GiBs at a real size)
            fn = jax.jit(
                lambda o, f, v, be=be: be.reach_dense(o, f, v, ctx)
            )
            ms = self.measure_ms(fn, ops, frontier, visited)
            out[name] = {
                "ms_per_slot": ms / max(slots, 1),
                "bytes_per_slot": BYTES_PER_SLOT,
                "probe_ms": ms,
                "slots": slots,
            }
        return out
