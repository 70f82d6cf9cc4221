"""Pallas TPU kernel: fused slab-major degree-binned pull extension.

One ``pallas_call`` realizes a full bottom-up (pull) frontier extension over
the degree-binned reverse slabs (``graph.csr.BinnedRevEll``): the per-slab
neighbor gathers, the OR / min / min-parent reduction over each row's slab
width, the ``inv`` un-permute back to local row order, and the visited
suppression — work the jnp path (``core.extend.BinnedPullBackend``) spreads
over one XLA gather per slab plus a final re-gather through HBM.

Grid layout (1-D sequential): ``T_compute`` slab row-tile steps followed by
``T_out`` output row-tile steps.

* Compute step ``i`` owns one ``[TR_b, width_b]`` tile of one nonzero-width
  slab ``b`` (native width — no cross-slab width padding; ``TR_b`` is chosen
  per slab so a tile holds ~``TILE_SLOTS`` int32 entries). It gathers the
  source value of every neighbor id from the VMEM-resident source vector,
  reduces over the width axis, and combines into a persistent VMEM scratch
  accumulator at the tile's padded-binned-position offset.
* Output step ``j = i - T_compute`` gathers the accumulator through the
  padded inverse permutation for one ``[TR_OUT]`` tile of local rows, applies
  the visited suppression, and writes the output tile.

Frontier-inactive tiles are skipped with the ``msbfs_extend`` activity trick:
a scalar-prefetched per-tile activity bitmap gates the compute under
``pl.when``, and a cummax'd per-slab tile selector re-addresses inactive
steps at the previously fetched tile so the slab DMA is elided entirely.
A tile is *inactive* when every (row, lane) it feeds is already visited —
its contribution is suppressed to the neutral element either way, so
skipping is bit-identical to computing.

The source vector (frontier / lane mask / distance vector being pulled from)
is held as a single VMEM-resident block padded to a multiple of 128 with the
gather-neutral value, so sentinel slab entries (= padded node count) gather
the neutral **in-bounds**. This sizes the kernel for graphs whose padded
node vector fits VMEM alongside one slab tile; the streaming row-block
variant for larger graphs is a ROADMAP follow-on.

The kernel runs in interpret mode only. The TPU (Mosaic) lowering for
v5e refuses it three ways: the rank-1 output-phase row tiles
(``out_tile_rows``) are not multiples of the 128-element (int32) /
512-element (uint8) tiling; the source-vector gather ``gsrc[idx]`` is a
general 1-D gather ("Only 2D gather is supported"); and the lane ops'
gather fails its shape check. Compiling it for a TPU therefore raises
``FusedPullNotLowerable`` instead of interpreting or falling back to the
jnp reference; ``tests/test_v5e_compile.py`` keeps the refusal on record.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import default_interpret

# matches core.edge_compute.NO_PARENT; a numpy scalar so the kernel closes
# over a compile-time constant rather than capturing a traced array
NO_PARENT = np.int32(2**31 - 1)

OPS = ("reach", "reach_lanes", "min_parent", "min_parent_lanes", "min_dist")
LANE_OPS = ("reach_lanes", "min_parent_lanes")

TILE_SLOTS = 4096  # target int32 adjacency slots per compute tile (16 KiB)
MIN_TILE_ROWS = 8
MAX_TILE_ROWS = 256


class FusedPullNotLowerable(NotImplementedError):
    """The fused binned-pull kernel cannot be compiled for a TPU."""


def require_lowerable() -> None:
    """Raise ``FusedPullNotLowerable`` when JAX's backend is a TPU."""
    if jax.default_backend() == "tpu":
        raise FusedPullNotLowerable(
            "the fused binned-pull kernel (backends pull_binned_fused / "
            "dopt_fused) does not lower for TPU: Mosaic refuses its 1-D "
            "row tiles and its general VMEM gather; serve pull_binned or "
            "dopt_binned instead"
        )


def tile_rows(width: int) -> int:
    """Compute-tile rows for a width-``width`` slab (multiple of 8)."""
    tr = TILE_SLOTS // max(int(width), 1)
    tr = (tr // MIN_TILE_ROWS) * MIN_TILE_ROWS
    return max(MIN_TILE_ROWS, min(MAX_TILE_ROWS, tr))


def out_tile_rows(rows_local: int) -> int:
    """Output-tile rows: the largest pow2 ≤ 256 dividing ``rows_local``."""
    for tro in (256, 128, 64, 32, 16, 8, 4, 2):
        if rows_local % tro == 0:
            return tro
    return 1


def op_config(op: str):
    """Per-op (accumulator dtype, reduction neutral, source-vector pad value,
    visited-suppression value, combine) — shared by kernel and oracle."""
    if op in ("reach", "reach_lanes"):
        return jnp.uint8, 0, 0, 0, jnp.maximum
    if op in ("min_parent", "min_parent_lanes"):
        return jnp.int32, NO_PARENT, 0, NO_PARENT, jnp.minimum
    assert op == "min_dist", op
    return jnp.float32, jnp.inf, jnp.inf, None, jnp.minimum


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Static slab→grid layout, derived purely from padded slab shapes.

    The accumulator lays padded binned positions out in bucket order: the
    zero-width bucket's rows first (no compute steps — they stay at the
    neutral), then each nonzero-width slab's row-padded segment."""

    widths: tuple  # nonzero-width slab widths, bucket order
    trs: tuple  # compute-tile rows per slab
    rows_pad: tuple  # row-padded rows per slab (multiple of trs[b])
    ntiles: tuple
    t_starts: tuple  # first grid step of each slab
    astarts: tuple  # accumulator offset of each slab
    zero_rows: int  # zero-width-bucket rows (accumulator prefix)
    t_compute: int
    rbp: int  # accumulator length (padded binned positions)


def make_plan(widths, rows_pad, zero_rows) -> TilePlan:
    trs = tuple(tile_rows(w) for w in widths)
    for w, r, tr in zip(widths, rows_pad, trs):
        assert w > 0 and r > 0 and r % tr == 0, (w, r, tr)
    ntiles = tuple(r // tr for r, tr in zip(rows_pad, trs))
    t_starts, astarts = [], []
    t, a = 0, int(zero_rows)
    for nt, r in zip(ntiles, rows_pad):
        t_starts.append(t)
        astarts.append(a)
        t += nt
        a += r
    return TilePlan(
        widths=tuple(int(w) for w in widths),
        trs=trs,
        rows_pad=tuple(int(r) for r in rows_pad),
        ntiles=ntiles,
        t_starts=tuple(t_starts),
        astarts=tuple(astarts),
        zero_rows=int(zero_rows),
        t_compute=t,
        rbp=a,
    )


def _make_kernel(op, plan, lanes, has_w, has_v):
    acc_dtype, neutral, _, suppress, combine = op_config(op)
    S = len(plan.widths)
    t_compute = plan.t_compute

    def kernel(*refs):
        act_ref = refs[0]
        k = 1 + S  # act + per-slab tile selectors
        slab_refs = refs[k : k + S]
        k += S
        if has_w:
            wslab_refs = refs[k : k + S]
            k += S
        gsrc_ref = refs[k]
        inv_ref = refs[k + 1]
        k += 2
        v_ref = refs[k] if has_v else None
        out_ref = refs[-2]
        acc_ref = refs[-1]

        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.full(acc_ref.shape, neutral, acc_ref.dtype)

        for b in range(S):
            t0 = plan.t_starts[b]
            t1 = t0 + plan.ntiles[b]

            @pl.when((i >= t0) & (i < t1) & (act_ref[i] != 0))
            def _compute(b=b, t0=t0):
                idx = slab_refs[b][...]  # [tr, w] int32
                got = gsrc_ref[...][idx]  # [tr, w] or [tr, w, L]
                if op in ("reach", "reach_lanes"):
                    part = got.max(axis=1)
                elif op == "min_parent":
                    part = jnp.where(got != 0, idx, NO_PARENT).min(axis=1)
                elif op == "min_parent_lanes":
                    part = jnp.where(
                        got != 0, idx[:, :, None], NO_PARENT
                    ).min(axis=1)
                else:  # min_dist
                    w = wslab_refs[b][...] if has_w else jnp.float32(1.0)
                    part = (got + w).min(axis=1)
                tr = plan.trs[b]
                start = plan.astarts[b] + (i - t0) * tr
                sl = (pl.dslice(start, tr),) + (
                    (slice(None),) if lanes else ()
                )
                acc_ref[sl] = combine(acc_ref[sl], part)

        @pl.when(i >= t_compute)
        def _emit():
            res = acc_ref[...][inv_ref[...]]  # [TRO] or [TRO, L]
            if has_v:
                res = jnp.where(v_ref[...] != 0, suppress, res)
            out_ref[...] = res

    return kernel


def fused_binned_pull(
    op: str,
    plan: TilePlan,
    slabs,  # list of [rows_pad_b, width_b] int32 (nonzero-width buckets)
    wslabs,  # None, or matching [rows_pad_b, width_b] f32 (min_dist only)
    gsrc: jax.Array,  # [n_out] or [n_out, L]: uint8 mask or f32 distance
    inv_pad: jax.Array,  # [rows_local] int32 into the padded accumulator
    vloc,  # None, or [rows_local](, L) uint8 (nonzero = visited)
    tile_act,  # None (= all active), or [t_compute] int32 activity bitmap
    interpret: bool | None = None,
) -> jax.Array:
    """Returns the fused pull result ``[rows_local]`` (or ``[rows_local, L]``
    for the lane ops) — uint8 / int32 / f32 per ``op``."""
    interpret = default_interpret(interpret)
    if not interpret:
        require_lowerable()
    assert op in OPS, op
    lanes = op in LANE_OPS
    assert gsrc.ndim == (2 if lanes else 1), (op, gsrc.shape)
    acc_dtype, _, src_pad, _, _ = op_config(op)
    S = len(slabs)
    rows_local = int(inv_pad.shape[0])
    tro = out_tile_rows(rows_local)
    t_out = rows_local // tro
    t_total = plan.t_compute + t_out
    n_out = int(gsrc.shape[0])
    ne = -(-(n_out + 1) // 128) * 128  # sentinel (= n_out) gathers in-bounds
    tail = gsrc.shape[1:]
    gsrc_ext = jnp.concatenate(
        [gsrc, jnp.full((ne - n_out,) + tail, src_pad, gsrc.dtype)]
    )

    # scalar prefetch: activity per grid step + per-slab cummax'd tile
    # selectors (inactive / foreign steps re-address the previous tile so
    # the slab DMA is elided)
    if tile_act is None:
        act = jnp.ones((t_total,), jnp.int32)
    else:
        act = jnp.concatenate(
            [tile_act.astype(jnp.int32), jnp.ones((t_out,), jnp.int32)]
        )
    steps = jnp.arange(t_total, dtype=jnp.int32)
    sels = []
    for b in range(S):
        t0, nt = plan.t_starts[b], plan.ntiles[b]
        in_rng = (steps >= t0) & (steps < t0 + nt)
        cand = jnp.where(in_rng & (act != 0), steps - t0, -1)
        sel = jax.lax.associative_scan(jnp.maximum, cand)
        sels.append(jnp.clip(sel, 0, nt - 1).astype(jnp.int32))

    def slab_spec(b):
        return pl.BlockSpec(
            (plan.trs[b], plan.widths[b]),
            lambda i, a, *s, b=b: (s[b][i], 0),
        )

    def row_spec(shape):  # full-residency source vector
        return pl.BlockSpec(shape, lambda i, a, *s: (0,) * len(shape))

    def out_step_spec(shape):  # output-phase row tiles
        return pl.BlockSpec(
            shape,
            lambda i, a, *s: (jnp.maximum(i - plan.t_compute, 0),)
            + (0,) * (len(shape) - 1),
        )

    inputs = list(slabs)
    in_specs = [slab_spec(b) for b in range(S)]
    has_w = wslabs is not None
    if has_w:
        inputs += list(wslabs)
        in_specs += [slab_spec(b) for b in range(S)]
    inputs.append(gsrc_ext)
    in_specs.append(row_spec(gsrc_ext.shape))
    inputs.append(inv_pad.astype(jnp.int32))
    in_specs.append(out_step_spec((tro,)))
    has_v = vloc is not None
    if has_v:
        v = vloc.astype(jnp.uint8)
        inputs.append(v)
        in_specs.append(out_step_spec((tro,) + v.shape[1:]))

    out_shape = jax.ShapeDtypeStruct((rows_local,) + tail, acc_dtype)
    kernel = _make_kernel(op, plan, lanes, has_w, has_v)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + S,
            grid=(t_total,),
            in_specs=in_specs,
            out_specs=out_step_spec((tro,) + tail),
            scratch_shapes=[pltpu.VMEM((plan.rbp,) + tail, acc_dtype)],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(act, *sels, *inputs)
