"""jit'd wrapper + host-side operand pack for the fused binned-pull kernel."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .binned_pull import (
    OPS,
    TilePlan,
    fused_binned_pull,
    make_plan,
    tile_rows,
)
from .ref import fused_binned_pull_ref


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BinnedPullPack:
    """Kernel-ready repack of the reverse ``graph.csr.BinnedEll``.

    Same edge set and the same perm/inverse contract, re-laid-out for the
    fused kernel: every nonzero-width slab is row-padded to a multiple of
    its compute-tile rows (pad rows all-sentinel ⇒ gather the neutral), and
    the permutation pair is re-indexed into the padded binned-position
    space. ``K`` is the graph shard count; leading axes shard over the
    policy's graph mesh axes exactly like the source ``BinnedEll``.
    """

    slabs: tuple  # of [K, rows_pad_b, width_b] int32 (nonzero-width buckets)
    inv_pad: jax.Array  # [K, rows_local] int32 (local row -> padded pos)
    perm_pad: jax.Array  # [K, rbp] int32 (padded pos -> local row;
    #                       sentinel rows_local at pad positions)
    slab_weights: Optional[tuple] = None  # matching [K, rows_pad_b, w] f32

    @property
    def rows_local(self) -> int:
        return int(self.inv_pad.shape[-1])

    @property
    def n_shards(self) -> int:
        return int(self.inv_pad.shape[0])

    @property
    def widths(self) -> tuple:
        return tuple(int(s.shape[-1]) for s in self.slabs)

    @property
    def capacity_slots(self) -> int:
        """One shard's full-scan adjacency slots **including** the kernel's
        row-tile padding (≥ the source structure's ``capacity_slots``)."""
        return int(sum(s.shape[-2] * s.shape[-1] for s in self.slabs))


def pack_plan(pack: BinnedPullPack) -> TilePlan:
    """Rebuild the static grid layout from the pack's shapes alone (the
    same deterministic rule ``build_pack`` padded with)."""
    rows_pad = tuple(int(s.shape[-2]) for s in pack.slabs)
    return make_plan(
        widths=tuple(int(s.shape[-1]) for s in pack.slabs),
        rows_pad=rows_pad,
        zero_rows=int(pack.perm_pad.shape[-1]) - sum(rows_pad),
    )


def build_pack(bn, n_pad: int, as_numpy: bool = False) -> BinnedPullPack:
    """Host-side (numpy, deterministic) repack of a reverse ``BinnedEll``.

    ``n_pad`` is the padded node count — the slab sentinel value.
    ``as_numpy`` keeps the leaves as host numpy arrays (the streamed
    operand build places them per device itself); every transform is
    rowwise per shard, so a ``K=1`` input yields exactly the matching
    shard slice of the full pack."""
    conv = np.ascontiguousarray if as_numpy else jnp.asarray
    k = int(bn.inv.shape[0])
    rows_local = bn.rows_local
    widths = bn.widths
    assert widths[0] == 0 and all(w > 0 for w in widths[1:]), widths
    rows_raw = [int(s.shape[-2]) for s in bn.slabs]
    rows_pad = [
        -(-r // tile_rows(w)) * tile_rows(w)
        for w, r in zip(widths[1:], rows_raw[1:])
    ]
    # padded position of each unpadded binned position (bucket order:
    # zero-width rows first, then the row-padded nonzero slabs)
    starts = np.concatenate([[0], np.cumsum(rows_raw)])[:-1]
    seg = np.asarray([rows_raw[0]] + rows_pad, np.int64)
    pstarts = np.concatenate([[0], np.cumsum(seg)])[:-1]
    rbp = int(seg.sum())
    bop = np.repeat(np.arange(len(widths)), rows_raw)
    pp = pstarts[bop] + np.arange(int(np.sum(rows_raw))) - starts[bop]
    inv_pad = pp[np.asarray(bn.inv)].astype(np.int32)
    perm_pad = np.full((k, rbp), rows_local, np.int32)
    perm_pad[:, pp] = np.asarray(bn.perm)
    slabs, wslabs = [], []
    for b in range(1, len(widths)):
        s = np.asarray(bn.slabs[b])
        pad = rows_pad[b - 1] - s.shape[1]
        fill = np.full((k, pad, widths[b]), n_pad, np.int32)
        slabs.append(conv(np.concatenate([s, fill], axis=1)))
        if bn.slab_weights is not None:
            wv = np.asarray(bn.slab_weights[b])
            wfill = np.zeros((k, pad, widths[b]), np.float32)
            wslabs.append(conv(np.concatenate([wv, wfill], axis=1)))
    return BinnedPullPack(
        slabs=tuple(slabs),
        inv_pad=conv(inv_pad),
        perm_pad=conv(perm_pad),
        slab_weights=(
            tuple(wslabs) if bn.slab_weights is not None else None
        ),
    )


@partial(jax.jit, static_argnames=("op", "interpret", "use_ref"))
def binned_pull(
    pack: BinnedPullPack,
    gsrc: jax.Array,  # [n_out](, L): uint8 mask (reach/parent) or f32 dist
    vloc: jax.Array | None = None,  # [rows_local](, L) bool/uint8 visited
    *,
    op: str,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> jax.Array:
    """Fused pull extension of one shard's rows.

    Like the jnp path's ``slab[0]`` convention, the wrapper consumes shard 0
    of the pack it is given — inside ``shard_map`` every shard sees its own
    ``K=1`` slice. Returns ``[rows_local]`` (``[rows_local, L]`` for the
    ``*_lanes`` ops): uint8 reach mask, int32 min-parent, or f32 distance.
    """
    assert op in OPS, op
    plan = pack_plan(pack)
    slabs = [s[0] for s in pack.slabs]
    wslabs = None
    if op == "min_dist" and pack.slab_weights is not None:
        wslabs = [w[0] for w in pack.slab_weights]
    inv = pack.inv_pad[0]
    vloc_u8 = None if vloc is None else vloc.astype(jnp.uint8)
    if use_ref:
        return fused_binned_pull_ref(
            op, plan, slabs, wslabs, gsrc, inv, vloc_u8
        )
    tile_act = None
    if vloc_u8 is not None and plan.t_compute > 0:
        # per-compute-tile activity: a tile is active iff any (row, lane)
        # it feeds is still unvisited (else its output is suppressed)
        unvis = vloc_u8 == 0
        if unvis.ndim == 2:
            unvis = unvis.any(axis=-1)
        ub = jnp.concatenate([unvis, jnp.zeros((1,), bool)])[
            pack.perm_pad[0]
        ]
        acts = []
        for b in range(len(plan.widths)):
            a0 = plan.astarts[b]
            seg = ub[a0 : a0 + plan.rows_pad[b]]
            acts.append(
                seg.reshape(plan.ntiles[b], plan.trs[b]).any(axis=1)
            )
        tile_act = jnp.concatenate(acts).astype(jnp.int32)
    return fused_binned_pull(
        op, plan, slabs, wslabs, gsrc, inv, vloc_u8, tile_act,
        interpret=interpret,
    )


def pack_tile_map(pack: BinnedPullPack):
    """Host-side scanned-slot accounting for shard 0.

    Returns ``(tile_of_row, tile_slots)``: the compute-tile id of every
    local row (``-1`` for zero-in-degree rows, which no tile scans) and the
    int32 adjacency slots each compute tile pays. Used by the benchmark's
    fused-scan accounting and the coverage proptest."""
    plan = pack_plan(pack)
    inv = np.asarray(pack.inv_pad[0]).astype(np.int64)
    tile_of_acc = np.full(plan.rbp, -1, np.int64)
    slots = []
    for b in range(len(plan.widths)):
        a0, tr = plan.astarts[b], plan.trs[b]
        rel = np.arange(plan.rows_pad[b]) // tr
        tile_of_acc[a0 : a0 + plan.rows_pad[b]] = plan.t_starts[b] + rel
        slots.extend([tr * plan.widths[b]] * plan.ntiles[b])
    return tile_of_acc[inv], np.asarray(slots, np.int64)
