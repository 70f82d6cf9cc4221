"""Frontier-union collectives (DESIGN.md §6).

Per IFE iteration under nT1S/nTkS/nTkMS, graph shards must union their partial
next-frontier bitmaps across the graph axes. XLA exposes no OR all-reduce
through jax, so we provide three implementations:

- ``pmax``      — unpacked uint8/bool lanes, ``lax.pmax`` (OR ≡ max). True
                  all-reduce, but 8× wire width vs packed bits.
- ``allgather`` — bit-pack to uint32, ``all_gather`` + local OR fold.
                  (K−1)·N/8 wire bytes per device. Paper-faithful baseline
                  ("every thread sees the whole next frontier").
- ``ring``      — bit-pack + manual reduce-scatter/all-gather rings via
                  ``ppermute`` with bitwise-OR combine: 2·(K−1)/K·N/8 bytes.
                  Beyond-paper optimization (§Perf).

All entry points take/return the *unpacked* layout so callers stay oblivious.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PACK = 32

#: Frontier-union impl for the hybrid's re-dispatch phase. Phase 2 runs nT1S
#: with the graph over ALL mesh axes, so the union spans the largest K in the
#: system — where ring's 2·(K−1)/K·N/8 wire bytes beat allgather's (K−1)·N/8
#: by ~2× and pmax's unpacked lanes by ~8×. Phase-1/static engines keep their
#: policy's own ``or_impl`` (allgather is the paper-faithful baseline).
REDISPATCH_OR_IMPL = "ring"


def _pack_bits(x: jax.Array) -> jax.Array:
    """[..., n] bool/uint8 -> [..., ceil(n/32)] uint32."""
    n = x.shape[-1]
    pad = (-n) % PACK
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((*x.shape[:-1], pad), x.dtype)], axis=-1
        )
    w = x.shape[-1] // PACK
    bits = x.reshape(*x.shape[:-1], w, PACK).astype(jnp.uint32)
    shifts = jnp.arange(PACK, dtype=jnp.uint32)
    return (bits << shifts).sum(axis=-1, dtype=jnp.uint32)


def _unpack_bits(p: jax.Array, n: int) -> jax.Array:
    """[..., w] uint32 -> [..., n] bool."""
    shifts = jnp.arange(PACK, dtype=jnp.uint32)
    bits = (p[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*p.shape[:-1], p.shape[-1] * PACK)[..., :n] != 0


def _axis_size(axis_names) -> int:
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    s = 1
    for a in axis_names:
        s *= lax.axis_size(a)
    return s


def ring_or_u32(x: jax.Array, axis_name: str) -> jax.Array:
    """Bitwise-OR all-reduce of a uint32 array over one mesh axis via
    ring reduce-scatter + ring all-gather (ppermute)."""
    K = lax.axis_size(axis_name)
    if K == 1:
        return x
    d = lax.axis_index(axis_name)
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % K
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(K, -1)
    perm = [(i, (i + 1) % K) for i in range(K)]

    # K is static: the rings are UNROLLED python loops so every ppermute is
    # its own HLO op — correct roofline accounting (a fori_loop body would
    # be cost-counted once) and XLA can pipeline the steps
    def rs_body(t, ch):
        send_idx = (d - t) % K
        buf = jnp.take(ch, send_idx, axis=0)
        recv = lax.ppermute(buf, axis_name, perm)
        recv_idx = (d - t - 1) % K
        merged = jnp.take(ch, recv_idx, axis=0) | recv
        return ch.at[recv_idx].set(merged)

    for t in range(K - 1):
        chunks = rs_body(t, chunks)

    def ag_body(t, ch):
        send_idx = (d + 1 - t) % K
        buf = jnp.take(ch, send_idx, axis=0)
        recv = lax.ppermute(buf, axis_name, perm)
        recv_idx = (d - t) % K
        return ch.at[recv_idx].set(recv)

    for t in range(K - 1):
        chunks = ag_body(t, chunks)
    return chunks.reshape(-1)[:n].reshape(shape)


def or_allreduce(
    x: jax.Array, axis_names, impl: str = "ring"
) -> jax.Array:
    """OR-union of a bool/uint8 array across mesh axes. Shape-preserving."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if not axis_names or _axis_size(axis_names) == 1:
        return x
    orig_dtype = x.dtype
    if impl == "pmax":
        out = lax.pmax(x.astype(jnp.uint8), axis_names)
        return out.astype(orig_dtype) if orig_dtype != jnp.uint8 else out
    # bit-packed paths
    shape = x.shape
    flat = (x != 0).reshape(1, -1)
    packed = _pack_bits(flat)[0]
    if impl == "allgather":
        for a in axis_names:
            gathered = lax.all_gather(packed, a)  # [K, w]
            packed = jax.lax.reduce(
                gathered,
                jnp.uint32(0),
                lax.bitwise_or,
                dimensions=(0,),
            )
    elif impl == "ring":
        for a in axis_names:
            packed = ring_or_u32(packed, a)
    else:
        raise ValueError(f"unknown or_allreduce impl: {impl}")
    out = _unpack_bits(packed[None], flat.shape[-1])[0].reshape(shape)
    return out.astype(orig_dtype)


def ring_reduce_scatter(x: jax.Array, axis_name: str, op) -> jax.Array:
    """Generic ring reduce-scatter over one mesh axis: x (flat, length
    divisible by K) -> this device's fully-reduced chunk [n/K].
    ``op(a, b)`` combines chunks (e.g. bitwise_or, minimum)."""
    K = lax.axis_size(axis_name)
    flat = x.reshape(-1)
    if K == 1:
        return flat
    d = lax.axis_index(axis_name)
    n = flat.shape[0]
    assert n % K == 0, (n, K)
    chunks = flat.reshape(K, -1)
    perm = [(i, (i + 1) % K) for i in range(K)]

    def rs_body(t, ch):
        send_idx = (d - t) % K
        buf = jnp.take(ch, send_idx, axis=0)
        recv = lax.ppermute(buf, axis_name, perm)
        recv_idx = (d - t - 1) % K
        merged = op(jnp.take(ch, recv_idx, axis=0), recv)
        return ch.at[recv_idx].set(merged)

    for t in range(K - 1):  # unrolled: see ring_or_u32
        chunks = rs_body(t, chunks)
    # device d now owns chunk (d+1)%K; one rotation hands chunk d to d
    owned = jnp.take(chunks, (d + 1) % K, axis=0)
    return lax.ppermute(owned, axis_name, perm)


def allgather_reduce_scatter(x: jax.Array, axis_name: str, op) -> jax.Array:
    """Reduce-scatter over one mesh axis as all-gather + strict left fold
    (device-index order) + own-chunk slice. Same contract as
    ``ring_reduce_scatter`` but built only from group-safe collectives:
    ``all_gather`` compiles with per-replica-group rendezvous, whereas the
    ring's ``ppermute`` lowers to one CollectivePermute whose rendezvous
    spans EVERY device on the mesh. Engines whose while_loop trip count
    can diverge across source-shard groups (``sync="shard"``, the hybrid's
    phase 1) must use this flavor: a ring there deadlocks the groups still
    iterating once the first group exits (the early group never arrives at
    the all-device rendezvous)."""
    K = lax.axis_size(axis_name)
    flat = x.reshape(-1)
    if K == 1:
        return flat
    n = flat.shape[0]
    assert n % K == 0, (n, K)
    gathered = lax.all_gather(flat, axis_name)  # [K, n]
    red = gathered[0]
    for k in range(1, K):  # strict fold: deterministic combine order
        red = op(red, gathered[k])
    d = lax.axis_index(axis_name)
    return lax.dynamic_slice_in_dim(red, d * (n // K), n // K)


def or_reduce_scatter(x: jax.Array, axis_names, impl: str = "ring") -> jax.Array:
    """OR-reduce-scatter of a bool/uint8 array over mesh axes: returns this
    device's row block (length = x.size / prod(K)). Used by the
    sharded-state engine (DESIGN.md §6): per-node state lives only on the
    owning graph shard, so billion-node graphs fit."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    orig_dtype = x.dtype
    shape_tail = x.shape[1:]
    if not axis_names or _axis_size(axis_names) == 1:
        return x
    if impl == "allgather":
        full = or_allreduce(x, axis_names, "allgather")
        # slice own rows
        rows = x.shape[0] // _axis_size(axis_names)
        idx = jnp.int32(0)
        for a in axis_names:
            idx = idx * lax.axis_size(a) + lax.axis_index(a)
        return lax.dynamic_slice_in_dim(full, idx * rows, rows, axis=0)
    # ring on packed bits, sequentially over axes (major axis first)
    flat = (x != 0).reshape(1, -1)
    packed = _pack_bits(flat)[0]
    for a in axis_names:
        packed = ring_reduce_scatter(packed, a, jnp.bitwise_or)
    n_rows = x.shape[0] // _axis_size(axis_names)
    n_bits = n_rows * int(np.prod(shape_tail)) if shape_tail else n_rows
    out = _unpack_bits(packed[None], n_bits)[0]
    return out.reshape(n_rows, *shape_tail).astype(orig_dtype)


def _rs_impl(impl: str):
    if impl == "ring":
        return ring_reduce_scatter
    if impl == "allgather":
        return allgather_reduce_scatter
    raise ValueError(f"unknown reduce-scatter impl: {impl}")


def min_reduce_scatter(x: jax.Array, axis_names, impl: str = "ring") -> jax.Array:
    """Min-reduce-scatter (parents / Bellman-Ford / top-k contributions)."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if not axis_names or _axis_size(axis_names) == 1:
        return x
    rs = _rs_impl(impl)
    shape_tail = x.shape[1:]
    flat = x.reshape(-1)
    for a in axis_names:
        flat = rs(flat, a, jnp.minimum)
    n_rows = x.shape[0] // _axis_size(axis_names)
    return flat.reshape(n_rows, *shape_tail)


def sum_reduce_scatter(x: jax.Array, axis_names, impl: str = "ring") -> jax.Array:
    """Sum-reduce-scatter (PPR residual pushes / pattern-count
    contributions). Each shard's additive partial over its local forward
    rows sums exactly once per target row — disjoint edge sets, so either
    impl reconstructs the global sum in a fixed deterministic order (ring:
    ring order; allgather: device-index fold order)."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if not axis_names or _axis_size(axis_names) == 1:
        return x
    rs = _rs_impl(impl)
    shape_tail = x.shape[1:]
    flat = x.reshape(-1)
    for a in axis_names:
        flat = rs(flat, a, jnp.add)
    n_rows = x.shape[0] // _axis_size(axis_names)
    return flat.reshape(n_rows, *shape_tail)


def merge_scatter(merge: str, contribution, axis_names, or_impl: str,
                  impl: str = "ring"):
    """Sharded-state variant of merge_contribution: global contributions in,
    this shard's fully-merged row block out.

    ``impl`` selects the min/sum reduce-scatter flavor ("ring" |
    "allgather"); for ``merge="or"`` an ``impl="allgather"`` overrides the
    policy's ``or_impl`` so that NO ppermute ring runs — required inside
    ``sync="shard"`` engine bodies (see ``allgather_reduce_scatter``)."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if not axis_names:
        return contribution
    if impl == "allgather" and or_impl == "ring":
        or_impl = "allgather"
    if merge == "or":
        return or_reduce_scatter(contribution, axis_names, or_impl)
    if merge == "min":
        return min_reduce_scatter(contribution, axis_names, impl)
    if merge == "sum":
        return sum_reduce_scatter(contribution, axis_names, impl)
    if merge == "or_min":
        reached, cand = contribution
        return (
            or_reduce_scatter(reached, axis_names, or_impl),
            min_reduce_scatter(cand, axis_names, impl),
        )
    raise ValueError(f"unknown merge: {merge}")


def gang_merge_scatter(merge: str, contribution, axis_names, or_impl: str):
    """Sharded-state merge for *gang-stacked* contributions.

    The gang-scheduled resume carries a leading morsel axis: contribution
    leaves are ``[S, n_out, ...]`` and the row axis to reduce-scatter is
    axis 1, not axis 0. Rotating the gang axis to the back makes rows
    leading again (row-major flattening keeps each device's row block
    contiguous and 32-bit word aligned — rows pad to 32×shards), so the
    existing OR/MIN reduce-scatter rings apply unchanged; the result
    rotates back to ``[S, rows_local, ...]``.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if not axis_names or _axis_size(axis_names) == 1:
        return contribution
    move = lambda x: jnp.moveaxis(x, 0, -1)
    unmove = lambda x: jnp.moveaxis(x, -1, 0)
    if merge == "or":
        return unmove(or_reduce_scatter(move(contribution), axis_names, or_impl))
    if merge == "min":
        return unmove(min_reduce_scatter(move(contribution), axis_names))
    if merge == "sum":
        return unmove(sum_reduce_scatter(move(contribution), axis_names))
    if merge == "or_min":
        reached, cand = contribution
        return (
            unmove(or_reduce_scatter(move(reached), axis_names, or_impl)),
            unmove(min_reduce_scatter(move(cand), axis_names)),
        )
    raise ValueError(f"unknown merge: {merge}")


def gang_handoff(state, idx, gang: int, mesh, axes):
    """Phase-1 → phase-2 frontier handoff for the sharded state layout.

    ``state``: the phase-1 stacked state pytree (leaves ``[m, n, ...]``,
    rows sharded over the phase-1 graph axes, morsels over the source
    axes). Gathers the surviving morsels ``idx``, zero-pads the morsel
    axis to the pow2 ``gang`` width (all-zero frontiers are inert in the
    resume loop), and re-places rows over ``axes`` (every mesh axis) —
    the layout the sharded gang-resume engine consumes. XLA lowers the
    re-placement to the all-gather(phase-1 graph axes) + dynamic-slice
    (all axes) handoff; the per-iteration merge inside the resume stays
    the OR/MIN reduce-scatter (``gang_merge_scatter``).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    idxa = jnp.asarray(np.asarray(idx), jnp.int32)
    k = int(idxa.shape[0])

    def pick(x):
        sub = jnp.take(jnp.asarray(x), idxa, axis=0)
        if gang > k:
            pad = jnp.zeros((gang - k,) + sub.shape[1:], sub.dtype)
            sub = jnp.concatenate([sub, pad], axis=0)
        sharding = NamedSharding(
            mesh, P(None, tuple(axes), *(None,) * (sub.ndim - 2))
        )
        return jax.device_put(sub, sharding)

    return jax.tree.map(pick, state)


def gang_scatter_back(full, sub, idx):
    """Inverse handoff: write the ``len(idx)`` resumed survivors (leading
    rows of the padded ``sub`` pytree) back into the stacked phase-1-layout
    ``full`` state; gang pad slots are dropped."""
    idxa = jnp.asarray(np.asarray(idx), jnp.int32)
    k = int(idxa.shape[0])
    return jax.tree.map(
        lambda f, s: jnp.asarray(f).at[idxa].set(s[:k]), full, sub
    )


def min_allreduce(x: jax.Array, axis_names) -> jax.Array:
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if not axis_names or _axis_size(axis_names) == 1:
        return x
    return lax.pmin(x, axis_names)


def merge_contribution(merge: str, contribution, axis_names, or_impl: str):
    """Apply an edge compute's MERGE across graph axes."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if not axis_names:
        return contribution
    if merge == "or":
        return or_allreduce(contribution, axis_names, or_impl)
    if merge == "min":
        return min_allreduce(contribution, axis_names)
    if merge == "sum":
        return lax.psum(contribution, axis_names)
    if merge == "or_min":
        reached, cand = contribution
        return (
            or_allreduce(reached, axis_names, or_impl),
            min_allreduce(cand, axis_names),
        )
    raise ValueError(f"unknown merge: {merge}")
