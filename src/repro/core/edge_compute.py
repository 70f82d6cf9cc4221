"""edgeCompute() implementations (paper Listing 2/4) as JAX aggregation ops.

The paper's interface is a per-edge callback ``edgeCompute(u, v)`` mutating
shared auxiliary state under atomics/CAS. The SPMD re-think: an edge compute is
a triple

    local_extend(graph_shard, state) -> contribution        (pure, per shard)
    MERGE  : how contributions combine across graph shards  ('or' | 'min')
    apply(state, merged_contribution, it) -> state          (pure, replicated)

``local_extend`` is the frontier-extension scan (the hot loop the paper
parallelizes with frontier morsels); MERGE is the inter-chip frontier union
(nT1S/nTkS collective); ``apply`` is the pipeline-break at the end of each IFE
iteration (paper's ``checkIfFrontierFinished``).

Supported algorithms:
- ``bfs_levels`` / ``sp_lengths``: unweighted shortest-path lengths
  (paper Listing 2; identical math, both names kept).
- ``sp_parents``: shortest paths with parent edges (paper Listing 4). The CAS
  linked-list Parents structure becomes a deterministic segment-min over
  candidate parents (min node id wins — any parent on a shortest path is valid).
- ``bellman_ford``: weighted SSSP (paper Fig 1's recursive operator).
- ``reachability``: transitive closure from sources.
- ``msbfs_lengths`` / ``msbfs_parents``: 64-lane multi-source variants
  (paper §3.4 / §4.2) with the lane dimension as a tensor axis.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.csr import SCAN_CHUNK, BinnedEll, EllGraph

INF_U8 = jnp.uint8(255)
NO_PARENT = jnp.int32(2**31 - 1)


def _gang_pack(x: jax.Array) -> jax.Array:
    from .msbfs import gang_pack_lanes

    return gang_pack_lanes(x)


def _gang_unpack(y: jax.Array, gang: int, lanes: int = 0) -> jax.Array:
    from .msbfs import gang_unpack_lanes

    return gang_unpack_lanes(y, gang, lanes)


# ---------------------------------------------------------------------------
# Extension primitives over ELL (pure jnp; Pallas kernels mirror these).
# ---------------------------------------------------------------------------

def _local_rows(x: jax.Array, rows: int, row_offset) -> jax.Array:
    """This shard's ``rows`` rows of a global per-node array (identity when
    the array is already local: sharded layout or a single shard)."""
    if row_offset is None:
        return x
    return jax.lax.dynamic_slice_in_dim(x, row_offset, rows, axis=0)


def ell_reach_dense(
    g: EllGraph, frontier: jax.Array, row_offset=None, n_out=None
) -> jax.Array:
    """frontier bool -> [n_out] bool: v reached iff some active u has u->v.

    Two state layouts (DESIGN.md §6):
    - replicated: ``frontier`` is global [n]; ``row_offset`` slices this
      shard's rows; ``n_out`` defaults to n.
    - sharded: ``frontier`` is already this shard's rows [rows_local];
      ``row_offset`` is None and ``n_out`` gives the global width.
    Destinations in ``g.indices`` are global ids, so the contribution is
    always global-[n_out]-sized (padding sentinel drops).
    """
    n = frontier.shape[0] if n_out is None else n_out
    local_f = _local_rows(frontier, g.indices.shape[0], row_offset)
    contrib = jnp.broadcast_to(local_f[:, None], g.indices.shape)
    out = jnp.zeros((n,), dtype=jnp.bool_)
    return out.at[g.indices].max(contrib, mode="drop")


def _deg_chunk(rows: int, width: int, budget: int = 2 << 30) -> int:
    """Degree-dim chunk so the scatter temp [rows, chunk, width] stays under
    ``budget`` bytes (billion-node lane morsels would otherwise materialize a
    rows×max_deg×L broadcast — 31 GB/device for Graph500-28).

    Returns the largest power of two that fits the budget, so the chunk
    divides every pow2-padded slab width exactly. Widths that are NOT a
    chunk multiple (a padded ELL pads to a multiple of 8, not a pow2;
    refined degree buckets can have arbitrary widths) are handled by
    ``chunk_fold``'s static remainder tail — the historical round-to-8
    chunk could land on e.g. 24 against a 32-wide slab and trip the
    divisibility assert."""
    per_slot = max(rows * width, 1)
    c = max(budget // per_slot, 1)
    return 1 << (int(c).bit_length() - 1)


def chunk_fold(D: int, chunk: int, step, acc0):
    """Fold ``step(start, width, acc)`` over the degree axis ``[0, D)`` in
    ``chunk``-sized pieces: a ``fori_loop`` over the full chunks (bounded
    temps, in-place carry) plus ONE statically-shaped remainder tail of
    ``D % chunk`` columns when the chunk does not divide ``D``. ``start``
    may be traced; ``width`` is always a Python int so callers can
    ``dynamic_slice`` with it. Order is ascending-degree-slot either way,
    so order-invariant (OR/min/max/sum-of-int) reductions are bitwise
    equal to the unchunked single-shot fold."""
    full, rem = divmod(D, chunk)
    acc = acc0
    if full == 1 and rem == 0:
        return step(0, D, acc)
    if full:
        acc = jax.lax.fori_loop(
            0, full, lambda i, a: step(i * chunk, chunk, a), acc
        )
    if rem:
        acc = step(full * chunk, rem, acc)
    return acc


def _chunked_scatter(g: EllGraph, out, values_row, chunk: int, reducer: str):
    """Scatter values_row[:, None, :] over degree chunks of g.indices into
    ``out`` via ``chunk_fold`` (bounded temps, in-place carry)."""
    D = g.indices.shape[1]

    def step(start, width, acc):
        idx = (
            g.indices
            if width == D
            else jax.lax.dynamic_slice_in_dim(g.indices, start, width, 1)
        )
        contrib = jnp.broadcast_to(
            values_row[:, None, :], (*idx.shape, values_row.shape[-1])
        )
        return getattr(acc.at[idx], reducer)(contrib, mode="drop")

    if chunk >= D:
        return step(0, D, out)
    return chunk_fold(D, chunk, step, out)


def ell_reach_lanes(
    g: EllGraph, lanes: jax.Array, row_offset=None, n_out=None
) -> jax.Array:
    """[*, L] uint8 -> [n_out, L]: per-lane reach (shared edge scan across
    lanes — the MS-BFS economy; one gather of the neighbor list serves all L
    lanes). Layout contract as in ``ell_reach_dense``."""
    L = lanes.shape[-1]
    n = lanes.shape[0] if n_out is None else n_out
    local = _local_rows(lanes, g.indices.shape[0], row_offset)
    out = jnp.zeros((n, L), dtype=jnp.uint8)
    chunk = _deg_chunk(local.shape[0], L)
    return _chunked_scatter(g, out, local, chunk, "max")


def ell_min_dist(
    g: EllGraph, dist: jax.Array, frontier: jax.Array, row_offset=None,
    n_out=None,
) -> jax.Array:
    """Weighted relax: cand[v] = min over active u of dist[u] + w(u,v)."""
    n = dist.shape[0] if n_out is None else n_out
    w = g.weights if g.weights is not None else jnp.ones_like(
        g.indices, dtype=jnp.float32
    )
    du = _local_rows(jnp.where(frontier, dist, jnp.inf), g.indices.shape[0],
                     row_offset)
    cand = du[:, None] + w
    out = jnp.full((n,), jnp.inf, dtype=jnp.float32)
    return out.at[g.indices].min(cand, mode="drop")


def ell_push_sum(
    g: EllGraph, values: jax.Array, row_offset=None, n_out=None,
    normalize: bool = False,
) -> jax.Array:
    """Additive push: out[v] = sum over local rows u with edge u->v of
    values[u] (optionally divided by u's out-degree first). This is the
    ``y += xᵀA`` linear-algebra primitive under the diffusion / pattern-count
    computes, restricted to this shard's rows; padding rows/slots carry the
    sentinel index and drop. Layout contract as in ``ell_reach_dense``."""
    n = values.shape[0] if n_out is None else n_out
    vloc = _local_rows(values, g.indices.shape[0], row_offset)
    if normalize:
        vloc = vloc / jnp.maximum(g.degrees, 1).astype(vloc.dtype)
    out = jnp.zeros((n, 1), vloc.dtype)
    if g.indices.shape[1] == 0:
        return out[:, 0]
    chunk = _deg_chunk(g.indices.shape[0], 4)
    return _chunked_scatter(g, out, vloc[:, None], chunk, "add")[:, 0]


def ell_min_topk(
    rev: EllGraph, gdists: jax.Array, seed_row: jax.Array
) -> jax.Array:
    """Full-Jacobi k-best relax over the reverse ELL: for each local row v,
    the k smallest of {gdists[u, :] + w(u, v) : u in-neighbor of v} plus v's
    own seed value (0 for sources, +inf otherwise). ``gdists`` is the global
    [n_out, k] sorted slot table; ``seed_row`` is [rows] local. Returns the
    sorted [rows, k] recompute. Degree-chunked: the running top-k merge keeps
    the candidate temp at [rows, (chunk+1)·k] instead of [rows, max_in_deg·k].
    """
    rows, D = rev.indices.shape
    k = gdists.shape[-1]
    acc0 = jnp.full((rows, k), jnp.inf, jnp.float32).at[:, 0].set(seed_row)
    if D == 0:  # edgeless/zero-cap slab: seed-only candidates
        return acc0
    w = (
        rev.weights
        if rev.weights is not None
        else jnp.ones_like(rev.indices, dtype=jnp.float32)
    )

    def step(start, width, acc):
        if width == D:
            idx, wts = rev.indices, w
        else:
            idx = jax.lax.dynamic_slice_in_dim(rev.indices, start, width, 1)
            wts = jax.lax.dynamic_slice_in_dim(w, start, width, 1)
        got = gdists.at[idx].get(mode="fill", fill_value=jnp.inf)
        cand = (got + wts[:, :, None]).reshape(rows, -1)
        merged = jnp.concatenate([acc, cand], axis=1)
        return jnp.sort(merged, axis=1)[:, :k]

    chunk = _deg_chunk(rows, 4 * k)
    if chunk >= D:
        return step(0, D, acc0)
    return chunk_fold(D, chunk, step, acc0)


def _row_ids(rows: int, row_offset, row_base) -> jax.Array:
    """Global node ids of this shard's ``rows`` rows (after any slicing)."""
    base = row_offset if row_offset is not None else row_base
    ids = jnp.arange(rows, dtype=jnp.int32)
    return ids if base is None else ids + base


def ell_min_parent(
    g: EllGraph, frontier: jax.Array, row_offset=None, n_out=None,
    row_base=None,
) -> jax.Array:
    """cand_parent[v] = min active u with edge u->v (NO_PARENT if none).
    ``row_base``: global id of the first local row (sharded layout)."""
    n = frontier.shape[0] if n_out is None else n_out
    rows = g.indices.shape[0]
    local_f = _local_rows(frontier, rows, row_offset)
    cand = jnp.where(local_f, _row_ids(rows, row_offset, row_base),
                     NO_PARENT)
    cand = jnp.broadcast_to(cand[:, None], g.indices.shape)
    out = jnp.full((n,), NO_PARENT, jnp.int32)
    return out.at[g.indices].min(cand, mode="drop")


def ell_min_parent_lanes(
    g: EllGraph, lanes: jax.Array, row_offset=None, n_out=None, row_base=None
) -> jax.Array:
    """Per-lane min-parent: [*, L] uint8 -> [n_out, L] int32."""
    L = lanes.shape[-1]
    n = lanes.shape[0] if n_out is None else n_out
    rows = g.indices.shape[0]
    local = _local_rows(lanes, rows, row_offset)
    u_ids = _row_ids(rows, row_offset, row_base)[:, None]
    cand_row = jnp.where(local != 0, u_ids, NO_PARENT)
    out = jnp.full((n, L), NO_PARENT, jnp.int32)
    chunk = _deg_chunk(local.shape[0], 4 * L)
    return _chunked_scatter(g, out, cand_row, chunk, "min")


# ---------------------------------------------------------------------------
# Scans of degree-binned slabs: the served push (forward slabs) and the
# binned pull's gathers (reverse slabs).
# ---------------------------------------------------------------------------


#: slots one step of a binned scan reads and writes. A TPU gather or
#: scatter compiles in time that grows with its update count (tens of
#: seconds for a few hundred thousand 64-lane rows); a loop of fixed-size
#: steps compiles one step, whatever the graph's size
SCAN_SLOTS = 1 << 14
#: a gather index no array reaches: ``mode="fill"`` reads give the fill,
#: ``mode="drop"`` writes vanish
_OUT_OF_RANGE = np.iinfo(np.int32).max


def _any_lane(mask: jax.Array) -> jax.Array:
    return mask if mask.ndim == 1 else mask.reshape(
        mask.shape[0], -1).any(axis=1)


def rows_off_fill(src: jax.Array, fill) -> jax.Array:
    """``[rows]`` bool: the rows of ``src`` that differ from ``fill`` in
    some lane. Under max, min or integer add, ``fill`` is the
    reduction's identity, so only these rows can change a push."""
    return _any_lane(src != fill)


def unvisited_rows(visited: jax.Array) -> jax.Array:
    """``[rows]`` bool: the rows some lane has not visited. A pull
    suppresses every visited lane after its scan, so only these rows can
    change its result."""
    return _any_lane(visited == 0)


#: entries one matmul of ``prefix_count`` sums
_PREFIX_BLOCK = 512


def prefix_count(x: jax.Array) -> jax.Array:
    """Inclusive prefix sums (int32) of a 1-D bool vector. A TPU compiles
    ``jnp.cumsum`` over a few hundred thousand entries in seconds, so
    each block of ``_PREFIX_BLOCK`` entries is summed by a matmul with a
    triangular ones matrix (0/1 operands are exact in bfloat16, their
    sums exact in float32), and the blocks' totals by a short cumsum."""
    n, b = x.shape[0], _PREFIX_BLOCK
    blocks = jnp.pad(x.astype(jnp.bfloat16), (0, -n % b)).reshape(-1, b)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (b, b), 1))
    inner = jnp.dot(blocks, tri.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32).astype(jnp.int32)
    total = inner[:, -1]
    return (inner + (jnp.cumsum(total) - total)[:, None]).reshape(-1)[:n]


def live_chunks(bn: BinnedEll, live: jax.Array) -> jax.Array:
    """``[n_chunks]`` bool: the ``SCAN_CHUNK``-slot chunks of ``bn``'s
    first shard that hold a slot of a ``live`` row (``[rows_local]``
    bool, local row order). ``live`` goes into binned order through
    ``perm`` (slab-padding rows read False) and is prefix-summed; a
    chunk is live where the sum rises across its span of positions
    (``chunk_lo``, ``chunk_hi``): a read a row and two a chunk, none a
    slot."""
    m = live.at[bn.perm[0]].get(mode="fill", fill_value=False)
    c = jnp.concatenate([jnp.zeros((1,), jnp.int32), prefix_count(m)])
    return c[bn.chunk_hi[0] + 1] > c[bn.chunk_lo[0]]


def binned_scan(bn: BinnedEll, src: jax.Array, out: jax.Array,
                reducer: str, fill, *, pull: bool = False,
                value=None, slot_add=None, live=None) -> jax.Array:
    """One pass over the slots of ``bn`` (its slabs in bucket order,
    each row-major: the flat ``nbrs`` / ``rows`` arrays) in fixed steps of
    at most ``SCAN_SLOTS`` slots; the steps, and the ``_deg_chunk``
    budget, bound the temps.

    Push (``pull=False``): each slot reads ``src`` (``[rows_local, ...]``
    per-row values) at its own row — slab-padding slots read ``fill`` —
    and reduces into ``out[neighbour]`` (sentinel ids drop). Pull: each
    slot reads ``src`` (a global ``[n_out, ...]`` activation) at its
    neighbour (sentinels read ``fill``) and reduces into ``out[row]``
    (``out`` is ``[rows_local, ...]``; padding slots drop).
    ``value(got, nbr)`` maps the read values to the reduced ones;
    ``slot_add`` (per-slot weights, ``[K, S]``) is added to them.

    ``live`` (``[rows_local]`` bool, local row order) names the rows
    whose slots can change the result; the caller vouches that every
    other row's slots reduce identities or are overwritten after the
    scan. The scan then reads only the chunks that hold a live slot
    (``live_chunks``): their ids, compacted, feed a ``while_loop`` whose
    trips each gather ``SCAN_SLOTS // SCAN_CHUNK`` chunks and run one
    step on them; ids past the live ones name no chunk and read as
    sentinels. Without ``live``, or when every chunk is live, every slot
    is read in place: a ``fori_loop`` plus one static tail.

    Either way the slots and the reduction are a subset of a single
    padded scan's whose other slots reduce identities, so the
    order-invariant reductions (max / min / integer add) are bitwise
    equal to it; a float add, which is never given ``live``, sums each
    node's contributions in one fixed order."""
    nbr, own = bn.nbrs[0], bn.rows[0]
    wts = None if slot_add is None else slot_add[0]
    total = int(nbr.shape[0])
    if not total:
        return out
    assert total % SCAN_CHUNK == 0, total
    item = max(int(np.prod(out.shape[1:], dtype=np.int64)), 1) * max(
        jnp.dtype(out.dtype).itemsize, 1)
    step = min(SCAN_SLOTS, total, _deg_chunk(1, item))

    def reduce(nb, ow, w, acc):
        read_at, write_at = (nb, ow) if pull else (ow, nb)
        got = src.at[read_at].get(mode="fill", fill_value=fill)
        if value is not None:
            got = value(got, nb)
        if w is not None:
            got = got + w
        return getattr(acc.at[write_at], reducer)(got, mode="drop")

    def dense(acc):
        def part(start, width, acc):
            cut = lambda a: None if a is None else (
                jax.lax.dynamic_slice_in_dim(a, start, width))
            return reduce(cut(nbr), cut(own), cut(wts), acc)

        return chunk_fold(total, step, part, acc)

    if live is None:
        return dense(out)

    per_trip = max(step // SCAN_CHUNK, 1)
    n_chunks = total // SCAN_CHUNK
    act = live_chunks(bn, live)
    rank = prefix_count(act)

    def compact(acc):
        # the live chunks' ids in order, then n_chunks (no chunk) to the end
        size = -(-n_chunks // per_trip) * per_trip
        work = jnp.full((size,), n_chunks, jnp.int32).at[
            jnp.where(act, rank - 1, size)].set(
            jnp.arange(n_chunks, dtype=jnp.int32), mode="drop")
        views = [(None if a is None else a.reshape(n_chunks, SCAN_CHUNK), pad)
                 for a, pad in ((nbr, _OUT_OF_RANGE), (own, _OUT_OF_RANGE),
                                (wts, 0))]
        trips = (rank[-1] + per_trip - 1) // per_trip

        def trip(carry):
            t, acc = carry
            ids = jax.lax.dynamic_slice_in_dim(work, t * per_trip, per_trip)
            nb, ow, w = (
                None if v is None
                else v.at[ids].get(mode="fill", fill_value=pad).reshape(-1)
                for v, pad in views)
            return t + 1, reduce(nb, ow, w, acc)

        return jax.lax.while_loop(
            lambda carry: carry[0] < trips, trip, (jnp.int32(0), acc))[1]

    # every chunk live: the plain steps, without the chunk gathers
    return jax.lax.cond(rank[-1] == n_chunks, dense, compact, out)


def _binned_push(bn: BinnedEll, src: jax.Array, out: jax.Array,
                 reducer: str, fill, **kw) -> jax.Array:
    """``binned_scan`` in push form over the chunks that hold a row of
    ``src`` other than ``fill``."""
    return binned_scan(bn, src, out, reducer, fill,
                       live=rows_off_fill(src, fill), **kw)


def binned_reach_dense(bn: BinnedEll, frontier: jax.Array, row_offset=None,
                       n_out=None) -> jax.Array:
    """``ell_reach_dense`` over the binned forward slabs (same layout
    contract: replicated state slices at ``row_offset``, sharded state
    is already local; destinations are global ids). The scatter runs in
    int32: a one-byte scatter compiles far slower on a TPU."""
    n = frontier.shape[0] if n_out is None else n_out
    local = _local_rows(frontier, bn.rows_local, row_offset)
    out = _binned_push(bn, local.astype(jnp.int32),
                       jnp.zeros((n,), jnp.int32), "max", 0)
    return out > 0


def binned_reach_lanes(bn: BinnedEll, lanes: jax.Array, row_offset=None,
                       n_out=None) -> jax.Array:
    """``ell_reach_lanes`` over the binned forward slabs: one scan of
    each neighbour list serves all L lanes."""
    n = lanes.shape[0] if n_out is None else n_out
    local = _local_rows(lanes, bn.rows_local, row_offset)
    out = jnp.zeros((n, lanes.shape[-1]), jnp.uint8)
    return _binned_push(bn, local, out, "max", 0)


def binned_min_parent(bn: BinnedEll, frontier: jax.Array, row_offset=None,
                      n_out=None, row_base=None) -> jax.Array:
    n = frontier.shape[0] if n_out is None else n_out
    local = _local_rows(frontier, bn.rows_local, row_offset)
    cand = jnp.where(local, _row_ids(bn.rows_local, row_offset, row_base),
                     NO_PARENT)
    out = jnp.full((n,), NO_PARENT, jnp.int32)
    return _binned_push(bn, cand, out, "min", int(NO_PARENT))


def binned_min_parent_lanes(bn: BinnedEll, lanes: jax.Array,
                            row_offset=None, n_out=None,
                            row_base=None) -> jax.Array:
    n = lanes.shape[0] if n_out is None else n_out
    local = _local_rows(lanes, bn.rows_local, row_offset)
    ids = _row_ids(bn.rows_local, row_offset, row_base)[:, None]
    cand = jnp.where(local != 0, ids, NO_PARENT)
    out = jnp.full((n, lanes.shape[-1]), NO_PARENT, jnp.int32)
    return _binned_push(bn, cand, out, "min", int(NO_PARENT))


def binned_min_dist(bn: BinnedEll, dist: jax.Array, frontier: jax.Array,
                    row_offset=None, n_out=None) -> jax.Array:
    """Weighted relax over the binned slabs (unit weights when the slabs
    carry none)."""
    n = dist.shape[0] if n_out is None else n_out
    du = _local_rows(jnp.where(frontier, dist, jnp.inf), bn.rows_local,
                     row_offset)
    out = jnp.full((n,), jnp.inf, jnp.float32)
    if bn.slot_weights is None:
        return _binned_push(bn, du, out, "min", jnp.inf,
                            value=lambda got, nbr: got + 1.0)
    return _binned_push(bn, du, out, "min", jnp.inf,
                        slot_add=bn.slot_weights)


def binned_push_sum(bn: BinnedEll, values: jax.Array, row_offset=None,
                    n_out=None, normalize: bool = False) -> jax.Array:
    """``ell_push_sum`` over the binned slabs (``normalize`` divides by
    each row's out-degree, the ``degrees`` leaf)."""
    n = values.shape[0] if n_out is None else n_out
    vloc = _local_rows(values, bn.rows_local, row_offset)
    if normalize:
        vloc = vloc / jnp.maximum(bn.degrees[0], 1).astype(vloc.dtype)
    # a [n, 1] accumulator sliced at the end, as ``ell_push_sum`` has:
    # the slice keeps XLA from folding a consumer's ``x + pushed`` into
    # the scan's zero start, which would re-associate the float sums
    # differently in the replicated and sharded state layouts
    out = jnp.zeros((n, 1), vloc.dtype)
    return binned_scan(bn, vloc[:, None], out, "add", 0)[:, 0]


# ---------------------------------------------------------------------------
# Edge computes.
# ---------------------------------------------------------------------------

class SPLengthState(NamedTuple):
    frontier: jax.Array  # [n] bool
    visited: jax.Array  # [n] bool
    levels: jax.Array  # [n] int32 (-1 = unreached)


class SPLengths:
    """Unweighted shortest-path lengths (paper Listing 2)."""

    MERGE = "or"
    #: safe to fold into 64-lane MS-BFS batches (saturating-OR frontier);
    #: weighted/float/int frontiers have no lane form and must never be
    #: packed (admission checks this flag before nTkMS planning)
    LANES_OK = True

    @staticmethod
    def init(n_nodes: int, sources: jax.Array) -> SPLengthState:
        f = jnp.zeros((n_nodes,), jnp.bool_).at[sources].set(True, mode="drop")
        levels = jnp.full((n_nodes,), -1, jnp.int32)
        levels = levels.at[sources].set(0, mode="drop")
        return SPLengthState(frontier=f, visited=f, levels=levels)

    @staticmethod
    def local_extend(g: EllGraph, state: SPLengthState, row_offset=None,
                     n_out=None, row_base=None) -> jax.Array:
        return ell_reach_dense(g, state.frontier, row_offset, n_out)

    @staticmethod
    def extend(be, ops, state: SPLengthState, ctx):
        """Backend-pluggable extension (core.extend): same contribution
        contract as ``local_extend``, physical scan chosen by ``be``."""
        return be.reach_dense(ops, state.frontier, state.visited, ctx)

    @staticmethod
    def gang_extend(be, ops, state: SPLengthState, ctx):
        """Batched multi-frontier extension for the gang-scheduled resume:
        state leaves carry a leading gang axis ``[S, ...]``; the S dense
        frontiers are repacked as MS-BFS lanes so one shared adjacency scan
        serves the whole gang. Bit-identical per morsel to ``extend`` (the
        lane scatter/gather computes the same OR per column)."""
        S = state.frontier.shape[0]
        reached = be.reach_lanes(
            ops, _gang_pack(state.frontier), _gang_pack(state.visited), ctx
        )
        return _gang_unpack(reached, S) != 0

    @staticmethod
    def apply(state: SPLengthState, reached: jax.Array, it: jax.Array):
        new = reached & ~state.visited
        return SPLengthState(
            frontier=new,
            visited=state.visited | new,
            levels=jnp.where(new, it + 1, state.levels),
        )


class BFSLevels(SPLengths):
    """Alias — BFS levels are unweighted SP lengths."""


class ReachState(NamedTuple):
    frontier: jax.Array
    visited: jax.Array


class Reachability:
    MERGE = "or"
    LANES_OK = True

    @staticmethod
    def init(n_nodes: int, sources: jax.Array) -> ReachState:
        f = jnp.zeros((n_nodes,), jnp.bool_).at[sources].set(True, mode="drop")
        return ReachState(frontier=f, visited=f)

    @staticmethod
    def local_extend(g: EllGraph, state: ReachState, row_offset=None,
                     n_out=None, row_base=None) -> jax.Array:
        return ell_reach_dense(g, state.frontier, row_offset, n_out)

    @staticmethod
    def extend(be, ops, state: ReachState, ctx):
        return be.reach_dense(ops, state.frontier, state.visited, ctx)

    @staticmethod
    def gang_extend(be, ops, state: ReachState, ctx):
        S = state.frontier.shape[0]
        reached = be.reach_lanes(
            ops, _gang_pack(state.frontier), _gang_pack(state.visited), ctx
        )
        return _gang_unpack(reached, S) != 0

    @staticmethod
    def apply(state: ReachState, reached: jax.Array, it: jax.Array):
        new = reached & ~state.visited
        return ReachState(frontier=new, visited=state.visited | new)


class SPParentState(NamedTuple):
    frontier: jax.Array
    visited: jax.Array
    levels: jax.Array
    parents: jax.Array  # [n] int32, NO_PARENT where unreached


class SPParents:
    """Shortest paths with parent pointers (paper Listing 4).

    Paper: per-thread memory buffers + CAS into a dense pointer array. SPMD:
    contributions carry (reached, candidate-parent); merged with (or, min).
    """

    MERGE = "or_min"
    LANES_OK = True

    @staticmethod
    def init(n_nodes: int, sources: jax.Array) -> SPParentState:
        f = jnp.zeros((n_nodes,), jnp.bool_).at[sources].set(True, mode="drop")
        levels = jnp.full((n_nodes,), -1, jnp.int32).at[sources].set(0, mode="drop")
        parents = jnp.full((n_nodes,), NO_PARENT, jnp.int32)
        return SPParentState(frontier=f, visited=f, levels=levels, parents=parents)

    @staticmethod
    def local_extend(g: EllGraph, state: SPParentState, row_offset=None,
                     n_out=None, row_base=None):
        return (
            ell_reach_dense(g, state.frontier, row_offset, n_out),
            ell_min_parent(g, state.frontier, row_offset, n_out, row_base),
        )

    @staticmethod
    def extend(be, ops, state: SPParentState, ctx):
        # paired call: the backend computes both contributions off one
        # frontier union / direction decision
        return be.reach_parent_dense(ops, state.frontier, state.visited, ctx)

    @staticmethod
    def gang_extend(be, ops, state: SPParentState, ctx):
        S = state.frontier.shape[0]
        reached, parents = be.reach_parent_lanes(
            ops, _gang_pack(state.frontier), _gang_pack(state.visited), ctx
        )
        return _gang_unpack(reached, S) != 0, _gang_unpack(parents, S)

    @staticmethod
    def apply(state: SPParentState, merged, it: jax.Array):
        reached, parent_cand = merged
        new = reached & ~state.visited
        return SPParentState(
            frontier=new,
            visited=state.visited | new,
            levels=jnp.where(new, it + 1, state.levels),
            parents=jnp.where(new, parent_cand, state.parents),
        )


class BellmanFordState(NamedTuple):
    frontier: jax.Array
    dist: jax.Array  # [n] float32


class BellmanFord:
    """Weighted SSSP — nodes may re-enter the frontier (walk semantics)."""

    MERGE = "min"
    LANES_OK = False  # float-min relax has no saturating lane form

    @staticmethod
    def init(n_nodes: int, sources: jax.Array) -> BellmanFordState:
        f = jnp.zeros((n_nodes,), jnp.bool_).at[sources].set(True, mode="drop")
        dist = jnp.full((n_nodes,), jnp.inf, jnp.float32)
        dist = dist.at[sources].set(0.0, mode="drop")
        return BellmanFordState(frontier=f, dist=dist)

    @staticmethod
    def local_extend(g: EllGraph, state: BellmanFordState, row_offset=None,
                     n_out=None, row_base=None) -> jax.Array:
        return ell_min_dist(g, state.dist, state.frontier, row_offset, n_out)

    @staticmethod
    def extend(be, ops, state: BellmanFordState, ctx):
        return be.min_dist(ops, state.dist, state.frontier, ctx)

    @staticmethod
    def gang_extend(be, ops, state: BellmanFordState, ctx):
        # weighted relax has no saturating lane formulation (float min, not
        # OR); map the members instead — still one while_loop for the
        # whole gang, so re-dispatch does not serialize. Not vmap: a
        # batched predicate turns the scans' and the direction switch's
        # ``lax.cond`` into a select that runs both branches for every
        # member, so no member's scan could skip its dead chunks
        return jax.lax.map(
            lambda st: BellmanFord.extend(be, ops, st, ctx), state
        )

    @staticmethod
    def apply(state: BellmanFordState, cand: jax.Array, it: jax.Array):
        improved = cand < state.dist
        return BellmanFordState(
            frontier=improved, dist=jnp.minimum(state.dist, cand)
        )


class MSBFSState(NamedTuple):
    frontier: jax.Array  # [n, L] uint8
    visited: jax.Array  # [n, L] uint8
    levels: jax.Array  # [n, L] uint8 (255 = unreached)


class MSBFSLengths:
    """Multi-source BFS lengths, L lanes (paper §3.4, Then et al. 2014).

    Levels stored as uint8 (paper stores 1-byte path lengths, §4.2):
    24 bytes/node of frontier+visited state per 64-lane morsel + 1 byte/lane.
    """

    MERGE = "or"
    LANES = 64
    LANES_OK = True

    @staticmethod
    def init(n_nodes: int, sources: jax.Array) -> MSBFSState:
        L = sources.shape[0]
        f = jnp.zeros((n_nodes, L), jnp.uint8)
        f = f.at[sources, jnp.arange(L)].set(1, mode="drop")
        levels = jnp.full((n_nodes, L), INF_U8, jnp.uint8)
        levels = levels.at[sources, jnp.arange(L)].set(0, mode="drop")
        return MSBFSState(frontier=f, visited=f, levels=levels)

    @staticmethod
    def local_extend(g: EllGraph, state: MSBFSState, row_offset=None,
                     n_out=None, row_base=None) -> jax.Array:
        return ell_reach_lanes(g, state.frontier, row_offset, n_out)

    @staticmethod
    def extend(be, ops, state: MSBFSState, ctx):
        return be.reach_lanes(ops, state.frontier, state.visited, ctx)

    @staticmethod
    def gang_extend(be, ops, state: MSBFSState, ctx):
        # S surviving 64-lane morsels fold into one [rows, S*64] lane
        # tensor: the shared scan now amortizes over S*64 BFS instances
        S, L = state.frontier.shape[0], state.frontier.shape[-1]
        reached = be.reach_lanes(
            ops, _gang_pack(state.frontier), _gang_pack(state.visited), ctx
        )
        return _gang_unpack(reached, S, L)

    @staticmethod
    def apply(state: MSBFSState, reached: jax.Array, it: jax.Array):
        new = (reached & ~state.visited).astype(jnp.uint8)
        lvl = (it + 1).astype(jnp.uint8)
        return MSBFSState(
            frontier=new,
            visited=state.visited | new,
            levels=jnp.where(new != 0, lvl, state.levels),
        )


class MSBFSParentState(NamedTuple):
    frontier: jax.Array
    visited: jax.Array
    levels: jax.Array
    parents: jax.Array  # [n, L] int32


class MSBFSParents:
    """Multi-source BFS with per-lane parents (the memory-hungry variant the
    paper flags: 536 B/node/morsel upfront for paths vs 88 B for lengths)."""

    MERGE = "or_min"
    LANES = 64
    LANES_OK = True

    @staticmethod
    def init(n_nodes: int, sources: jax.Array) -> MSBFSParentState:
        base = MSBFSLengths.init(n_nodes, sources)
        L = sources.shape[0]
        parents = jnp.full((n_nodes, L), NO_PARENT, jnp.int32)
        return MSBFSParentState(
            frontier=base.frontier,
            visited=base.visited,
            levels=base.levels,
            parents=parents,
        )

    @staticmethod
    def local_extend(g: EllGraph, state: MSBFSParentState, row_offset=None,
                     n_out=None, row_base=None):
        return (
            ell_reach_lanes(g, state.frontier, row_offset, n_out),
            ell_min_parent_lanes(g, state.frontier, row_offset, n_out,
                                 row_base),
        )

    @staticmethod
    def extend(be, ops, state: MSBFSParentState, ctx):
        return be.reach_parent_lanes(ops, state.frontier, state.visited, ctx)

    @staticmethod
    def gang_extend(be, ops, state: MSBFSParentState, ctx):
        S, L = state.frontier.shape[0], state.frontier.shape[-1]
        reached, parents = be.reach_parent_lanes(
            ops, _gang_pack(state.frontier), _gang_pack(state.visited), ctx
        )
        return _gang_unpack(reached, S, L), _gang_unpack(parents, S, L)

    @staticmethod
    def apply(state: MSBFSParentState, merged, it: jax.Array):
        reached, parent_cand = merged
        new = (reached & ~state.visited).astype(jnp.uint8)
        is_new = new != 0
        lvl = (it + 1).astype(jnp.uint8)
        return MSBFSParentState(
            frontier=new,
            visited=state.visited | new,
            levels=jnp.where(is_new, lvl, state.levels),
            parents=jnp.where(is_new, parent_cand, state.parents),
        )


class TopKState(NamedTuple):
    frontier: jax.Array  # [n] bool — some slot of this row improved
    dists: jax.Array  # [n, K] float32, sorted ascending (inf = empty slot)
    src_mask: jax.Array  # [n] bool


class TopKPaths:
    """Weighted top-k shortest-walk lengths (k-slot Bellman-Ford).

    Full-Jacobi pull each round: ``merged[v]`` is the k smallest of v's seed
    value (0 for sources) and ``dists[u, :] + w(u, v)`` over ALL in-neighbors
    u — a recompute, not a frontier-masked delta, so duplicate walks are
    never double-counted. From the seed-only init the recompute is monotone
    non-increasing, hence the engine's generic ``any(frontier != 0)`` loop
    condition terminates exactly at the k-best fixpoint; ``frontier`` marks
    rows whose slot vector improved last round. Pull-only: needs the reverse
    ELL operand (route ``extend='ell_pull'``)."""

    MERGE = "min"
    LANES_OK = False  # k-slot float frontier has no saturating lane form
    K = 4

    @staticmethod
    def init(n_nodes: int, sources: jax.Array) -> TopKState:
        src = jnp.zeros((n_nodes,), jnp.bool_).at[sources].set(
            True, mode="drop"
        )
        dists = jnp.full((n_nodes, TopKPaths.K), jnp.inf, jnp.float32)
        dists = dists.at[sources, 0].set(0.0, mode="drop")
        return TopKState(frontier=src, dists=dists, src_mask=src)

    @staticmethod
    def local_extend(g: EllGraph, state: TopKState, row_offset=None,
                     n_out=None, row_base=None):
        raise NotImplementedError(
            "top-k relax is pull-only (scans the reverse ELL); run it "
            "through a backend with reverse operands (extend='ell_pull')"
        )

    @staticmethod
    def extend(be, ops, state: TopKState, ctx):
        return be.min_topk(ops, state.dists, state.src_mask, ctx)

    @staticmethod
    def gang_extend(be, ops, state: TopKState, ctx):
        return jax.vmap(
            lambda st: TopKPaths.extend(be, ops, st, ctx)
        )(state)

    @staticmethod
    def apply(state: TopKState, merged: jax.Array, it: jax.Array):
        improved = jnp.any(merged < state.dists, axis=-1)
        return TopKState(
            frontier=improved, dists=merged, src_mask=state.src_mask
        )


class PPRState(NamedTuple):
    frontier: jax.Array  # [n] f32: residual where > EPS, else exactly 0
    residual: jax.Array  # [n] f32
    mass: jax.Array  # [n] f32 — the PPR estimate


class PPRDiffusion:
    """Personalized PageRank via residual diffusion (push-style).

    Every round, all rows with residual above EPS settle at once: ALPHA of
    the settled residual lands in ``mass`` and (1-ALPHA), out-degree
    normalized, diffuses to the out-neighbors (summed across shards with
    MERGE='sum'). The epsilon termination lives in the frontier leaf —
    ``frontier`` holds the residual where it exceeds EPS and exactly 0
    elsewhere, so the engine's generic ``any(frontier != 0)`` loop condition
    IS the residual-mass convergence test; resume/gang builders need no
    modification. Seeds start with residual 1 each (multi-seed results are
    the sum of per-seed PPR vectors — linearity). Dangling rows (out-degree
    0) leak their (1-ALPHA) share, which is what guarantees convergence and
    what the numpy oracle mirrors exactly."""

    MERGE = "sum"
    LANES_OK = False
    ALPHA = 0.15
    EPS = 1e-4

    @staticmethod
    def init(n_nodes: int, sources: jax.Array) -> PPRState:
        r = jnp.zeros((n_nodes,), jnp.float32).at[sources].set(
            1.0, mode="drop"
        )
        return PPRState(
            frontier=r, residual=r, mass=jnp.zeros((n_nodes,), jnp.float32)
        )

    @staticmethod
    def local_extend(g: EllGraph, state: PPRState, row_offset=None,
                     n_out=None, row_base=None) -> jax.Array:
        push = (1.0 - PPRDiffusion.ALPHA) * state.frontier
        return ell_push_sum(g, push, row_offset, n_out, normalize=True)

    @staticmethod
    def extend(be, ops, state: PPRState, ctx):
        push = (1.0 - PPRDiffusion.ALPHA) * state.frontier
        return be.push_sum(ops, push, ctx, normalize=True)

    @staticmethod
    def gang_extend(be, ops, state: PPRState, ctx):
        return jax.vmap(
            lambda st: PPRDiffusion.extend(be, ops, st, ctx)
        )(state)

    @staticmethod
    def apply(state: PPRState, pushed: jax.Array, it: jax.Array):
        settled = state.frontier  # the residual mass pushed this round
        r = state.residual - settled + pushed
        return PPRState(
            frontier=jnp.where(r > PPRDiffusion.EPS, r, 0.0),
            residual=r,
            mass=state.mass + PPRDiffusion.ALPHA * settled,
        )


class PatternState(NamedTuple):
    frontier: jax.Array  # [n] int32: walk counts of the current hop
    wedges: jax.Array  # [n] int32: 2-hop walk counts seed -> · -> v
    closed: jax.Array  # [n] int32: 3-hop walk counts seed -> · -> · -> v
    src_mask: jax.Array  # [n] bool


class PatternCounts:
    """2–3-hop pattern counts (wedges / triangles) as matmul chains.

    The frontier carries exact int32 walk multiplicities: hop t+1 is
    ``c[v] = Σ_u c[u]·A[u, v]`` — on the block path a chain of MXU matmuls
    over the existing ``ShardedBlocks``, on the push path the same additive
    scatter. After hop 2 the per-node wedge counts (2-walks from the seed
    set) are latched; after hop 3 the closed-walk counts are latched and the
    frontier zeroes itself, so the generic loop condition stops at exactly 3
    iterations. Triangle counts fall out host-side: ``closed`` at a seed row
    counts the directed 3-cycles through that seed (2 per undirected
    triangle); wedge totals are ``wedges.sum()``. Counts are exact (additive
    int32), not saturating — the saturating 0/1 matmul stays the
    reachability path."""

    MERGE = "sum"
    LANES_OK = False
    HOPS = 3

    @staticmethod
    def init(n_nodes: int, sources: jax.Array) -> PatternState:
        src = jnp.zeros((n_nodes,), jnp.bool_).at[sources].set(
            True, mode="drop"
        )
        z = jnp.zeros((n_nodes,), jnp.int32)
        return PatternState(
            frontier=src.astype(jnp.int32), wedges=z, closed=z, src_mask=src
        )

    @staticmethod
    def local_extend(g: EllGraph, state: PatternState, row_offset=None,
                     n_out=None, row_base=None) -> jax.Array:
        return ell_push_sum(g, state.frontier, row_offset, n_out)

    @staticmethod
    def extend(be, ops, state: PatternState, ctx):
        return be.push_sum(ops, state.frontier, ctx)

    @staticmethod
    def gang_extend(be, ops, state: PatternState, ctx):
        return jax.vmap(
            lambda st: PatternCounts.extend(be, ops, st, ctx)
        )(state)

    @staticmethod
    def apply(state: PatternState, pushed: jax.Array, it: jax.Array):
        # it=0 -> pushed = 1-hop counts; it=1 -> 2-hop; it=2 -> 3-hop
        return PatternState(
            frontier=jnp.where(it >= PatternCounts.HOPS - 1, 0, pushed),
            wedges=jnp.where(it == 1, pushed, state.wedges),
            closed=jnp.where(it == 2, pushed, state.closed),
            src_mask=state.src_mask,
        )


EDGE_COMPUTES = {
    "bfs_levels": BFSLevels,
    "sp_lengths": SPLengths,
    "sp_parents": SPParents,
    "bellman_ford": BellmanFord,
    "reachability": Reachability,
    "msbfs_lengths": MSBFSLengths,
    "msbfs_parents": MSBFSParents,
    "topk_paths": TopKPaths,
    "ppr": PPRDiffusion,
    "pattern_counts": PatternCounts,
}


class QueryKind(NamedTuple):
    """One row of the serving-surface query registry: how a client-facing
    ``query_kind`` maps onto edge computes and what comes back.

    ``edge_compute`` is None for the built-in reachability family, where the
    dispatcher still picks sp/msbfs × lengths/parents from (policy,
    returns_paths); every other kind names one compute. ``result_leaves``
    are the state fields delivered per query. ``lanes_ok`` mirrors the
    compute's LANES_OK and gates MS-BFS lane packing at admission."""

    edge_compute: str | None
    result_leaves: tuple
    needs_weights: bool = False
    lanes_ok: bool = True


QUERY_KINDS = {
    "reach": QueryKind(None, ("levels",)),
    "topk_paths": QueryKind(
        "topk_paths", ("dists",), needs_weights=True, lanes_ok=False
    ),
    "ppr": QueryKind("ppr", ("mass",), lanes_ok=False),
    "pattern_counts": QueryKind(
        "pattern_counts", ("wedges", "closed"), lanes_ok=False
    ),
}
