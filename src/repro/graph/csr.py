"""Graph storage structures.

Two device-side layouts and one host-side layout:

- ``CSRGraph`` (host, numpy): canonical compressed-sparse-row adjacency. Used by
  generators, the numpy oracle, and for conversion.
- ``EllGraph`` (device, jnp): padded fixed-width neighbor lists (ELL format).
  TPU-friendly: every row has ``max_deg`` slots, padding uses the out-of-bounds
  sentinel ``n_nodes`` so scatter ops drop it. This is the layout the seed IFE
  engine (``core.ife`` on a bare ``EllGraph``) extends frontiers over, and
  the padded reverse operand of ``ell_pull`` / top-k relax.
- ``BinnedEll`` (device, jnp): degree-binned adjacency slabs, the scan
  operand of the served push (forward rows) and of the bottom-up pull
  (reverse rows). Rows are permuted into pow2-bounded degree buckets and
  each bucket is its own ELL slab padded only to that bucket's width, so a
  scan costs ~``sum(deg)`` slots instead of the single padded slab's
  ``n × max_deg`` (EmptyHeaded-style per-row layout specialization). The
  (permutation, inverse) pair restores the original row order
  bit-identically.
- ``BlockAdjacency`` (device, jnp): 0/1 dense blocks of the adjacency matrix plus
  block coordinates — the block-sparse layout consumed by the ``msbfs_extend``
  Pallas kernel (MXU formulation of MS-BFS).

The paper's Kuzu implementation reads CSR through a disk buffer manager; on TPU the
partitioned adjacency is HBM-resident, and "amount of scans" becomes HBM bytes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Host-side CSR adjacency (out-edges)."""

    indptr: np.ndarray  # [n_nodes + 1] int64
    indices: np.ndarray  # [n_edges] int32, destination node ids
    weights: Optional[np.ndarray] = None  # [n_edges] float32 (optional)

    @property
    def n_nodes(self) -> int:
        return len(self.indptr) - 1

    @property
    def n_edges(self) -> int:
        return int(self.indptr[-1])

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)

    @property
    def avg_degree(self) -> float:
        return self.n_edges / max(self.n_nodes, 1)

    def neighbors(self, u: int) -> np.ndarray:
        return self.indices[self.indptr[u] : self.indptr[u + 1]]

    def reverse(self) -> "CSRGraph":
        """In-edge CSR (transpose)."""
        n = self.n_nodes
        src = np.repeat(np.arange(n, dtype=np.int32), self.degrees)
        order = np.argsort(self.indices, kind="stable")
        rindices = src[order]
        rindptr = np.zeros(n + 1, dtype=np.int64)
        np.add.at(rindptr, self.indices + 1, 1)
        rindptr = np.cumsum(rindptr)
        w = None if self.weights is None else self.weights[order]
        return CSRGraph(indptr=rindptr, indices=rindices, weights=w)

    def edge_list(self) -> tuple[np.ndarray, np.ndarray]:
        src = np.repeat(
            np.arange(self.n_nodes, dtype=np.int32), self.degrees
        )
        return src, self.indices.astype(np.int32)

    def edge_keys(self) -> np.ndarray:
        """Sorted ``src * n_nodes + dst`` int64 keys, one per edge — the
        identity the dedup in ``csr_from_edges`` and the delta layer's
        edge-set arithmetic (``graph.delta``) both key on. Self-loops are
        ordinary keys; a deduped CSR's keys are strictly increasing.

        Supported range: ``n_nodes < 2**31``. Node ids are stored as int32
        throughout the operand layouts, and the int64 key arithmetic itself
        overflows near ``n_nodes ~ 2**31.5``; the int32 storage bound is hit
        first, so we raise there rather than silently wrap."""
        if self.n_nodes >= 2**31:
            raise ValueError(
                f"n_nodes={self.n_nodes} exceeds the int32 node-id range "
                "(< 2**31) that edge keys and operand layouts support"
            )
        src = np.repeat(
            np.arange(self.n_nodes, dtype=np.int64), self.degrees
        )
        return src * self.n_nodes + self.indices.astype(np.int64)


def csr_from_edges(
    n_nodes: int,
    src: np.ndarray,
    dst: np.ndarray,
    weights: Optional[np.ndarray] = None,
    dedup: bool = True,
) -> CSRGraph:
    """Build CSR from an edge list, sorting (and optionally deduplicating).

    The dedup is *stable keep-first* over the ``src * n_nodes + dst`` key
    (see ``CSRGraph.edge_keys``): among duplicate edges the one earliest
    in the input order survives, weights included. The mutable-graph path
    (``graph.delta.apply_delta_csr``) relies on this by concatenating
    surviving old edges ahead of inserts — re-inserting a live edge keeps
    the existing edge and its weight, exactly as a from-scratch build of
    the same concatenated list would.

    Supported range: ``n_nodes < 2**31``. The emitted ``indices`` are int32
    (every downstream operand layout stores node ids as int32), so larger
    graphs would silently wrap on the cast; we raise instead. The int64
    ``src * n_nodes + dst`` dedup key overflows slightly later (around
    ``n_nodes ~ 2**31.5``), so the int32 bound is the binding one."""
    if n_nodes >= 2**31:
        raise ValueError(
            f"n_nodes={n_nodes} exceeds the int32 node-id range (< 2**31); "
            "indices would silently wrap on the int32 cast"
        )
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    key = src * n_nodes + dst
    order = np.argsort(key, kind="stable")
    key, src, dst = key[order], src[order], dst[order]
    if weights is not None:
        weights = np.asarray(weights, dtype=np.float32)[order]
    if dedup and len(key):
        keep = np.concatenate([[True], key[1:] != key[:-1]])
        src, dst = src[keep], dst[keep]
        if weights is not None:
            weights = weights[keep]
    indptr = np.zeros(n_nodes + 1, dtype=np.int64)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr)
    return CSRGraph(
        indptr=indptr, indices=dst.astype(np.int32), weights=weights
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EllGraph:
    """Device-side padded neighbor lists.

    ``indices[v, j]`` is the j'th out-neighbor of v, or ``n_nodes`` (an
    out-of-bounds sentinel) when ``j >= degree(v)``. Scatter updates use
    ``mode='drop'`` so sentinel writes vanish; gathers index a (n_nodes+1)-sized
    array whose last row is a neutral element.
    """

    indices: jax.Array  # [n_nodes, max_deg] int32
    degrees: jax.Array  # [n_nodes] int32
    weights: Optional[jax.Array] = None  # [n_nodes, max_deg] float32

    @property
    def n_nodes(self) -> int:
        return self.indices.shape[0]

    @property
    def max_deg(self) -> int:
        return self.indices.shape[1]

    @property
    def mask(self) -> jax.Array:
        return (
            jnp.arange(self.max_deg, dtype=jnp.int32)[None, :]
            < self.degrees[:, None]
        )

    @property
    def n_edges(self) -> jax.Array:
        return self.degrees.sum()


def _ell_slot_positions(
    indptr: np.ndarray, cap: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized (row, slot, csr_position) triples for every kept edge:
    slot j of row v maps to csr position indptr[v] + j, for j < min(deg, cap)."""
    degs = np.diff(indptr).astype(np.int64)
    kept = np.minimum(degs, cap)
    rows = np.repeat(np.arange(len(degs), dtype=np.int64), kept)
    total = int(kept.sum())
    slots = np.arange(total, dtype=np.int64) - np.repeat(
        np.cumsum(kept) - kept, kept
    )
    pos = indptr[:-1][rows] + slots
    return rows, slots, pos


def ell_from_csr(
    csr: CSRGraph, max_deg: Optional[int] = None, pad_to_multiple: int = 8
) -> EllGraph:
    """Convert CSR → ELL, truncating rows beyond ``max_deg`` if given.

    Fully vectorized (no per-node Python loop): host-side graph prep is
    O(n_edges) numpy index arithmetic, so setup no longer dominates for
    large graphs.

    A zero effective cap (``max_deg=0``, or an edgeless graph with
    ``max_deg=None``) yields a genuine zero-width ``[n, 0]`` slab — NOT a
    1-wide padded row. Every slot of a 1-wide slab would be scanned by
    every backend on every iteration for rows that own no edges, which
    breaks the binned-pull scanned-slot accounting (and the historical
    ``max_deg or 1`` coercion silently turned an explicit 0 into 8)."""
    n = csr.n_nodes
    degs = csr.degrees.astype(np.int32)
    if max_deg is None:
        cap = int(degs.max()) if n else 0
    else:
        cap = max(int(max_deg), 0)
    if cap > 0:
        cap = -(-cap // pad_to_multiple) * pad_to_multiple
    indices = np.full((n, cap), n, dtype=np.int32)  # sentinel = n
    rows, slots, pos = _ell_slot_positions(csr.indptr, cap)
    indices[rows, slots] = csr.indices[pos]
    w = None
    if csr.weights is not None:
        w = np.zeros((n, cap), dtype=np.float32)
        w[rows, slots] = csr.weights[pos]
    clipped = np.minimum(degs, cap)
    return EllGraph(
        indices=jnp.asarray(indices),
        degrees=jnp.asarray(clipped),
        weights=None if w is None else jnp.asarray(w),
    )


def truncate_csr(csr: CSRGraph, max_deg: Optional[int]) -> CSRGraph:
    """The *effective* graph after an ELL degree cap: first ``max_deg``
    out-edges per node. Reverse-ELL and block operands are derived from this
    so every extension backend scans the same edge set (bit-parity)."""
    if max_deg is None or (len(csr.degrees) == 0) or (
        int(csr.degrees.max()) <= max_deg
    ):
        return csr
    rows, _, pos = _ell_slot_positions(csr.indptr, int(max_deg))
    kept = np.minimum(csr.degrees, int(max_deg))
    indptr = np.zeros(csr.n_nodes + 1, dtype=np.int64)
    indptr[1:] = np.cumsum(kept)
    return CSRGraph(
        indptr=indptr,
        indices=csr.indices[pos].astype(np.int32),
        weights=None if csr.weights is None else csr.weights[pos],
    )


#: slots of one chunk of a binned structure's flat slot arrays: the unit a
#: scan skips by (``BinnedEll.chunk_lo`` / ``chunk_hi``)
SCAN_CHUNK = 128


def _chunk_spans(shapes) -> tuple[np.ndarray, np.ndarray]:
    """Binned positions of the first and the last row that each
    ``SCAN_CHUNK``-slot chunk of the flat slot arrays touches, for slabs
    of ``shapes`` (``(rows_b, width_b)`` each) stored one after another,
    row-major."""
    rows_b = np.asarray([r for r, _ in shapes], np.int64)
    widths = np.asarray([w for _, w in shapes], np.int64)
    size = rows_b * widths
    total = int(size.sum())
    off = np.concatenate([[0], np.cumsum(size)])
    first = np.concatenate([[0], np.cumsum(rows_b)])[:-1]

    def pos(s):
        # the slab holding slot s: the last whose offset is <= s, which
        # skips every slab with no slots
        b = np.searchsorted(off, s, side="right") - 1
        return first[b] + (s - off[b]) // widths[b]

    a = np.arange(0, total, SCAN_CHUNK, dtype=np.int64)
    last = np.minimum(a + SCAN_CHUNK - 1, total - 1)
    return pos(a).astype(np.int32), pos(last).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class BinnedEll:
    """Degree-binned adjacency slabs: the scan operand of both directions
    (the push scatters over the forward structure, the pull gathers over
    the reverse one).

    Rows are partitioned into degree buckets with pow2-bounded edges,
    refined so that every row's slab width is within ``max_overhead`` of
    its true degree; bucket ``b`` is a dense ELL slab of ``shapes[b] =
    (rows_b, width_b)`` holding neighbour ids (sentinel = padded row
    count, out of range for every ``[n_pad]`` scatter and gather). The
    slabs are stored one after another, each row-major, in one flat
    ``nbrs: [K, S]`` array (``slabs`` gives the 2-D views), and ``rows``
    names each slot's local row: a scan walks one flat array in
    fixed-size steps, which a TPU compiles in seconds where a program
    over many odd 2-D slab shapes takes minutes. ``K`` is the graph
    shard count: shard ``k`` owns contiguous local rows
    ``[k·rows_local, (k+1)·rows_local)`` and bins them independently, but
    slab shapes are common across shards (counts padded to the
    per-bucket max) so the structure is SPMD under shard_map — leading
    axes shard over the policy's graph mesh axes.

    Row placement is carried by a per-shard permutation: concatenating
    the slab rows gives a ``[K, rows_binned]`` virtual vector of per-row
    results; ``perm[k, p]`` is the local row stored at binned position
    ``p`` (``rows_local`` for slab-padding rows, whose slots' ``rows``
    are ``rows_local`` too) and ``inv[k, r]`` is the binned position of
    local row ``r`` — so ``cat[inv]`` restores the original row order
    bit-identically. ``degrees[k, r]`` is local row ``r``'s degree (its
    kept edges).

    The flat arrays end in sentinel slots up to a whole number of
    ``SCAN_CHUNK``-slot chunks, and ``chunk_lo[k, c]`` / ``chunk_hi[k, c]``
    are the binned positions of the first and the last row chunk ``c``
    touches: slots are row-major in binned order, so a chunk's rows are
    one span of positions, and a scan can tell from per-row activity
    alone which chunks hold a slot that can change its result
    (``edge_compute.live_chunks``). The spans follow from ``shapes``
    alone, so they hold across shards and across in-place delta folds.

    Zero-degree rows (including rows emptied by degree truncation) live
    in a genuine **zero-width** slab: they cost nothing to scan. A full
    scan costs ~``sum(deg)`` slots instead of a single padded slab's
    ``n × max_deg`` (EmptyHeaded-style per-row layout specialization).
    """

    nbrs: jax.Array  # [K, S] int32 neighbour per slot (sentinel = n_pad)
    rows: jax.Array  # [K, S] int32 local row per slot (rows_local = pad)
    perm: jax.Array  # [K, rows_binned] int32 (binned pos -> local row)
    inv: jax.Array  # [K, rows_local] int32 (local row -> binned pos)
    degrees: jax.Array  # [K, rows_local] int32 (kept edges per row)
    chunk_lo: jax.Array  # [K, S / SCAN_CHUNK] int32 (first row's position)
    chunk_hi: jax.Array  # [K, S / SCAN_CHUNK] int32 (last row's position)
    slot_weights: Optional[jax.Array] = None  # [K, S] float32
    shapes: tuple = ()  # static: (rows_b, width_b) of every bucket

    def _views(self, flat) -> tuple:
        out, off = [], 0
        for r, w in self.shapes:
            out.append(flat[:, off:off + r * w].reshape(flat.shape[0], r, w))
            off += r * w
        return tuple(out)

    @property
    def slabs(self) -> tuple:
        """Each bucket's ``[K, rows_b, width_b]`` slab (views of ``nbrs``
        for host numpy leaves, so writes land in the structure)."""
        return self._views(self.nbrs)

    @property
    def slab_weights(self) -> Optional[tuple]:
        return None if self.slot_weights is None else self._views(
            self.slot_weights)

    def slab_rows(self) -> tuple:
        """Each bucket's ``[K, rows_b, width_b]`` view of ``rows``."""
        return self._views(self.rows)

    @property
    def n_slabs(self) -> int:
        return len(self.shapes)

    @property
    def rows_local(self) -> int:
        return self.inv.shape[-1]

    @property
    def n_rows(self) -> int:
        """Rows over every shard the structure holds (``n_pad`` when it
        holds them all)."""
        return self.inv.shape[0] * self.inv.shape[1]

    @property
    def widths(self) -> tuple:
        return tuple(w for _, w in self.shapes)

    @property
    def capacity_slots(self) -> int:
        """Adjacency slots of one shard's slabs (the trailing chunk padding
        left out)."""
        return int(sum(r * w for r, w in self.shapes))

    @property
    def slots(self) -> int:
        """Adjacency slots over every shard the structure holds."""
        return int(self.nbrs.shape[0]) * self.capacity_slots

    @property
    def scan_slots(self) -> int:
        """Slots of one shard's flat arrays, whole chunks: what a scan
        that skips no chunk reads."""
        return int(self.nbrs.shape[-1])

    def row_widths(self) -> np.ndarray:
        """[K, rows_local] host array: each local row's slab width — the
        slots a scan pays for that row (scanned-slot accounting)."""
        w = np.concatenate(
            [np.full((r,), w, np.int64) for r, w in self.shapes]
        )
        return w[np.asarray(self.inv)]


jax.tree_util.register_dataclass(
    BinnedEll,
    data_fields=["nbrs", "rows", "perm", "inv", "degrees", "chunk_lo",
                 "chunk_hi", "slot_weights"],
    meta_fields=["shapes"],
)


def _degree_bucket_edges(
    degs: np.ndarray, max_overhead: float
) -> list[tuple[int, int]]:
    """Inclusive (lo, hi) degree ranges of the nonzero buckets.

    Pow2 bucket edges, greedily refined over the distinct degree values
    so every bucket satisfies ``hi <= max_overhead * lo`` — which bounds
    each row's padding (slab width / true degree) and therefore the whole
    structure's scan overhead by ``max_overhead``."""
    uniq = np.unique(degs[degs > 0])
    edges: list[tuple[int, int]] = []
    i = 0
    while i < len(uniq):
        lo = int(uniq[i])
        pow2_hi = 1 << (lo - 1).bit_length() if lo > 1 else 1
        limit = min(int(lo * max_overhead), pow2_hi) if lo > 1 else 1
        j = i
        while j + 1 < len(uniq) and int(uniq[j + 1]) <= limit:
            j += 1
        edges.append((lo, int(uniq[j])))
        i = j + 1
    return edges


@dataclasses.dataclass(frozen=True)
class BinnedPlan:
    """Shard-independent layout of a ``BinnedEll``.

    Everything that couples shards — the bucket edges (derived from the
    *global* degree histogram), the common (max-over-shards) slab row
    counts, and the row→bucket assignment — is computed here in one O(n)
    pass, so a single shard's slabs can then be built from its own rows
    alone (``binned_shard``). ``binned_ell`` stacks those shards, so the
    streamed operand path and the wholesale build agree bitwise: host
    peak memory per shard is the shard's own slab bytes, not the whole
    structure's (see docs/scale.md).
    """

    widths: tuple  # per-bucket slab width; widths[0] == 0
    rows_b: np.ndarray  # [n_buckets] common slab row counts
    bucket_of: np.ndarray  # [n_pad] bucket id per padded row
    degs: np.ndarray  # [n_pad] effective degree per padded row
    shards: int
    n_pad: int

    @property
    def rows_local(self) -> int:
        return self.n_pad // self.shards

    @property
    def rows_binned(self) -> int:
        return int(self.rows_b.sum())


def binned_plan(
    degs: np.ndarray,
    n_pad: int,
    shards: int = 1,
    max_overhead: float = 1.1,
) -> BinnedPlan:
    """Global planning pass of a binned build, without touching any edge
    data: ``degs`` is the degree of each row to bin (out-degrees for the
    forward structure, ``np.bincount(eff.indices)`` for the reverse)."""
    assert n_pad % max(shards, 1) == 0, (n_pad, shards)
    rows_local = n_pad // shards
    degs_pad = np.zeros(n_pad, np.int64)
    degs_pad[: len(degs)] = degs
    nz_edges = _degree_bucket_edges(degs_pad, max_overhead)
    # bucket 0 is always the zero-width slab (rows with no edges)
    bucket_of = np.zeros(n_pad, np.int64)
    widths = [0]
    for b, (lo, hi) in enumerate(nz_edges, start=1):
        bucket_of[(degs_pad >= lo) & (degs_pad <= hi)] = b
        widths.append(hi)
    shard_of = np.arange(n_pad, dtype=np.int64) // rows_local
    counts = np.zeros((shards, len(widths)), np.int64)
    np.add.at(counts, (shard_of, bucket_of), 1)
    return BinnedPlan(
        widths=tuple(widths),
        rows_b=counts.max(axis=0),
        bucket_of=bucket_of,
        degs=degs_pad,
        shards=shards,
        n_pad=n_pad,
    )


def binned_shard(plan: BinnedPlan, k: int, local: CSRGraph) -> BinnedEll:
    """Shard ``k`` of a binned structure (leading axis K=1), built from
    that shard's own rows: ``local`` holds ``plan.rows_local`` rows whose
    ``indices`` are global neighbour ids (``csr_rows`` for the forward
    direction, ``partition.reverse_shard`` for the reverse). All leaves
    are host numpy so the caller controls device placement. Slots within
    one bucket follow ascending local row, and each row keeps its CSR
    neighbour order — the structure is a deterministic function of the
    plan and the rows."""
    rl = plan.rows_local
    n_pad = plan.n_pad
    bucket_k = plan.bucket_of[k * rl : (k + 1) * rl]
    degs_k = plan.degs[k * rl : (k + 1) * rl]
    n_buckets = len(plan.widths)
    starts = np.cumsum(plan.rows_b) - plan.rows_b

    rows_all = np.arange(rl, dtype=np.int64)
    order = np.argsort(bucket_k, kind="stable")  # (bucket, local) asc
    o_bucket, o_local = bucket_k[order], rows_all[order]
    run_start = np.concatenate(
        [[0], np.cumsum(np.bincount(o_bucket, minlength=n_buckets))]
    )[:-1]
    slot_in_bucket = np.arange(rl, dtype=np.int64) - run_start[o_bucket]
    pos = starts[o_bucket] + slot_in_bucket

    perm = np.full((1, plan.rows_binned), rl, np.int32)
    perm[0, pos] = o_local.astype(np.int32)
    inv = np.zeros((1, rl), np.int32)
    inv[0, o_local] = pos.astype(np.int32)

    has_w = local.weights is not None
    slabs, slab_w, slab_r = [], [], []
    for b in range(n_buckets):
        w = plan.widths[b]
        rb = int(plan.rows_b[b])
        slab = np.full((rb, w), n_pad, np.int32)
        wslab = np.zeros((rb, w), np.float32) if has_w else None
        # each slab row's slots belong to the row stored there
        p0 = int(starts[b])
        slab_r.append(np.broadcast_to(perm[0, p0:p0 + rb, None], (rb, w)))
        if w > 0:
            sel = o_bucket == b
            rows = o_local[sel]  # local row ids, slot order
            kept = degs_k[rows]
            flat = np.repeat(np.arange(len(rows)), kept)
            slots = np.arange(int(kept.sum()), dtype=np.int64) - np.repeat(
                np.cumsum(kept) - kept, kept
            )
            src = local.indptr[rows][flat] + slots
            slab[slot_in_bucket[sel][flat], slots] = local.indices[src]
            if has_w:
                wslab[slot_in_bucket[sel][flat], slots] = local.weights[src]
        slabs.append(slab)
        slab_w.append(wslab)
    shapes = tuple(
        (int(r), int(w)) for r, w in zip(plan.rows_b, plan.widths)
    )
    # sentinel slots up to a whole chunk: their neighbour and row drop
    pad = -sum(r * w for r, w in shapes) % SCAN_CHUNK
    flat = lambda parts, dt, fill: np.concatenate(
        [x.reshape(-1) for x in parts] + [np.full(pad, fill, dt)]
    ).astype(dt)[None]
    lo, hi = _chunk_spans(shapes)
    return BinnedEll(
        nbrs=flat(slabs, np.int32, n_pad),
        rows=flat(slab_r, np.int32, rl),
        perm=perm,
        inv=inv,
        degrees=degs_k.astype(np.int32)[None],
        chunk_lo=lo[None],
        chunk_hi=hi[None],
        slot_weights=flat(slab_w, np.float32, 0) if has_w else None,
        shapes=shapes,
    )


def csr_rows(csr: CSRGraph, lo: int, hi: int) -> CSRGraph:
    """Rows ``[lo, hi)`` of ``csr`` as a CSR of ``hi - lo`` rows (global
    neighbour ids kept); rows at or beyond ``csr.n_nodes`` are empty pad
    rows. The forward counterpart of ``partition.reverse_shard``."""
    n = csr.n_nodes
    lo_r, hi_r = min(lo, n), min(hi, n)
    e_lo, e_hi = int(csr.indptr[lo_r]), int(csr.indptr[hi_r])
    indptr = np.full(hi - lo + 1, e_hi - e_lo, np.int64)
    indptr[: hi_r - lo_r + 1] = csr.indptr[lo_r : hi_r + 1] - e_lo
    return CSRGraph(
        indptr=indptr,
        indices=csr.indices[e_lo:e_hi],
        weights=None if csr.weights is None else csr.weights[e_lo:e_hi],
    )


def binned_ell(
    csr: CSRGraph,
    n_pad: int,
    shards: int = 1,
    max_overhead: float = 1.1,
) -> BinnedEll:
    """The degree-binned slabs of ``csr``'s rows, stacked over ``shards``
    (jnp leaves). Build the forward structure from the effective graph
    (see ``truncate_csr``) and the reverse one from its transpose, so
    every backend scans the same edge set. ``n_pad`` is the padded row
    count (divisible by ``shards``); rows ``>= csr.n_nodes`` are empty and
    land in the zero-width slab. Shard by shard through ``binned_shard``,
    so the streamed build's pieces are this structure's slices."""
    plan = binned_plan(csr.degrees, n_pad, shards, max_overhead)
    rl = plan.rows_local
    parts = [
        binned_shard(plan, k, csr_rows(csr, k * rl, (k + 1) * rl))
        for k in range(shards)
    ]
    return jax.tree.map(
        lambda *xs: jnp.asarray(np.concatenate(xs)), *parts
    )


def ell_shard(
    csr: CSRGraph, lo: int, hi: int, cap: int, sentinel: int
) -> tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Rows ``[lo, hi)`` of the padded ELL slab as host numpy
    ``(indices [rows, cap], degrees [rows], weights-or-None)`` — the
    streamed build's row-range counterpart of
    ``pad_ell(ell_from_csr(csr), ...)``. ``cap`` is the *global* padded
    row width and ``sentinel`` the padded node count ``n_pad`` (when
    ``n_pad == n_nodes`` the wholesale slab's sentinel is the same value,
    so the slices agree bitwise either way). Rows at or beyond
    ``csr.n_nodes`` are pad rows: all-sentinel, degree 0, zero weights."""
    n = csr.n_nodes
    rows = hi - lo
    lo_r, hi_r = min(lo, n), min(hi, n)
    indices = np.full((rows, cap), sentinel, np.int32)
    degs = np.zeros(rows, np.int32)
    w = (
        np.zeros((rows, cap), np.float32)
        if csr.weights is not None
        else None
    )
    if hi_r > lo_r and cap > 0:
        sub = csr.indptr[lo_r : hi_r + 1] - csr.indptr[lo_r]
        r, s, p = _ell_slot_positions(sub, cap)
        base = csr.indptr[lo_r]
        indices[r, s] = csr.indices[base + p]
        if w is not None:
            w[r, s] = csr.weights[base + p]
    if hi_r > lo_r:
        degs[: hi_r - lo_r] = np.minimum(
            csr.degrees[lo_r:hi_r], cap
        ).astype(np.int32)
    return indices, degs, w


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BlockAdjacency:
    """Block-sparse 0/1 adjacency: only blocks containing at least one edge are
    stored. ``blocks[b]`` is a dense ``[block, block]`` int8 tile;
    ``block_rows[b]``/``block_cols[b]`` give its (src-block, dst-block) coords.
    ``row_ptr`` groups the block list by src-block (CSR over blocks) so a kernel
    can iterate the nonzero blocks of one frontier stripe.
    """

    blocks: jax.Array  # [n_blocks, B, B] int8  (A[u, v] = 1 if edge u->v)
    block_rows: jax.Array  # [n_blocks] int32
    block_cols: jax.Array  # [n_blocks] int32
    row_ptr: jax.Array  # [n_row_blocks + 1] int32

    @property
    def block_size(self) -> int:
        return self.blocks.shape[1]

    @property
    def n_blocks(self) -> int:
        return self.blocks.shape[0]

    @property
    def n_row_blocks(self) -> int:
        return self.row_ptr.shape[0] - 1

    @property
    def occupancy(self) -> float:
        """Fraction of the dense block grid that is materialized — the
        block-level sparsity economy (paper's 'reduced scans' analogue)."""
        g = self.n_row_blocks
        return self.n_blocks / float(g * g)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedBlocks:
    """Per-shard block-sparse 0/1 adjacency, stacked over graph shards.

    Shard k owns rows [k·rows_local, (k+1)·rows_local) of the padded graph;
    its nonzero ``[B, B]`` tiles have *local* source row-block ids
    (``block_rows``) and *global* destination col-block ids (``block_cols``).
    Shards are padded to one common block count with all-zero tiles whose col
    id is the out-of-range sentinel ``n_out // B`` (scatter ``mode='drop'``).
    Leading axis shards over the policy's graph mesh axes, so inside
    ``shard_map`` each device sees exactly its own ``[1, nb, B, B]`` slice.
    This is the operand of the ``block_mxu`` extension backend.
    """

    blocks: jax.Array  # [K, nb, B, B] int8
    block_rows: jax.Array  # [K, nb] int32 (local row-block ids)
    block_cols: jax.Array  # [K, nb] int32 (global col-block ids; pad = G)

    @property
    def block_size(self) -> int:
        return self.blocks.shape[2]


def sharded_blocks_from_csr(
    csr: CSRGraph, n_pad: int, shards: int, block: int = 128
) -> ShardedBlocks:
    """Build the stacked per-shard block adjacency (host-side, vectorized).

    ``n_pad`` must be divisible by ``shards * block``; pad rows/cols beyond
    ``csr.n_nodes`` are empty so they never materialize tiles.
    """
    assert n_pad % (shards * block) == 0, (n_pad, shards, block)
    rows_local = n_pad // shards
    rb = rows_local // block  # row blocks per shard
    g = n_pad // block  # global col blocks
    src, dst = csr.edge_list()
    src = src.astype(np.int64)
    dst = dst.astype(np.int64)
    shard = src // rows_local
    br = (src % rows_local) // block
    bc = dst // block
    key = (shard * rb + br) * g + bc
    uniq, inv = np.unique(key, return_inverse=True)
    nb_tot = len(uniq)
    tiles = np.zeros((max(nb_tot, 1), block, block), dtype=np.int8)
    tiles[inv, src % block, dst % block] = 1
    u_shard = (uniq // (rb * g)).astype(np.int64)
    u_row = ((uniq // g) % rb).astype(np.int32)
    u_col = (uniq % g).astype(np.int32)
    counts = np.bincount(u_shard, minlength=shards) if nb_tot else np.zeros(
        shards, np.int64
    )
    nb = max(int(counts.max()) if nb_tot else 0, 1)
    out_blocks = np.zeros((shards, nb, block, block), dtype=np.int8)
    out_rows = np.zeros((shards, nb), dtype=np.int32)
    out_cols = np.full((shards, nb), g, dtype=np.int32)  # sentinel col
    if nb_tot:
        starts = np.cumsum(counts) - counts
        slot = np.arange(nb_tot) - starts[u_shard]
        out_blocks[u_shard, slot] = tiles[:nb_tot]
        out_rows[u_shard, slot] = u_row
        out_cols[u_shard, slot] = u_col
    return ShardedBlocks(
        blocks=jnp.asarray(out_blocks),
        block_rows=jnp.asarray(out_rows),
        block_cols=jnp.asarray(out_cols),
    )


def sharded_blocks_nb(
    csr: CSRGraph, n_pad: int, shards: int, block: int = 128
) -> int:
    """The common per-shard tile count ``nb`` of
    ``sharded_blocks_from_csr`` — the one global quantity a per-shard
    block build needs (shards pad their tile lists to the max count)."""
    assert n_pad % (shards * block) == 0, (n_pad, shards, block)
    rows_local = n_pad // shards
    rb = rows_local // block
    g = n_pad // block
    src, dst = csr.edge_list()
    src = src.astype(np.int64)
    key = ((src // rows_local) * rb + (src % rows_local) // block) * g + (
        dst.astype(np.int64) // block
    )
    uniq = np.unique(key)
    if not len(uniq):
        return 1
    counts = np.bincount(uniq // (rb * g), minlength=shards)
    return max(int(counts.max()), 1)


def sharded_blocks_shard(
    csr: CSRGraph,
    n_pad: int,
    shards: int,
    nb: int,
    f_lo: int,
    f_hi: int,
    block: int = 128,
) -> ShardedBlocks:
    """Fine shards ``[f_lo, f_hi)`` of the wholesale
    ``sharded_blocks_from_csr`` structure (leading axis ``f_hi - f_lo``),
    built from only those shards' edges. ``nb`` is the global common tile
    count (``sharded_blocks_nb``). Host numpy leaves. Bitwise-identical to
    the wholesale build's slices: a shard's edges are a contiguous CSR
    row-range slice, and ``np.unique`` over its keys reproduces the global
    sorted key order restricted to the shard (the shard id is the key's
    leading factor)."""
    rows_local = n_pad // shards
    rb = rows_local // block
    g = n_pad // block
    n = csr.n_nodes
    span = f_hi - f_lo
    lo = min(f_lo * rows_local, n)
    hi = min(f_hi * rows_local, n)
    e_lo, e_hi = int(csr.indptr[lo]), int(csr.indptr[hi])
    out_blocks = np.zeros((span, nb, block, block), np.int8)
    out_rows = np.zeros((span, nb), np.int32)
    out_cols = np.full((span, nb), g, np.int32)  # sentinel col
    if e_hi > e_lo:
        pos = np.arange(e_lo, e_hi, dtype=np.int64)
        src = np.searchsorted(csr.indptr, pos, side="right") - 1
        dst = csr.indices[e_lo:e_hi].astype(np.int64)
        shard = src // rows_local
        key = (shard * rb + (src % rows_local) // block) * g + dst // block
        uniq, inv = np.unique(key, return_inverse=True)
        tiles = np.zeros((len(uniq), block, block), np.int8)
        tiles[inv, src % block, dst % block] = 1
        u_shard = (uniq // (rb * g)).astype(np.int64) - f_lo
        counts = np.bincount(u_shard, minlength=span)
        starts = np.cumsum(counts) - counts
        slot = np.arange(len(uniq)) - starts[u_shard]
        out_blocks[u_shard, slot] = tiles
        out_rows[u_shard, slot] = ((uniq // g) % rb).astype(np.int32)
        out_cols[u_shard, slot] = (uniq % g).astype(np.int32)
    return ShardedBlocks(
        blocks=out_blocks, block_rows=out_rows, block_cols=out_cols
    )


def blocks_from_csr(csr: CSRGraph, block: int = 128) -> BlockAdjacency:
    """Build the block-sparse adjacency (host-side)."""
    n = csr.n_nodes
    g = -(-n // block)
    src, dst = csr.edge_list()
    br, bc = src // block, dst // block
    key = br.astype(np.int64) * g + bc
    uniq, inv = np.unique(key, return_inverse=True)
    nb = len(uniq)
    blocks = np.zeros((max(nb, 1), block, block), dtype=np.int8)
    lr = src % block
    lc = dst % block
    blocks[inv, lr, lc] = 1
    urows = (uniq // g).astype(np.int32)
    ucols = (uniq % g).astype(np.int32)
    row_ptr = np.zeros(g + 1, dtype=np.int32)
    np.add.at(row_ptr, urows + 1, 1)
    row_ptr = np.cumsum(row_ptr).astype(np.int32)
    if nb == 0:
        urows = np.zeros(1, dtype=np.int32)
        ucols = np.zeros(1, dtype=np.int32)
    return BlockAdjacency(
        blocks=jnp.asarray(blocks),
        block_rows=jnp.asarray(urows),
        block_cols=jnp.asarray(ucols),
        row_ptr=jnp.asarray(row_ptr),
    )
