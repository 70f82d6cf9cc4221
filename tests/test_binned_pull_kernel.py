"""Fused binned-pull Pallas kernel corpus (ISSUE 7 acceptance).

Three layers of parity, all bit-exact:

- kernel level: ``kernels.binned_pull.ops.binned_pull`` (Pallas, interpret
  auto-detected on CPU) vs its pure-jnp oracle (``use_ref=True``) across
  all five kernel ops, with and without visited-suppression, on ER /
  power-law / heavy-tail-hub / zero-in-degree / edgeless fixtures;
- engine level: ``pull_binned_fused`` vs ``pull_binned`` through
  ``run_recursive_query`` — final states AND iteration counts — for every
  applicable edge compute, dense and lanes, replicated and sharded state
  layouts (sharded compiles every backend's scan program twice: slow lane);
- structure level (proptest): the pack's slab-descriptor grid covers every
  nonzero-in-degree row in exactly one compute tile, zero-in-degree rows in
  none, and the padded permutation pair stays a bijection on live rows
  (``perm_pad[inv_pad[r]] == r``, pad positions all-sentinel).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from proptest import given, st_ints, st_sampled, st_seeds
from oracle import bfs_levels

from repro.core import build_operands, policy_ntks, policy_ntkms
from repro.core.dispatcher import run_recursive_query
from repro.graph.csr import CSRGraph, csr_from_edges, truncate_csr
from repro.graph.generators import erdos_renyi, powerlaw
from repro.kernels.binned_pull.binned_pull import LANE_OPS, OPS
from repro.kernels.binned_pull.ops import binned_pull, pack_tile_map
from repro.launch.mesh import make_mesh

from test_extend import heavy_tail_csr


def mesh11():
    return make_mesh((1, 1), ("data", "model"))


def star_csr(n: int) -> CSRGraph:
    """Node 0 fans out to every live node: the root has in-degree 0 and the
    trailing 8 nodes are fully isolated — both land in the zero-width
    slab."""
    dsts = np.arange(1, n - 8)
    return csr_from_edges(n, np.zeros_like(dsts), dsts)


def fixture(kind: str, seed: int = 0, n: int = 96) -> CSRGraph:
    if kind == "er":
        return erdos_renyi(n, 5.0, seed=seed)
    if kind == "pl":
        return powerlaw(n, 4.0, seed=seed)
    if kind == "hub":
        return heavy_tail_csr(n, seed=seed)
    if kind == "star":
        return star_csr(n)
    assert kind == "edgeless", kind
    return truncate_csr(erdos_renyi(n, 3.0, seed=seed), 0)


def weighted(csr: CSRGraph, seed: int) -> CSRGraph:
    rng = np.random.default_rng(seed)
    return CSRGraph(
        indptr=csr.indptr,
        indices=csr.indices,
        weights=rng.uniform(0.1, 2.0, csr.n_edges).astype(np.float32),
    )


def kernel_inputs(op: str, n_pad: int, rows_local: int, seed: int,
                  lanes: int = 4):
    """Random mid-traversal tensors: a ~30% frontier, a ~40% visited set,
    finite distances on the frontier only (the min_dist neutral elsewhere)."""
    rng = np.random.default_rng(seed)
    shape = (n_pad, lanes) if op in LANE_OPS else (n_pad,)
    mask = (rng.random(shape) < 0.3).astype(np.uint8)
    if op == "min_dist":
        gsrc = jnp.asarray(
            np.where(rng.random(n_pad) < 0.3,
                     rng.uniform(0.0, 9.0, n_pad), np.inf).astype(np.float32)
        )
        return gsrc, None  # min_dist has no suppression value
    vshape = (rows_local, lanes) if op in LANE_OPS else (rows_local,)
    vloc = jnp.asarray((rng.random(vshape) < 0.4))
    return jnp.asarray(mask), vloc


@pytest.mark.parametrize("kind", ["er", "pl", "hub", "star", "edgeless"])
def test_kernel_vs_ref_parity_all_ops(kind):
    """The Pallas kernel against the pure-jnp oracle, every op, with and
    without the visited-suppression operand, on every fixture class —
    including the edgeless graph whose pack has zero compute tiles."""
    csr = weighted(fixture(kind, seed=3), seed=4)
    ops, n_pad = build_operands(csr, "pull_binned_fused")
    pack = ops.rev_binned_pack
    for op in OPS:
        gsrc, vloc = kernel_inputs(op, n_pad, pack.rows_local, seed=11)
        for v in ([None, vloc] if vloc is not None else [None]):
            got = binned_pull(pack, gsrc, v, op=op)
            exp = binned_pull(pack, gsrc, v, op=op, use_ref=True)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(exp), err_msg=f"{kind}/{op}"
            )


@pytest.mark.parametrize("entry", ["kernel", "serve"])
def test_fused_refused_on_tpu(monkeypatch, entry):
    """On a TPU the fused kernel neither interprets nor falls back to its
    jnp reference: compiling it, or serving a backend built on it, raises
    the named error (Mosaic refuses the kernel for v5e)."""
    from repro.kernels.binned_pull.binned_pull import FusedPullNotLowerable
    from repro.launch import serve

    ops, n_pad = build_operands(fixture("er", seed=3), "pull_binned_fused")
    pack = ops.rev_binned_pack
    gsrc, vloc = kernel_inputs("reach", n_pad, pack.rows_local, seed=11)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(FusedPullNotLowerable):
        if entry == "kernel":
            binned_pull(pack, gsrc, vloc, op="reach", interpret=False)
        else:
            serve.main(["--backend", "dopt_fused"])


# every edge compute the binned-pull scan applies to, with its morsel policy
ENGINE_CASES = [
    ("sp_lengths", policy_ntks),
    ("sp_parents", policy_ntks),
    ("reachability", policy_ntks),
    ("bellman_ford", policy_ntks),
    ("msbfs_lengths", policy_ntkms),
    ("msbfs_parents", policy_ntkms),
]


@pytest.mark.parametrize(
    "state_layout",
    ["replicated", pytest.param("sharded", marks=pytest.mark.slow)],
)
def test_engine_fused_parity_states_and_iterations(state_layout):
    """run_recursive_query under pull_binned_fused must match pull_binned
    bit-for-bit — final states AND per-morsel iteration counts (the fused
    kernel changes the scan, never the fixpoint trajectory) — and the BFS
    levels must match the numpy oracle."""
    mesh = mesh11()
    csr = weighted(powerlaw(150, 5.0, seed=3), seed=8)
    srcs = np.array([0, 11, 42], np.int32)
    for ec, pol in ENGINE_CASES:
        ref = run_recursive_query(
            mesh, csr, srcs, pol(), ec,
            state_layout=state_layout, extend="pull_binned",
        )
        got = run_recursive_query(
            mesh, csr, srcs, pol(), ec,
            state_layout=state_layout, extend="pull_binned_fused",
        )
        for fa, fb in zip(
            jax.tree_util.tree_leaves(ref.state),
            jax.tree_util.tree_leaves(got.state),
        ):
            np.testing.assert_array_equal(
                np.asarray(fa), np.asarray(fb), err_msg=ec
            )
        np.testing.assert_array_equal(
            np.asarray(ref.iterations), np.asarray(got.iterations),
            err_msg=f"{ec}: iteration counts diverged",
        )
    exp = np.stack([bfs_levels(csr, [s]) for s in srcs])
    res = run_recursive_query(
        mesh, csr, srcs, policy_ntks(), "sp_lengths",
        state_layout=state_layout, extend="pull_binned_fused",
    )
    np.testing.assert_array_equal(
        np.asarray(res.state.levels)[: len(srcs), : csr.n_nodes], exp
    )


def test_engine_fused_heavy_tail_and_star():
    """The fixtures that punish the padded reverse slab — a hub with
    in-degree ≈ n and a zero-in-degree root with an isolated tail — through
    the full engine path."""
    mesh = mesh11()
    for csr, srcs in (
        (heavy_tail_csr(120, seed=7), np.array([1, 9], np.int32)),
        (star_csr(72), np.array([0], np.int32)),
    ):
        ref = run_recursive_query(
            mesh, csr, srcs, policy_ntks(), "sp_lengths",
            extend="pull_binned",
        )
        got = run_recursive_query(
            mesh, csr, srcs, policy_ntks(), "sp_lengths",
            extend="pull_binned_fused",
        )
        np.testing.assert_array_equal(
            np.asarray(ref.state.levels), np.asarray(got.state.levels)
        )
        np.testing.assert_array_equal(
            np.asarray(ref.iterations), np.asarray(got.iterations)
        )


def test_fused_edgeless_zero_width_slab_engine():
    """An edgeless graph packs to a single zero-width slab ([n, 0]
    capacity): the fused engine must converge with zero compute tiles and
    spread nothing."""
    from repro.core.ife import run_ife

    eff = truncate_csr(erdos_renyi(64, 3.0, seed=2), 0)
    ops, n_pad = build_operands(eff, "pull_binned_fused")
    assert ops.rev_binned_pack.capacity_slots == 0
    assert len(ops.rev_binned_pack.slabs) == 0
    for ec in ("sp_lengths", "sp_parents", "bellman_ford"):
        res = run_ife(ops, jnp.array([3]), ec, extend="pull_binned_fused")
        if hasattr(res.state, "levels"):
            lv = np.asarray(res.state.levels)[:64].reshape(64, -1)[:, 0]
            assert lv[3] == 0 and (np.delete(lv, 3) != 0).all(), ec
        else:
            d = np.asarray(res.state.dist)[:64]
            assert d[3] == 0 and np.isinf(np.delete(d, 3)).all(), ec


@given(st_seeds(), st_ints(40, 160), st_sampled(["er", "pl", "hub", "star"]),
       cases=6)
def test_prop_pack_covers_every_row_exactly_once(seed, n, kind):
    """Coverage contract of the scalar-prefetched slab descriptors: the
    compute grid visits every nonzero-in-degree row in exactly one tile,
    zero-in-degree rows in none, and the padded perm/inverse pair is a
    bijection on live rows with all-sentinel pad positions."""
    csr = fixture(kind, seed=seed, n=max(n, 48))
    ops, n_pad = build_operands(csr, "pull_binned_fused")
    pack = ops.rev_binned_pack
    tile_of_row, tile_slots = pack_tile_map(pack)

    rev_deg = np.zeros(n_pad, np.int64)
    rev_deg[: csr.n_nodes] = np.asarray(csr.reverse().degrees)
    assert tile_of_row.shape == (pack.rows_local,) == (n_pad,)
    # exactly-once: live rows get one compute tile, dead rows get none
    assert (tile_of_row[rev_deg > 0] >= 0).all(), kind
    assert (tile_of_row[rev_deg == 0] == -1).all(), kind
    assert tile_slots.shape[0] == 0 or tile_of_row.max() < tile_slots.shape[0]
    assert (tile_slots > 0).all()
    # a tile's slot cost is its rows x its slab width; each row it covers
    # has true in-degree <= that width (binning invariant)
    # perm/inverse bijection on live rows
    inv = np.asarray(pack.inv_pad[0], np.int64)
    perm = np.asarray(pack.perm_pad[0], np.int64)
    np.testing.assert_array_equal(perm[inv], np.arange(pack.rows_local))
    assert np.unique(inv).size == pack.rows_local  # injective => once each
    pad_pos = np.ones(perm.size, bool)
    pad_pos[inv] = False
    assert (perm[pad_pos] == pack.rows_local).all()  # sentinel pad rows
    # the padded capacity never undercuts the source structure's
    assert pack.capacity_slots >= ops.rev_binned.capacity_slots
