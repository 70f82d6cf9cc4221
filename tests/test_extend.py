"""Extension-backend parity corpus (ISSUE 2 + ISSUE 3 acceptance).

ell_push / ell_pull / pull_binned / pull_binned_fused / block_mxu and the
direction-optimized switch flavors must produce bit-identical final states
vs the numpy oracle
and vs each other, across ER and power-law graphs — including a pathological
heavy-tail fixture (one node with in-degree ≈ n) and graphs with
zero-in-degree / isolated nodes — all dense edge computes, the msbfs lane
computes, and both engine state layouts; plus operand-construction,
degree-binned slab pack/unpack + permutation-inverse, and frontier
pack/unpack invariants.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from proptest import given, st_ints, st_sampled, st_seeds
from oracle import bfs_levels

from repro.graph.csr import (
    CSRGraph,
    binned_rev_csr,
    csr_from_edges,
    ell_from_csr,
    truncate_csr,
)
from repro.graph.generators import erdos_renyi, powerlaw
from repro.core import (
    build_operands,
    policy_ntks,
    policy_ntkms,
    recommend_backend,
    run_recursive_query,
)
from repro.core.extend import ExtendSpec, GraphOperands, as_spec
from repro.core.ife import run_ife
from repro.launch.mesh import make_mesh

BACKENDS = ["ell_push", "ell_pull", "pull_binned", "pull_binned_fused",
            "block_mxu", "dopt", "dopt_ell"]
DENSE_ECS = ["sp_lengths", "sp_parents", "bellman_ford", "reachability"]


def mesh11():
    return make_mesh((1, 1), ("data", "model"))


def full_operands(csr, block=128):
    """One bundle carrying every operand at a common pad so final states
    are comparable bitwise across backends (engines strip what they don't
    scan)."""
    pull, n1 = build_operands(csr, "dopt_ell", block=block)
    binned, n3 = build_operands(csr, "pull_binned_fused", block=block)
    blk, n2 = build_operands(
        csr, ExtendSpec(backend="block_mxu", block=block), block=block
    )
    assert n1 == n2 == n3
    return (
        GraphOperands(
            fwd=pull.fwd,
            rev=pull.rev,
            rev_binned=binned.rev_binned,
            rev_binned_pack=binned.rev_binned_pack,
            blocks=blk.blocks,
        ),
        n1,
    )


def heavy_tail_csr(n: int, seed: int = 0) -> CSRGraph:
    """Pathological skew fixture: a hub with in-degree ≈ n (every other
    node points at it), a thin ring so BFS needs several hops, the hub
    fanning back out to a few nodes, and trailing isolated nodes with
    zero in- AND out-degree."""
    rng = np.random.default_rng(seed)
    live = n - max(n // 8, 1)  # the tail stays fully isolated
    hub = 0
    srcs = []
    dsts = []
    for v in range(1, live):
        srcs.append(v)  # v -> hub: rev degree of hub ≈ n
        dsts.append(hub)
        srcs.append(v)  # ring: v -> v+1
        dsts.append(1 + (v % (live - 1)))
    out_fan = rng.choice(np.arange(1, live), size=min(4, live - 1),
                         replace=False)
    for d in out_fan:
        srcs.append(hub)
        dsts.append(int(d))
    return csr_from_edges(n, np.asarray(srcs), np.asarray(dsts))


def assert_states_equal(a, b, msg=""):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb), msg
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=msg)


@given(st_seeds(), st_ints(48, 160), st_sampled(["er", "pl"]), cases=4)
def test_prop_backend_parity_all_dense_edge_computes(seed, n, kind):
    rng = np.random.default_rng(seed)
    csr = (
        erdos_renyi(n, 5.0, seed=seed)
        if kind == "er"
        else powerlaw(n, 4.0, seed=seed)
    )
    csr_w = CSRGraph(
        indptr=csr.indptr,
        indices=csr.indices,
        weights=rng.uniform(0.1, 2.0, csr.n_edges).astype(np.float32),
    )
    ops, n_pad = full_operands(csr)
    ops_w, _ = full_operands(csr_w)
    srcs = jnp.asarray(
        rng.integers(0, csr.n_nodes, size=2).astype(np.int32)
    )
    for ec in DENSE_ECS:
        use = ops_w if ec == "bellman_ford" else ops
        ref = run_ife(use, srcs, ec, extend="ell_push")
        if ec in ("sp_lengths",):
            exp = bfs_levels(csr, np.asarray(srcs))
            np.testing.assert_array_equal(
                np.asarray(ref.state.levels)[: csr.n_nodes], exp
            )
        for be in BACKENDS[1:]:
            got = run_ife(use, srcs, ec, extend=be)
            assert_states_equal(ref.state, got.state, f"{ec}/{be}")


@given(st_seeds(), st_ints(64, 200), cases=3)
def test_prop_backend_parity_msbfs(seed, n):
    csr = powerlaw(n, 4.0, seed=seed)
    ops, n_pad = full_operands(csr)
    rng = np.random.default_rng(seed)
    srcs = jnp.asarray(rng.integers(0, n, size=8).astype(np.int32))
    for ec in ("msbfs_lengths", "msbfs_parents"):
        ref = run_ife(ops, srcs, ec, extend="ell_push")
        for be in BACKENDS[1:]:
            got = run_ife(ops, srcs, ec, extend=be)
            assert_states_equal(ref.state, got.state, f"{ec}/{be}")


# ---------------------------------------------------------------------------
# Heavy-tail + degenerate-graph corpus (ISSUE 3): the fixtures that punish
# the padded reverse slab are exactly where binned pull must stay
# bit-identical.
# ---------------------------------------------------------------------------


def test_heavy_tail_hub_parity_all_edge_computes():
    """One node with in-degree ≈ n: the padded reverse ELL pays n·max ≈ n²
    here; the binned layout isolates the hub in its own slab. Parity must
    hold for every edge compute, dense and lanes."""
    csr = heavy_tail_csr(96, seed=5)
    rev_deg = csr.reverse().degrees
    assert rev_deg.max() >= 0.8 * (csr.n_nodes - csr.n_nodes // 8)
    rng = np.random.default_rng(1)
    csr_w = CSRGraph(
        indptr=csr.indptr,
        indices=csr.indices,
        weights=rng.uniform(0.1, 2.0, csr.n_edges).astype(np.float32),
    )
    ops, _ = full_operands(csr)
    ops_w, _ = full_operands(csr_w)
    srcs = jnp.array([1, 7], jnp.int32)
    for ec in DENSE_ECS + ["msbfs_lengths", "msbfs_parents"]:
        use = ops_w if ec == "bellman_ford" else ops
        ref = run_ife(use, srcs, ec, extend="ell_push")
        for be in BACKENDS[1:]:
            got = run_ife(use, srcs, ec, extend=be)
            assert_states_equal(ref.state, got.state, f"{ec}/{be}")
    # the hub's slab really is its own bucket: widths strictly separate
    # the hub from the degree-1 mass
    bn = ops.rev_binned
    w = bn.row_widths()[0]
    assert w.max() >= int(rev_deg.max())
    assert (w[w > 0].min()) <= 2


def test_zero_in_degree_and_isolated_nodes_parity():
    """Zero-in-degree rows (sources of a DAG) and fully isolated nodes land
    in the zero-width slab and must neither contribute nor corrupt
    placement."""
    # star out of node 0 only: every other node has in-degree <= 1, node 0
    # has in-degree 0; nodes [n-8, n) are fully isolated
    n = 72
    srcs_e = np.arange(1, n - 8)
    csr = csr_from_edges(n, np.zeros_like(srcs_e), srcs_e)
    ops, n_pad = full_operands(csr)
    bn = ops.rev_binned
    w = bn.row_widths()[0]
    assert w[0] == 0  # root: zero in-degree => zero-width slab
    assert (w[n - 8 : n] == 0).all()  # isolated tail
    for ec in ("sp_lengths", "sp_parents", "reachability"):
        ref = run_ife(ops, jnp.array([0]), ec, extend="ell_push")
        for be in BACKENDS[1:]:
            got = run_ife(ops, jnp.array([0]), ec, extend=be)
            assert_states_equal(ref.state, got.state, f"{ec}/{be}")


def test_truncation_emptied_rows_zero_width_slab():
    """Regression (latent ell_from_csr/truncate_csr edge case the binning
    exposed): a degree cap of 0 — or an edgeless graph — must produce a
    genuine zero-width ELL/slab, not a 1-wide (8-padded) row whose slots
    every backend would scan forever. The historical ``max_deg or 1``
    coercion silently turned an explicit 0 into width 8."""
    csr = erdos_renyi(64, 3.0, seed=2)
    # truncate away every edge, then convert: zero-width, zero-degree
    eff = truncate_csr(csr, 0)
    assert eff.n_edges == 0
    g = ell_from_csr(eff)
    assert g.indices.shape == (64, 0)
    assert int(np.asarray(g.degrees).sum()) == 0
    # explicit max_deg=0 on a graph WITH edges: same contract
    g0 = ell_from_csr(csr, max_deg=0)
    assert g0.indices.shape == (64, 0)
    # an edgeless graph's binned reverse: single zero-width slab, zero
    # capacity — scanning it costs nothing
    bn = binned_rev_csr(eff, 64, shards=1)
    assert bn.widths == (0,)
    assert bn.capacity_slots == 0
    # and the full pipeline still converges under EVERY backend flavor
    # that can scan a zero-width layout (sources never spread) — including
    # the min-reduction edge computes, whose jnp reductions have no
    # identity over a size-0 axis and need explicit width-0 guards
    for be in ("ell_push", "ell_pull", "pull_binned", "pull_binned_fused",
               "dopt", "dopt_ell"):
        ops, n_pad = build_operands(eff, be)
        for ec in ("sp_lengths", "sp_parents", "bellman_ford",
                   "msbfs_parents"):
            res = run_ife(ops, jnp.array([3]), ec, extend=be)
            if hasattr(res.state, "levels"):
                lv = np.asarray(res.state.levels)[:64]
                lv = lv.reshape(64, -1)[:, 0].astype(np.int64)
                assert lv[3] == 0, (be, ec)  # the source itself
                assert (np.delete(lv, 3) != 0).all(), (be, ec)  # nobody else
            else:  # bellman_ford: only the source is at finite distance
                d = np.asarray(res.state.dist)[:64]
                assert d[3] == 0 and np.isinf(np.delete(d, 3)).all(), be
    # nonzero cap above the max degree keeps the historical pad-to-8 width
    g8 = ell_from_csr(csr, max_deg=3)
    assert g8.indices.shape[1] == 8


@given(st_seeds(), st_ints(24, 140), st_sampled(["er", "pl", "hub"]),
       cases=6)
def test_prop_binned_slab_pack_unpack_roundtrip(seed, n, kind):
    """Slab pack/unpack + permutation-inverse property: for random graphs
    (including the heavy-tail hub fixture), unpacking the binned slabs
    through the permutation recovers exactly the reverse adjacency of the
    truncated graph, perm/inv are mutually inverse over real rows, widths
    cover the true in-degrees, and total capacity respects the 1.1x
    overhead contract."""
    rng = np.random.default_rng(seed)
    if kind == "er":
        csr = erdos_renyi(n, 4.0, seed=seed)
    elif kind == "pl":
        csr = powerlaw(n, 4.0, seed=seed)
    else:
        csr = heavy_tail_csr(n, seed=seed)
    cap = None if seed % 2 else 4
    eff = truncate_csr(csr, cap)
    shards = 1 if seed % 3 else 2
    n_pad = -(-n // (shards * 8)) * (shards * 8)
    bn = binned_rev_csr(eff, n_pad, shards=shards)
    rows_local = n_pad // shards
    rev = eff.reverse()
    rev_deg = np.zeros(n_pad, np.int64)
    rev_deg[:n] = rev.degrees

    perm = np.asarray(bn.perm)
    inv = np.asarray(bn.inv)
    widths = bn.row_widths()
    # perm/inv inverse bijection over real rows, pad positions inert
    for k in range(shards):
        np.testing.assert_array_equal(
            perm[k][inv[k]], np.arange(rows_local)
        )
        pad_pos = np.setdiff1d(np.arange(perm.shape[1]), inv[k])
        assert (perm[k][pad_pos] == rows_local).all()
    # widths cover degrees within the overhead contract
    flat_w = widths.reshape(-1)
    assert (flat_w >= rev_deg).all()
    assert flat_w.sum() <= 1.1 * rev_deg.sum() + 1e-9
    assert bn.capacity_slots * shards >= flat_w.sum()  # count padding only adds

    # unpack: concatenated slab rows, un-permuted, reproduce the reverse
    # neighbor multisets exactly
    for k in range(shards):
        per_pos = []  # binned position -> that row's slab slots
        for s in bn.slabs:
            for r in range(s.shape[1]):
                per_pos.append(np.asarray(s[k, r]))
        for r in range(rows_local):
            g = k * rows_local + r
            got = per_pos[inv[k, r]]
            got = np.sort(got[got < n_pad])
            exp = np.sort(rev.indices[rev.indptr[g]:rev.indptr[g + 1]]) if (
                g < n
            ) else np.zeros(0, np.int32)
            np.testing.assert_array_equal(got, exp, err_msg=f"row {g}")


@pytest.mark.parametrize("state_layout", ["replicated", "sharded"])
def test_engine_backend_parity_both_layouts(state_layout):
    csr = powerlaw(150, 5.0, seed=3)
    n = csr.n_nodes
    mesh = mesh11()
    srcs = np.array([0, 11, 42], np.int32)
    expected = np.stack([bfs_levels(csr, [s]) for s in srcs])
    for be in BACKENDS:
        res = run_recursive_query(
            mesh, csr, srcs, policy_ntks(), "sp_lengths",
            state_layout=state_layout, extend=be,
        )
        got = np.asarray(res.state.levels)[: len(srcs), :n]
        np.testing.assert_array_equal(got, expected, err_msg=be)


@pytest.mark.parametrize(
    "state_layout",
    ["replicated", pytest.param("sharded", marks=pytest.mark.slow)],
)
def test_engine_heavy_tail_parity_both_layouts(state_layout):
    """The heavy-tail hub through the full shard_map engine path (the
    sharded heavy-tail case is the expensive one: every backend compiles
    its own scan program — fast lane keeps replicated only)."""
    csr = heavy_tail_csr(180, seed=11)
    n = csr.n_nodes
    mesh = mesh11()
    srcs = np.array([1, 9, 33], np.int32)
    expected = np.stack([bfs_levels(csr, [s]) for s in srcs])
    for be in BACKENDS:
        res = run_recursive_query(
            mesh, csr, srcs, policy_ntks(), "sp_lengths",
            state_layout=state_layout, extend=be,
        )
        got = np.asarray(res.state.levels)[: len(srcs), :n]
        np.testing.assert_array_equal(got, expected, err_msg=be)


def test_engine_backend_parity_lane_morsels():
    csr = erdos_renyi(140, 5.0, seed=9)
    n = csr.n_nodes
    mesh = mesh11()
    srcs = np.array([1, 7, 99], np.int32)
    ref = run_recursive_query(
        mesh, csr, srcs, policy_ntkms(), "msbfs_parents", extend="ell_push"
    )
    for be in BACKENDS[1:]:
        got = run_recursive_query(
            mesh, csr, srcs, policy_ntkms(), "msbfs_parents", extend=be
        )
        for fa, fb in zip(ref.state, got.state):
            np.testing.assert_array_equal(
                np.asarray(fa)[:, :n], np.asarray(fb)[:, :n], err_msg=be
            )


def test_scheduler_backend_selection_and_cache_keys():
    from repro.runtime.scheduler import AdaptiveScheduler

    csr = powerlaw(200, 5.0, seed=11)
    n = csr.n_nodes
    sched = AdaptiveScheduler(mesh11(), csr, max_iters=64, phase1_iters=2)
    srcs = np.array([0, 17, 60], np.int32)
    ref = sched.query(srcs)  # scheduler default IS backend="recommend"
    n_engines = len(sched.cache)
    for be in ["ell_push", "ell_pull", "pull_binned", "pull_binned_fused",
               "block_mxu", "dopt", "recommend"]:
        out = sched.query(srcs, backend=be)
        np.testing.assert_array_equal(
            np.asarray(ref.result.state.levels)[:, :n],
            np.asarray(out.result.state.levels)[:, :n],
            err_msg=be,
        )
    # each distinct backend compiled its own engines under its own key ...
    assert len(sched.cache) > n_engines
    # ... and re-serving a backend is pure cache hits
    h0, m0 = sched.cache.hits, sched.cache.misses
    sched.query(srcs, backend="dopt")
    assert sched.cache.hits > h0 and sched.cache.misses == m0


def test_max_deg_truncation_consistent_across_backends():
    """Reverse/binned/block operands must be derived from the truncated
    forward graph, or pull would scan edges push cannot see."""
    csr = powerlaw(120, 6.0, seed=13)
    srcs = jnp.array([3])
    cap = 4
    ops_p, _ = build_operands(csr, "dopt_ell", max_deg=cap, block=128)
    ops_b, _ = build_operands(
        csr, "pull_binned_fused", max_deg=cap, block=128
    )
    blk_t, _ = build_operands(
        csr, ExtendSpec(backend="block_mxu"), max_deg=cap, block=128
    )
    ops_t = GraphOperands(
        fwd=ops_p.fwd, rev=ops_p.rev, rev_binned=ops_b.rev_binned,
        rev_binned_pack=ops_b.rev_binned_pack, blocks=blk_t.blocks,
    )
    ref = run_ife(ops_t, srcs, "sp_lengths", extend="ell_push")
    for be in BACKENDS[1:]:
        got = run_ife(ops_t, srcs, "sp_lengths", extend=be)
        assert_states_equal(ref.state, got.state, be)
    # and the effective graph really is capped
    eff = truncate_csr(csr, cap)
    assert int(eff.degrees.max()) <= cap
    assert eff.n_edges == int(np.minimum(csr.degrees, cap).sum())


@given(st_seeds(), st_ints(16, 120), st_ints(1, 9), cases=6)
def test_prop_ell_from_csr_vectorized_matches_loop(seed, n, deg):
    """The numpy-index ELL builder == the straightforward per-row loop,
    including weights and degree truncation."""
    rng = np.random.default_rng(seed)
    csr = erdos_renyi(n, float(deg), seed=seed)
    csr = CSRGraph(
        indptr=csr.indptr,
        indices=csr.indices,
        weights=rng.uniform(0.1, 1.0, csr.n_edges).astype(np.float32),
    )
    cap = None if seed % 2 else max(1, deg // 2)
    g = ell_from_csr(csr, max_deg=cap)
    # reference: the original interpreted loop
    degs = csr.degrees.astype(np.int32)
    width = g.indices.shape[1]
    ref_idx = np.full((n, width), n, np.int32)
    ref_w = np.zeros((n, width), np.float32)
    for v in range(n):
        d = min(int(degs[v]), width)
        lo = csr.indptr[v]
        ref_idx[v, :d] = csr.indices[lo : lo + d]
        ref_w[v, :d] = csr.weights[lo : lo + d]
    np.testing.assert_array_equal(np.asarray(g.indices), ref_idx)
    np.testing.assert_array_equal(np.asarray(g.weights), ref_w)
    np.testing.assert_array_equal(
        np.asarray(g.degrees), np.minimum(degs, width)
    )


@given(st_seeds(), st_ints(4, 64), cases=6)
def test_prop_pack_unpack_lanes_roundtrip(seed, n):
    from repro.core.frontier import LANES, pack_lanes, unpack_lanes

    rng = np.random.default_rng(seed)
    lanes = (rng.random((n, LANES)) < 0.3).astype(np.uint8)
    packed = pack_lanes(jnp.asarray(lanes))
    assert packed.shape == (n, LANES // 32) and packed.dtype == jnp.uint32
    back = unpack_lanes(packed)
    np.testing.assert_array_equal(np.asarray(back), lanes)
    repacked = pack_lanes(back)
    np.testing.assert_array_equal(np.asarray(repacked), np.asarray(packed))


def test_recommend_backend_rules():
    assert recommend_backend("bellman_ford", 300.0, n_nodes=1000) == "ell_push"
    assert (
        recommend_backend("msbfs_lengths", 300.0, n_nodes=1000, lanes=64)
        == "block_mxu"
    )
    # lane morsels on block-sparse (huge) graphs: stay direction-optimized
    # over the binned pull slabs (the post-binning default)
    assert (
        recommend_backend("msbfs_lengths", 8.0, n_nodes=10**7, lanes=64)
        == "dopt_binned"
    )
    assert recommend_backend("sp_lengths", 8.0, n_nodes=1000) == "dopt_binned"
    assert as_spec("dopt_binned").needs_binned
    assert not as_spec("dopt_binned").needs_rev


def test_block_operands_regroup_for_pad_shards():
    """prepare_graph(pad_shards=K) with K > the policy's own shard count
    must regroup the stacked block tiles (rebased local row-block ids) —
    the scheduler's shared-n_pad contract for the block backend."""
    from repro.core.dispatcher import (
        build_engine,
        pad_sources,
        prepare_graph,
    )

    csr = powerlaw(300, 5.0, seed=3)
    n = csr.n_nodes
    mesh = mesh11()
    spec = ExtendSpec(backend="block_mxu")
    pol = policy_ntks()
    g, n_pad = prepare_graph(csr, mesh, pol, pad_shards=4, extend=spec)
    assert n_pad % (4 * spec.block) == 0
    eng = build_engine(mesh, pol, "sp_lengths", n_pad, 64, extend=spec)
    srcs = np.array([0, 11, 42], np.int32)
    res = eng(g, jnp.asarray(pad_sources(srcs, 1, 1, n_pad)))
    expected = np.stack([bfs_levels(csr, [s]) for s in srcs])
    np.testing.assert_array_equal(
        np.asarray(res.state.levels)[:3, :n], expected
    )


def test_binned_operands_rebuild_for_pad_shards():
    """prepare_graph(pad_shards=K): binned slabs are re-binned at the
    policy's own shard count (per-shard binning can't just reshape) but on
    the SHARED n_pad — the scheduler's phase-1/phase-2 state-flow
    contract for the binned-pull backend."""
    from repro.core.dispatcher import (
        build_engine,
        pad_sources,
        prepare_graph,
    )

    csr = powerlaw(300, 5.0, seed=3)
    n = csr.n_nodes
    mesh = mesh11()
    spec = as_spec("pull_binned")
    pol = policy_ntks()
    g, n_pad = prepare_graph(csr, mesh, pol, pad_shards=4, extend=spec)
    assert n_pad % (4 * 32) == 0
    assert g.rev_binned is not None
    assert g.rev_binned.inv.shape == (1, n_pad)  # policy has 1 graph shard
    eng = build_engine(
        mesh, pol, "sp_lengths", n_pad, 64, extend=spec, operands=g
    )
    srcs = np.array([0, 11, 42], np.int32)
    res = eng(g, jnp.asarray(pad_sources(srcs, 1, 1, n_pad)))
    expected = np.stack([bfs_levels(csr, [s]) for s in srcs])
    np.testing.assert_array_equal(
        np.asarray(res.state.levels)[:3, :n], expected
    )


def test_extend_spec_validation_and_errors():
    with pytest.raises(ValueError):
        ExtendSpec(backend="nope")
    with pytest.raises(ValueError):
        ExtendSpec(direction="sometimes")
    with pytest.raises(ValueError):
        ExtendSpec(pull="bidirectional")
    with pytest.raises(ValueError):
        # auto IS the push/pull choice; pinning another backend with it
        # would otherwise be silently ignored
        ExtendSpec(backend="block_mxu", direction="auto")
    csr = erdos_renyi(64, 3.0, seed=1)
    ops, _ = build_operands(csr, "ell_push")
    with pytest.raises(ValueError):
        run_ife(ops, jnp.array([0]), "sp_lengths", extend="ell_pull")
    with pytest.raises(ValueError):
        run_ife(ops, jnp.array([0]), "sp_lengths", extend="pull_binned")
    with pytest.raises(ValueError):
        run_ife(ops, jnp.array([0]), "sp_lengths", extend="dopt")
    with pytest.raises(ValueError):
        run_ife(ops, jnp.array([0]), "sp_lengths", extend="block_mxu")


@pytest.mark.parametrize("shards", [1, 4])
def test_cost_probe_rates_one_shard_scan(shards):
    """The cost="measured" probe times what one device scans: operands
    stacked for several graph shards are probed on shard 0, so each
    backend's slot count is its per-shard full scan."""
    from repro.core.extend import BackendCostProbe

    csr = powerlaw(200, 5.0, seed=2)
    ops, n_pad = build_operands(
        csr, "dopt_binned", shards=shards, binned_shards=shards
    )
    rates = BackendCostProbe(reps=1).rates(ops, n_pad)
    assert set(rates) == {"ell_push", "pull_binned"}
    assert rates["ell_push"]["slots"] == ops.fwd.indices.size // shards
    assert rates["pull_binned"]["slots"] == ops.rev_binned.capacity_slots
    for r in rates.values():
        assert np.isfinite(r["ms_per_slot"]) and r["ms_per_slot"] > 0


def test_cost_probe_takes_operands_as_arguments():
    """The probe's jitted steps read the operands as arguments, not as
    captured constants: constants put the whole padded forward ELL into
    the program, which the compiler then holds and folds on the host."""
    import warnings

    from repro.core.extend import BackendCostProbe

    csr = powerlaw(200, 5.0, seed=2)
    ops, n_pad = build_operands(csr, "dopt_binned")
    was = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 1024)
    try:
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "error", message=".*constants were captured"
            )
            rates = BackendCostProbe(reps=1).rates(ops, n_pad)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", was)
    assert set(rates) == {"ell_push", "pull_binned"}
