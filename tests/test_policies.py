"""Policy dispatcher correctness on a 1x1 mesh (degenerate but full code path)
plus policy-equivalence invariants and the policy-layer oracles: direction-
threshold fitting recovers known crossovers, and ``recommend_backend`` is a
deterministic, total function (it never names a backend whose operands the
given bundle can't supply). Real multi-device parity is covered by
test_multidev.py (subprocess with forced host device count)."""
import itertools

import numpy as np
import jax
import jax.numpy as jnp

from oracle import bfs_levels
from proptest import given, st_ints, st_seeds

from repro.graph.generators import erdos_renyi, powerlaw
from repro.core import (
    BudgetModel,
    DirectionThresholds,
    as_spec,
    build_operands,
    count_budget_mispredicts,
    degree_bucket,
    fit_direction_thresholds,
    pow2ceil,
    run_recursive_query,
    policy_1t1s,
    policy_nt1s,
    policy_ntks,
    policy_ntkms,
    recommend_backend,
    recommend_policy,
    recommend_k,
)
from repro.core.ife import run_ife
from repro.launch.mesh import make_mesh


def mesh11():
    return make_mesh((1, 1), ("data", "model"))


def _levels(res):
    return np.asarray(res.state.levels)


def test_all_policies_agree_with_oracle():
    csr = erdos_renyi(96, 4.0, seed=4)
    mesh = mesh11()
    sources = np.array([0, 7, 23], dtype=np.int32)
    expected = np.stack([bfs_levels(csr, [s]) for s in sources])

    for pol in (policy_1t1s(), policy_nt1s(), policy_ntks()):
        res = run_recursive_query(mesh, csr, sources, pol, "sp_lengths")
        got = _levels(res)[: len(sources), : csr.n_nodes]
        np.testing.assert_array_equal(got, expected, err_msg=pol.name)

    # nTkMS: one 64-lane morsel, first 3 lanes are our sources
    res = run_recursive_query(
        mesh, csr, sources, policy_ntkms(), "msbfs_lengths"
    )
    lanes = _levels(res)  # [n_morsels, n_pad, 64] uint8
    got = np.transpose(lanes[0, : csr.n_nodes, :3], (1, 0)).astype(np.int32)
    got[got == 255] = -1
    np.testing.assert_array_equal(got, expected)


@given(st_seeds(), st_ints(32, 160), st_ints(1, 12))
def test_prop_policy_equivalence(seed, n, n_sources):
    csr = powerlaw(n, 4.0, seed=seed)
    mesh = mesh11()
    rng = np.random.default_rng(seed)
    sources = rng.integers(0, csr.n_nodes, size=n_sources).astype(np.int32)
    ref = None
    for pol in (policy_1t1s(), policy_ntks(or_impl="ring")):
        res = run_recursive_query(mesh, csr, sources, pol, "sp_lengths")
        got = _levels(res)[: len(sources), : csr.n_nodes]
        if ref is None:
            ref = got
        else:
            np.testing.assert_array_equal(got, ref)


def test_ntkms_empty_lanes_are_inert():
    csr = erdos_renyi(80, 3.0, seed=6)
    mesh = mesh11()
    res = run_recursive_query(
        mesh, csr, np.array([5], dtype=np.int32), policy_ntkms(), "msbfs_lengths"
    )
    lanes = _levels(res)[0]  # [n_pad, 64]
    # lanes 1..63 were padded -> never reach anything
    assert (lanes[:, 1:] == 255).all()
    got = lanes[: csr.n_nodes, 0].astype(np.int32)
    got[got == 255] = -1
    np.testing.assert_array_equal(got, bfs_levels(csr, [5]))


def test_parents_policy_invariant():
    from repro.core.ife import validate_parents

    csr = erdos_renyi(120, 4.0, seed=8)
    mesh = mesh11()
    src = np.array([11], dtype=np.int32)
    for pol in (policy_1t1s(), policy_ntks()):
        res = run_recursive_query(mesh, csr, src, pol, "sp_parents")
        st = jax.tree.map(lambda x: x[0], res.state)
        assert bool(
            validate_parents(
                st.levels[: csr.n_nodes],
                st.parents[: csr.n_nodes],
                jnp.asarray(src),
            )
        ), pol.name


def test_recommendations():
    assert recommend_policy(1, 32, 40.0) == "ntks"
    assert recommend_policy(8, 32, 40.0) == "ntks"
    assert recommend_policy(128, 32, 40.0) == "ntkms"
    # path outputs with huge graph: fall back (paper §5.6 OOM finding)
    assert (
        recommend_policy(
            256, 32, 35.0, returns_paths=True, n_nodes=120_000_000
        )
        == "ntks"
    )
    assert recommend_k(44.0) == 32
    assert recommend_k(535.0) == 4
    assert recommend_k(250.0) == 8


# ---------------------------------------------------------------------------
# Policy-layer oracles: threshold fitting + backend recommendation (ISSUE 3)
# ---------------------------------------------------------------------------


def _synthetic_trace(n, alpha_star, m_u=8000.0, push=1000.0):
    """A trace whose oracle-optimal direction flips exactly at
    ``m_f * alpha_star > m_u`` (beta non-binding: full frontier)."""
    iters = []
    for i in range(20):
        m_f = 150.0 * (i + 1)
        pull_wins = m_f * alpha_star > m_u
        iters.append({
            "it": i,
            "frontier": n,  # n_f*beta > n for any beta > 1
            "unvisited": n // 2,
            "m_frontier": m_f,
            "m_unexplored": m_u,
            "push_slots": push,
            "pull_slots_binned": 100.0 if pull_wins else 10 * push,
            "pull_slots_ell": 100.0 if pull_wins else 10 * push,
            "scanned_slots": push,
            "wall_ms": 0.1,
        })
    return iters


def test_fit_direction_thresholds_recovers_crossover():
    """A synthetic trace with a known optimal alpha crossover: the fitted
    alpha must land within one pow2 bucket (factor 2) of the true value,
    and the fitted table must beat Beamer's constants on its own trace."""
    n, alpha_star = 1024, 4.0
    doc = {
        "workloads": [{
            "graph": "synth", "kind": "powerlaw", "n": n,
            "n_edges": n * 8, "avg_degree": 8.0,
            "backends": {"ell_push": {
                "iterations": _synthetic_trace(n, alpha_star)
            }},
        }]
    }
    th = fit_direction_thresholds(doc)
    alpha, beta = th.table[("powerlaw", degree_bucket(8.0))]
    assert alpha_star / 2 <= alpha <= alpha_star * 2, alpha
    # fitted predicate reproduces the oracle labels over the whole trace
    for r in doc["workloads"][0]["backends"]["ell_push"]["iterations"]:
        use_pull = (r["m_frontier"] * alpha > r["m_unexplored"]) and (
            r["frontier"] * beta > n
        )
        assert use_pull == (r["pull_slots_binned"] < r["push_slots"]), r
    # degraded inputs never fail the fit: missing fields => Beamer defaults
    th0 = fit_direction_thresholds(
        {"workloads": [{"graph": "old", "kind": "er", "n": 64,
                        "n_edges": 128, "avg_degree": 2.0,
                        "backends": {"ell_push": {"iterations": [
                            {"it": 0, "frontier": 1, "scanned_slots": 9,
                             "wall_ms": 0.1}]}}}]}
    )
    assert th0.table[("er", 1)] == (14.0, 24.0)


def test_fit_direction_thresholds_mixed_sizes_one_group():
    """Two same-(family, bucket) workloads of very different node counts:
    the beta predicate must be evaluated against each record's OWN n, not
    the first workload's — a beta fitted for the small graph must still
    dispatch the big graph's iterations correctly."""
    alpha_star = 4.0
    small, big = 1024, 65536
    doc = {"workloads": [
        {"graph": "s", "kind": "powerlaw", "n": small, "n_edges": small * 8,
         "avg_degree": 8.0,
         "backends": {"ell_push": {
             "iterations": _synthetic_trace(small, alpha_star)}}},
        {"graph": "b", "kind": "powerlaw", "n": big, "n_edges": big * 8,
         "avg_degree": 8.0,
         "backends": {"ell_push": {
             "iterations": _synthetic_trace(big, alpha_star)}}},
    ]}
    th = fit_direction_thresholds(doc)
    alpha, beta = th.table[("powerlaw", 3)]
    # with each record's own n, the fit classifies BOTH workloads'
    # iterations optimally (each trace has frontier = its own n)
    for w in doc["workloads"]:
        n = w["n"]
        for r in w["backends"]["ell_push"]["iterations"]:
            use_pull = (r["m_frontier"] * alpha > r["m_unexplored"]) and (
                r["frontier"] * beta > n
            )
            assert use_pull == (
                r["pull_slots_binned"] < r["push_slots"]
            ), (w["graph"], r["it"])


def test_direction_threshold_lookup_fallbacks():
    th = DirectionThresholds(table={
        ("powerlaw", 3): (4.0, 16.0),
        ("powerlaw", 6): (30.0, 24.0),
        ("er", 2): (7.0, 12.0),
    })
    assert th.lookup("powerlaw", 8.0) == (4.0, 16.0)  # exact bucket
    assert th.lookup("powerlaw", 20.0) == (30.0, 24.0)  # nearest in family
    assert th.lookup("er", 4.0) == (7.0, 12.0)
    assert th.lookup("rmat", 4.0) == (7.0, 12.0)  # nearest cross-family
    empty = DirectionThresholds(table={})
    assert empty.lookup("powerlaw", 8.0) == (14.0, 24.0)  # Beamer default
    assert degree_bucket(1.0) == 0 and degree_bucket(8.0) == 3
    assert degree_bucket(9.0) == 4


def test_recommend_backend_deterministic_and_total():
    """recommend_backend is a pure function of its arguments (identical
    result on repeated calls across the whole argument grid) and total:
    with an operand bundle it only ever names a backend that bundle can
    actually run."""
    grid = itertools.product(
        ["sp_lengths", "sp_parents", "bellman_ford", "msbfs_lengths"],
        [2.0, 8.0, 300.0],
        [512, 10**7],
        [1, 64],
    )
    for ec, deg, n, lanes in grid:
        r1 = recommend_backend(ec, deg, n_nodes=n, lanes=lanes)
        r2 = recommend_backend(ec, deg, n_nodes=n, lanes=lanes)
        assert r1 == r2, (ec, deg, n, lanes)
        as_spec(r1)  # always a constructible spec

    # totality vs concrete operand bundles: the recommendation must run
    csr = powerlaw(96, 4.0, seed=2)
    for built in ["ell_push", "ell_pull", "pull_binned", "dopt",
                  "dopt_ell"]:
        ops, _ = build_operands(csr, built)
        for ec, lanes in [("sp_lengths", 1), ("bellman_ford", 1),
                          ("msbfs_lengths", 64)]:
            rec = recommend_backend(
                ec, csr.avg_degree, n_nodes=csr.n_nodes, lanes=lanes,
                operands=ops,
            )
            spec = as_spec(rec)
            assert not spec.needs_rev or ops.rev is not None
            assert not spec.needs_binned or ops.rev_binned is not None
            assert not spec.needs_blocks or ops.blocks is not None
            if ec != "msbfs_lengths":  # dense path: actually execute it
                run_ife(ops, jnp.array([0]), ec, extend=spec)
    # a bare-push bundle degrades all the way to ell_push
    ops_push, _ = build_operands(csr, "ell_push")
    assert recommend_backend(
        "sp_lengths", csr.avg_degree, n_nodes=csr.n_nodes,
        operands=ops_push,
    ) == "ell_push"


# ---------------------------------------------------------------------------
# Phase-1 budget model (ISSUE 5): per-(family, source-degree-bucket) windows,
# pow2-quantized quantile serving, lookup-style fallback, mispredict counters.
# ---------------------------------------------------------------------------


def test_budget_model_empty_predicts_none():
    """An empty model must predict None for every key — the scheduler's
    signal to fall back to the legacy global pow2 p90 path."""
    m = BudgetModel()
    assert len(m) == 0 and m.n_samples == 0
    assert m.predict("powerlaw", 3, 64) is None
    assert m.budget_for("powerlaw", [0, 1, 2], 64) is None
    assert m.budget_for("powerlaw", [], 64) is None
    assert m.budgets(64) == {}


def test_budget_model_pow2_quantile_serving():
    m = BudgetModel(floor=4)
    m.observe("er", 2, [5, 5, 6])
    # p90 of [5,5,6] = 5.8 -> int 5 -> +1 -> pow2 8
    assert m.predict("er", 2, 64) == 8
    m2 = BudgetModel(floor=4)
    m2.observe("er", 2, [40] * 8)
    assert m2.predict("er", 2, 64) == 64  # pow2ceil(41)
    assert m2.predict("er", 2, 32) == 32  # clamped to max_iters
    m3 = BudgetModel(floor=4)
    m3.observe("er", 2, [1, 1])
    assert m3.predict("er", 2, 64) == 4  # clamped to the floor
    assert pow2ceil(41) == 64 and pow2ceil(8) == 8 and pow2ceil(0) == 1


def test_budget_model_window_is_bounded():
    """Old observations age out: the window forgets a workload shift."""
    m = BudgetModel(window=8)
    m.observe("er", 2, [60] * 8)
    assert m.predict("er", 2, 64) == 64
    m.observe("er", 2, [3] * 8)  # window full of the new regime
    assert m.predict("er", 2, 64) == 4
    assert m.n_samples == 8


def test_budget_model_fallback_mirrors_threshold_lookup():
    """family -> nearest bucket in family -> nearest bucket globally —
    the DirectionThresholds.lookup chain, applied to budget windows."""
    m = BudgetModel()
    m.observe("er", 1, [3, 3, 3])  # -> 4
    m.observe("er", 4, [30, 30])  # -> 32
    m.observe("powerlaw", 6, [10, 10])  # -> 16
    assert m.predict("er", 1, 64) == 4  # exact
    assert m.predict("er", 2, 64) == 4  # nearest in family: bucket 1
    assert m.predict("er", 3, 64) == 32  # nearest in family: bucket 4
    assert m.predict("powerlaw", 0, 64) == 16  # family first, any distance
    assert m.predict("rmat", 5, 64) == 32  # global nearest: ("er", 4)
    assert m.predict(None, 5, 64) == 32  # no-family queries also served
    # covering budget of a mixed batch = max over its buckets
    assert m.budget_for("er", [1, 4], 64) == 32
    assert m.budget_for("er", [1], 64) == 4


def test_budget_model_empty_observations_are_ignored():
    """The all-pad guard's model half: zero-length observations (a batch
    with no real morsels) must not create windows or samples."""
    m = BudgetModel()
    m.observe("er", 2, [])
    m.observe_batch("er", [], [])
    assert len(m) == 0 and m.predict("er", 2, 64) is None


def test_count_budget_mispredicts_semantics():
    # survivors are too_low; converged morsels with trips*2 < budget are
    # too_high; inert_slots is the converged slack
    tl, th, inert = count_budget_mispredicts(
        8, trips=[8, 8, 5, 3, 2], survived=[True, True, False, False, False]
    )
    assert tl == 2
    assert th == 2  # trips 3 and 2 (2*t < 8); 5 is right-sized
    assert inert == (8 - 5) + (8 - 3) + (8 - 2)
    # the right-sized band is [budget/2, budget]: a steady depth-4 stream
    # served its own quantized budget pow2ceil(4+1)=8 never mispredicts
    tl, th, _ = count_budget_mispredicts(
        8, trips=[4, 4], survived=[False, False]
    )
    assert tl == 0 and th == 0
    # a budget at the quantization floor never counts too_high
    tl, th, inert = count_budget_mispredicts(
        4, trips=[1, 1], survived=[False, False]
    )
    assert tl == 0 and th == 0 and inert == 6
    # counters accumulate and reset on the model
    m = BudgetModel()
    m.mispredicts.count(2, 1, 9, 5)
    m.mispredicts.count(1, 0, 3, 5)
    assert (m.mispredicts.too_low, m.mispredicts.too_high) == (3, 1)
    assert m.mispredicts.inert_slots == 12 and m.mispredicts.observed == 10
    assert m.mispredicts.rate == 0.4
    m.mispredicts.reset()
    assert m.mispredicts.observed == 0 and m.mispredicts.rate == 0.0


def test_block_extend_matches_ell():
    from repro.graph.csr import ell_from_csr, blocks_from_csr
    from repro.graph.partition import pad_ell
    from repro.core.msbfs import block_extend_lanes
    from repro.core.edge_compute import ell_reach_lanes
    from repro.core.frontier import lanes_from_sources

    csr = erdos_renyi(200, 5.0, seed=10)
    block = 64
    n_pad = -(-csr.n_nodes // block) * block
    g = pad_ell(ell_from_csr(csr), shards=1, block=block)
    adj = blocks_from_csr(csr, block=block)
    lanes = lanes_from_sources(n_pad, jnp.arange(64, dtype=jnp.int32) * 3)
    ref = ell_reach_lanes(g, lanes)
    got = block_extend_lanes(adj, lanes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
