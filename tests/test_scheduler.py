"""Adaptive morsel runtime + mesh/autodiff regression tests.

Covers the two root-cause seed fixes (Auto-typed mesh construction,
grad-through-optimization_barrier) and the runtime: engine-cache hit/miss
identity, two-phase hybrid bit-parity with static nTkS, chunked dispatch,
multi-tenant lane-packing admission, and the gang-scheduled phase-2 resume
(differential parity corpus: ganged vs serial per-morsel resume vs static
nTkS vs the numpy oracle, over both state layouts; pow2-pad boundary,
single-survivor fast path, all-inert resume, and zero-survivor fixtures).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from oracle import bfs_levels
from proptest import given, st_ints, st_sampled, st_seeds, st_subset

from repro.core import (
    build_engine,
    pad_sources,
    policy_ntks,
    policy_ntkms,
    prepare_graph,
    run_recursive_query,
)
from repro.core.extend import as_spec
from repro.graph.csr import csr_from_edges
from repro.graph.generators import erdos_renyi, powerlaw
from repro.launch.mesh import make_mesh
from repro.runtime.scheduler import AdaptiveScheduler, _pow2ceil


@functools.lru_cache(maxsize=None)
def mesh11():
    return make_mesh((1, 1), ("data", "model"))


@functools.lru_cache(maxsize=None)
def skew_graph(kind: str = "powerlaw", n_main: int = 160,
               paths: tuple = (40, 28, 22), seed: int = 0):
    """A small-diameter main component plus ``len(paths)`` long-path
    straggler components: sources on the path heads survive any small
    phase-1 budget, so the survivor count is controllable per test.
    Returns (csr, path_head_ids)."""
    main = (powerlaw if kind == "powerlaw" else erdos_renyi)(
        n_main, 5.0, seed=seed
    )
    src_m, dst_m = main.edge_list()
    srcs, dsts, base, heads = [src_m], [dst_m], n_main, []
    for length in paths:
        p = np.arange(length - 1, dtype=np.int64) + base
        srcs += [p, p + 1]
        dsts += [p + 1, p]
        heads.append(base)
        base += length
    csr = csr_from_edges(base, np.concatenate(srcs), np.concatenate(dsts))
    return csr, tuple(heads)


# ---------------------------------------------------------------------------
# Bugfix regressions
# ---------------------------------------------------------------------------

def test_make_mesh_axes_auto():
    # the helper builds a working mesh whose axes are all Auto, which the
    # shard_map engines rely on
    m = make_mesh((1, 1), ("a", "b"))
    assert dict(m.shape) == {"a": 1, "b": 1}
    assert m.axis_types == (jax.sharding.AxisType.Auto,) * 2


def test_grad_through_barrier_under_scan_and_remat():
    """jax 0.4.x regression: grad of optimization_barrier inside
    scan-of-checkpoint raised NotImplementedError; the custom_jvp wrapper
    must be numerically an identity for both primal and gradient."""
    from repro.models.transformer import grad_safe_barrier

    def net(w, use_barrier):
        def layer(x, _):
            h = jnp.tanh(x @ w)
            if use_barrier:
                h = grad_safe_barrier(h)
            return h, ()

        y, _ = jax.lax.scan(
            jax.checkpoint(layer), jnp.ones((4,)), None, length=3
        )
        return jnp.sum(y * y)

    w = jnp.asarray(np.random.default_rng(0).standard_normal((4, 4)) * 0.3,
                    jnp.float32)
    loss_b, grad_b = jax.value_and_grad(lambda w: net(w, True))(w)
    loss_p, grad_p = jax.value_and_grad(lambda w: net(w, False))(w)
    np.testing.assert_allclose(float(loss_b), float(loss_p), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(grad_b), np.asarray(grad_p), rtol=1e-6, atol=1e-7
    )


# ---------------------------------------------------------------------------
# Engine cache
# ---------------------------------------------------------------------------

def test_engine_cache_hit_miss_by_key():
    csr = erdos_renyi(96, 4.0, seed=4)
    sched = AdaptiveScheduler(
        mesh11(), csr, max_iters=32, phase1_iters=2
    )
    srcs = np.array([0, 7, 23], np.int32)

    sched.query(srcs)
    n0, miss0 = len(sched.cache), sched.cache.misses
    assert n0 == miss0 and sched.cache.hits == 0
    assert n0 >= 1  # at least the phase-1 engine

    # same (policy, edge compute, shapes) => pure cache hits, no compiles
    sched.query(np.array([1, 2, 3], np.int32))
    assert len(sched.cache) == n0
    assert sched.cache.misses == miss0
    assert sched.cache.hits >= 1

    # different edge compute => new keys, old entries untouched
    sched.query(srcs, returns_paths=True)
    assert len(sched.cache) > n0
    assert sched.cache.misses > miss0


# ---------------------------------------------------------------------------
# Two-phase hybrid == static nTkS (bit-identical state)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("returns_paths", [False, True])
def test_hybrid_state_bit_identical_to_static_ntks(returns_paths):
    csr = powerlaw(260, 5.0, seed=7)
    mesh = mesh11()
    srcs = np.array([0, 11, 42, 97, 150, 201], np.int32)
    ec = "sp_parents" if returns_paths else "sp_lengths"

    static = run_recursive_query(mesh, csr, srcs, policy_ntks(), ec)
    sched = AdaptiveScheduler(mesh, csr, max_iters=64, phase1_iters=2)
    out = sched.query(srcs, returns_paths=returns_paths)
    assert out.hybrid
    assert out.redispatched > 0  # phase 2 must actually have run

    ref = jax.tree.map(np.asarray, static.state)
    got = jax.tree.map(np.asarray, out.result.state)
    for field in ref._fields:
        a, b = getattr(ref, field), getattr(got, field)
        assert a.dtype == b.dtype and a.shape == b.shape, field
        np.testing.assert_array_equal(a, b, err_msg=field)


def test_hybrid_budget_covers_convergence_skips_phase2():
    csr = erdos_renyi(80, 4.0, seed=2)
    sched = AdaptiveScheduler(
        mesh11(), csr, max_iters=64, phase1_iters=64
    )
    out = sched.query(np.array([3, 9], np.int32))
    assert out.hybrid and out.redispatched == 0
    assert out.phase_ms["phase2"] == 0.0
    lv = np.asarray(out.result.state.levels)[:2, : csr.n_nodes]
    np.testing.assert_array_equal(lv[0], bfs_levels(csr, [3]))
    np.testing.assert_array_equal(lv[1], bfs_levels(csr, [9]))


def test_chunked_dispatch_matches_unchunked():
    """recommend_k-style in-flight caps split the batch; results must be
    independent of the chunking."""
    csr = erdos_renyi(120, 4.0, seed=9)
    srcs = np.random.default_rng(1).integers(
        0, csr.n_nodes, 12
    ).astype(np.int32)
    capped = AdaptiveScheduler(
        mesh11(), csr, max_iters=64, phase1_iters=2, max_inflight=4
    )
    plain = AdaptiveScheduler(
        mesh11(), csr, max_iters=64, phase1_iters=2
    )
    la = np.asarray(capped.query(srcs).result.state.levels)
    lb = np.asarray(plain.query(srcs).result.state.levels)
    np.testing.assert_array_equal(
        la[: len(srcs), : csr.n_nodes], lb[: len(srcs), : csr.n_nodes]
    )


# ---------------------------------------------------------------------------
# backend="recommend" default (ISSUE 3): the served default must be
# bit-identical to any explicitly pinned backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("returns_paths", [False, True])
def test_recommend_default_bit_identical_to_explicit(returns_paths):
    """The scheduler's (and serve's) default is now backend="recommend"
    (direction-optimized binned pull for the BFS family). One scheduler
    left on the default and one pinned to each explicit backend must
    produce byte-identical result states — and both must match the static
    single-engine dispatcher."""
    csr = powerlaw(240, 5.0, seed=13)
    mesh = mesh11()
    srcs = np.array([0, 9, 41, 77, 160], np.int32)
    ec = "sp_parents" if returns_paths else "sp_lengths"

    sched = AdaptiveScheduler(mesh, csr, max_iters=64, phase1_iters=2)
    assert sched.backend == "recommend"
    out = sched.query(srcs, returns_paths=returns_paths)
    ref = jax.tree.map(np.asarray, out.result.state)

    static = run_recursive_query(mesh, csr, srcs, policy_ntks(), ec)
    for field in ref._fields:
        np.testing.assert_array_equal(
            getattr(ref, field),
            np.asarray(getattr(static.state, field)),
            err_msg=f"recommend-vs-static/{field}",
        )

    for be in ("ell_push", "ell_pull", "pull_binned", "dopt", "dopt_ell"):
        pinned = AdaptiveScheduler(
            mesh, csr, max_iters=64, phase1_iters=2, backend=be
        )
        got = jax.tree.map(
            np.asarray,
            pinned.query(srcs, returns_paths=returns_paths).result.state,
        )
        for field in ref._fields:
            a, b = getattr(ref, field), getattr(got, field)
            assert a.dtype == b.dtype and a.shape == b.shape, (be, field)
            np.testing.assert_array_equal(a, b, err_msg=f"{be}/{field}")


def test_recommend_with_fitted_thresholds_bit_identical():
    """A fitted threshold table changes WHEN the switch pulls, never WHAT
    it computes: results stay bit-identical, and the fitted spec is served
    through the same engine-cache path (fresh keys, then pure hits)."""
    from repro.core import DirectionThresholds

    csr = powerlaw(200, 6.0, seed=5)
    mesh = mesh11()
    srcs = np.array([2, 30, 71], np.int32)
    base = AdaptiveScheduler(mesh, csr, max_iters=64, phase1_iters=2)
    th = DirectionThresholds(table={("powerlaw", 4): (2.0, 2.0)})
    fitted = AdaptiveScheduler(
        mesh, csr, max_iters=64, phase1_iters=2,
        direction_thresholds=th, family="powerlaw",
    )
    a = np.asarray(base.query(srcs).result.state.levels)
    b = np.asarray(fitted.query(srcs).result.state.levels)
    np.testing.assert_array_equal(a, b)
    h0, m0 = fitted.cache.hits, fitted.cache.misses
    fitted.query(srcs)
    assert fitted.cache.hits > h0 and fitted.cache.misses == m0


# ---------------------------------------------------------------------------
# Multi-tenant admission
# ---------------------------------------------------------------------------

def test_admission_packs_lanes_only_when_saturated():
    csr = powerlaw(200, 5.0, seed=3)
    sched = AdaptiveScheduler(mesh11(), csr, max_iters=64)
    rng = np.random.default_rng(0)

    # 5 tenants x 16 sources = 80 >= 64 -> one packed MS-BFS run
    tenants = {
        sched.submit(s): s
        for s in [
            rng.integers(0, csr.n_nodes, 16).astype(np.int32)
            for _ in range(5)
        ]
    }
    res = sched.flush()
    assert sched.admissions == {"ntkms": 1, "per_query": 0}
    assert set(res) == set(tenants)
    for qid, srcs in tenants.items():
        assert res[qid].shape == (len(srcs), csr.n_nodes)
        for j, s in enumerate(srcs):
            np.testing.assert_array_equal(
                res[qid][j], bfs_levels(csr, [int(s)]), err_msg=f"{qid}/{j}"
            )

    # a lone small query must NOT be packed: per-query hybrid path
    qid = sched.submit(np.array([5, 17], np.int32))
    res = sched.flush()
    assert sched.admissions["per_query"] == 1
    np.testing.assert_array_equal(res[qid][0], bfs_levels(csr, [5]))
    np.testing.assert_array_equal(res[qid][1], bfs_levels(csr, [17]))

    assert sched.flush() == {}  # nothing pending


def test_pow2ceil():
    assert [_pow2ceil(x) for x in (0, 1, 2, 3, 4, 5, 8, 9)] == [
        1, 1, 2, 4, 4, 8, 8, 16,
    ]


# ---------------------------------------------------------------------------
# Gang-scheduled phase-2 resume (ISSUE 4): batched multi-frontier re-dispatch
# must be bit-identical to the serial per-morsel resume, to static nTkS, and
# to the numpy oracle — plus edge-case fixtures for the gang path itself.
# ---------------------------------------------------------------------------

_SCHED_CACHE: dict = {}
_STATIC_CACHE: dict = {}


def _sched(kind: str, backend: str, layout: str = "replicated",
           gang: bool = True, adapt: bool = False) -> AdaptiveScheduler:
    """One AdaptiveScheduler per corpus configuration — compiled engines
    are reused across fuzz cases, so the corpus pays each (graph, backend,
    engine-kind) compile exactly once. ``adapt=True`` is the
    online-learning configuration: no pinned budget (the per-bucket
    BudgetModel serves it), stats-tapped phase-1 engines, and in-flight
    threshold refits every few batches — the corpus proves none of that
    can move results."""
    key = (kind, backend, layout, gang, adapt)
    if key not in _SCHED_CACHE:
        csr, _ = skew_graph(kind)
        if adapt:
            _SCHED_CACHE[key] = AdaptiveScheduler(
                mesh11(), csr, max_iters=64, backend=backend,
                gang_resume=gang, family=kind, online_adapt=True,
                refit_every=4,
            )
        else:
            _SCHED_CACHE[key] = AdaptiveScheduler(
                mesh11(), csr, max_iters=64, phase1_iters=2,
                backend=backend, gang_resume=gang, online_adapt=False,
            )
    return _SCHED_CACHE[key]


def _static_levels(kind: str, backend: str, srcs: np.ndarray,
                   layout: str = "replicated") -> np.ndarray:
    """Static single-engine nTkS reference levels (cached engine)."""
    key = (kind, backend, layout)
    if key not in _STATIC_CACHE:
        csr, _ = skew_graph(kind)
        spec = as_spec(backend)
        g, n_pad = prepare_graph(csr, mesh11(), policy_ntks(), extend=spec)
        eng = build_engine(
            mesh11(), policy_ntks(), "sp_lengths", n_pad, 64,
            state_layout=layout, extend=spec, operands=g,
        )
        _STATIC_CACHE[key] = (csr, g, n_pad, eng)
    csr, g, n_pad, eng = _STATIC_CACHE[key]
    morsels = pad_sources(srcs, 1, 1, n_pad)
    res = eng(g, jnp.asarray(morsels))
    return np.asarray(res.state.levels)[: len(srcs), : csr.n_nodes]


def _gang_case_sources(kind: str, head_picks, rng) -> np.ndarray:
    """Fixed-size source batch (stable trace shapes across fuzz cases):
    the chosen straggler path heads + random main-component fillers."""
    csr, heads = skew_graph(kind)
    fill = rng.integers(0, 160, 6 - len(head_picks)).astype(np.int32)
    return np.concatenate(
        [np.asarray(head_picks, np.int32), fill]
    ).astype(np.int32)


@given(
    st_seeds(),
    st_sampled(["powerlaw", "er"]),
    st_sampled(["ell_push", "dopt"]),
    st_subset([0, 1, 2], min_size=0),
    cases=10,
)
def test_gang_parity_fuzz_corpus(seed, kind, backend, head_ids):
    """Differential engine-parity corpus (replicated layout): for a seeded
    random (graph family x backend x source set) case, the gang-scheduled
    hybrid, the serial per-morsel hybrid, the ONLINE-ADAPTING scheduler
    (per-bucket budget model + stats-tapped phase 1 + in-flight threshold
    refits, backend="recommend"), the static nTkS engine, and the numpy
    BFS oracle must agree bit-for-bit — online learning may only move
    iteration slots, never results."""
    rng = np.random.default_rng(seed)
    csr, heads = skew_graph(kind)
    srcs = _gang_case_sources(
        kind, [heads[i] for i in head_ids], rng
    )
    ganged = _sched(kind, backend).query(srcs)
    serial = _sched(kind, backend, gang=False).query(srcs)
    online = _sched(kind, "recommend", adapt=True).query(srcs)
    assert ganged.redispatched == serial.redispatched
    assert ganged.resumed_serial == 0 or ganged.gang_width == 0
    assert serial.resumed_ganged == 0

    a = jax.tree.map(np.asarray, ganged.result.state)
    b = jax.tree.map(np.asarray, serial.result.state)
    c = jax.tree.map(np.asarray, online.result.state)
    for field in a._fields:
        np.testing.assert_array_equal(
            getattr(a, field), getattr(b, field),
            err_msg=f"gang-vs-serial/{field}",
        )
        np.testing.assert_array_equal(
            getattr(a, field), getattr(c, field),
            err_msg=f"online-adapt-vs-disabled/{field}",
        )
    np.testing.assert_array_equal(
        np.asarray(ganged.result.iterations),
        np.asarray(serial.result.iterations),
        err_msg="gang-vs-serial/iterations",
    )
    # final iterations are each morsel's true convergence depth — the
    # learned budget moves the phase-1/phase-2 split, not the total
    np.testing.assert_array_equal(
        np.asarray(ganged.result.iterations),
        np.asarray(online.result.iterations),
        err_msg="online-adapt-vs-disabled/iterations",
    )

    lv = a.levels[: len(srcs), : csr.n_nodes]
    np.testing.assert_array_equal(
        lv, _static_levels(kind, backend, srcs), err_msg="gang-vs-static"
    )
    for j, s in enumerate(srcs):
        np.testing.assert_array_equal(
            lv[j], bfs_levels(csr, [int(s)]), err_msg=f"oracle/src{j}"
        )


@pytest.mark.slow
@given(
    st_seeds(),
    st_sampled(["powerlaw", "er"]),
    st_sampled(["ell_push", "dopt"]),
    st_subset([0, 1, 2], min_size=1),
    cases=6,
)
def test_gang_parity_fuzz_corpus_sharded(seed, kind, backend, head_ids):
    """Sharded-state layer of the corpus: the reduce-scatter/all-gather
    gang resume must match the replicated gang hybrid and the sharded
    static engine bit-for-bit — with online adaptation (stats-tapped
    sharded phase 1, learned budgets) enabled as well as disabled."""
    rng = np.random.default_rng(seed)
    csr, heads = skew_graph(kind)
    srcs = _gang_case_sources(kind, [heads[i] for i in head_ids], rng)
    out = _sched(kind, backend, layout="sharded").query(
        srcs, state_layout="sharded"
    )
    assert out.hybrid and out.resumed_ganged == out.redispatched > 0
    ref = _sched(kind, backend).query(srcs)
    onl = _sched(kind, "recommend", layout="sharded", adapt=True).query(
        srcs, state_layout="sharded"
    )
    a = jax.tree.map(np.asarray, out.result.state)
    b = jax.tree.map(np.asarray, ref.result.state)
    c = jax.tree.map(np.asarray, onl.result.state)
    for field in a._fields:
        np.testing.assert_array_equal(
            getattr(a, field), getattr(b, field),
            err_msg=f"sharded-vs-replicated/{field}",
        )
        np.testing.assert_array_equal(
            getattr(a, field), getattr(c, field),
            err_msg=f"sharded-online-adapt-vs-disabled/{field}",
        )
    lv = a.levels[: len(srcs), : csr.n_nodes]
    np.testing.assert_array_equal(
        lv, _static_levels(kind, backend, srcs, layout="sharded"),
        err_msg="sharded-gang-vs-sharded-static",
    )


def test_gang_pow2_pad_boundary_3_to_4():
    """3 survivors pad to a 4-wide gang; counters split accordingly."""
    csr, heads = skew_graph("powerlaw")
    sched = AdaptiveScheduler(
        mesh11(), csr, max_iters=64, phase1_iters=16
    )
    # budget 16 covers the main component (diameter << 16) but none of the
    # 3 path components (depths 39/27/21) => exactly the 3 heads survive
    srcs = np.concatenate([[heads[0], heads[1], heads[2]], [3, 9]]).astype(
        np.int32
    )
    out = sched.query(srcs)
    assert out.redispatched == 3
    assert out.resumed_ganged == 3 and out.resumed_serial == 0
    assert out.gang_width == 4
    assert sched.stats.gangs == 1 and sched.stats.gang_slots == 4
    assert sched.stats.gang_occupancy == 0.75
    lv = np.asarray(out.result.state.levels)
    for j, s in enumerate(srcs):
        np.testing.assert_array_equal(
            lv[j, : csr.n_nodes], bfs_levels(csr, [int(s)])
        )


def test_gang_pow2_pad_boundary_5_to_8():
    """5 survivors cross the pow2 boundary to an 8-wide gang."""
    csr, heads = skew_graph(
        "powerlaw", paths=(40, 38, 39, 41, 37), seed=1
    )
    sched = AdaptiveScheduler(
        mesh11(), csr, max_iters=64, phase1_iters=32
    )
    srcs = np.asarray(list(heads), np.int32)
    assert len(srcs) == 5
    out = sched.query(srcs)
    assert out.redispatched == 5
    assert out.resumed_ganged == 5 and out.gang_width == 8
    lv = np.asarray(out.result.state.levels)
    for j, s in enumerate(srcs):
        np.testing.assert_array_equal(
            lv[j, : csr.n_nodes], bfs_levels(csr, [int(s)])
        )


def test_gang_single_survivor_serial_fast_path():
    """Exactly one survivor skips gang packing: the serial per-morsel
    resume runs (no gang dispatch, gang_width 0)."""
    csr, heads = skew_graph("powerlaw", paths=(40,))
    sched = AdaptiveScheduler(
        mesh11(), csr, max_iters=64, phase1_iters=16
    )
    srcs = np.asarray([heads[0], 3, 9], np.int32)
    out = sched.query(srcs)
    assert out.redispatched == 1
    assert out.resumed_serial == 1 and out.resumed_ganged == 0
    assert out.gang_width == 0
    assert sched.stats.gangs == 0 and sched.stats.gang_slots == 0
    assert sched.stats.resumed_serial == 1
    lv = np.asarray(out.result.state.levels)
    for j, s in enumerate(srcs):
        np.testing.assert_array_equal(
            lv[j, : csr.n_nodes], bfs_levels(csr, [int(s)])
        )


def test_gang_all_survivors_inert_first_resume_iteration():
    """Survivors whose counters already sit at the iteration cap: the gang
    while_loop must be a zero-trip no-op (convergence masks keep capped
    morsels frozen), bit-identical to the static engine at the same cap."""
    cap = 4
    csr, heads = skew_graph("powerlaw")
    sched = AdaptiveScheduler(
        mesh11(), csr, max_iters=cap, phase1_iters=cap
    )
    srcs = np.asarray(list(heads), np.int32)  # all three survive at it==cap
    out = sched.query(srcs)
    assert out.redispatched == 3 and out.resumed_ganged == 3
    static = run_recursive_query(
        mesh11(), csr, srcs, policy_ntks(), "sp_lengths", max_iters=cap
    )
    a = jax.tree.map(np.asarray, out.result.state)
    b = jax.tree.map(np.asarray, static.state)
    for field in a._fields:
        np.testing.assert_array_equal(
            getattr(a, field), getattr(b, field), err_msg=field
        )
    np.testing.assert_array_equal(
        np.asarray(out.result.iterations), np.full(len(srcs), cap)
    )


def test_gang_zero_survivor_flush():
    """Budget covering convergence => no survivors, no gang dispatch, and
    every phase-2 counter stays zero."""
    csr, _ = skew_graph("powerlaw", paths=())
    sched = AdaptiveScheduler(mesh11(), csr, max_iters=64, phase1_iters=64)
    out = sched.query(np.asarray([3, 9, 17], np.int32))
    assert out.hybrid and out.redispatched == 0
    assert out.resumed_ganged == 0 and out.resumed_serial == 0
    assert out.gang_width == 0 and out.phase_ms["phase2"] == 0.0
    assert sched.stats.gangs == 0 and sched.stats.redispatched == 0
    assert sched.stats.gang_occupancy == 0.0


def test_stats_counter_split_invariant():
    """SchedulerStats aggregates the redispatched = ganged + serial split
    across queries, and the engine cache tracks gang compiles by kind."""
    csr, heads = skew_graph("powerlaw")
    sched = AdaptiveScheduler(mesh11(), csr, max_iters=64, phase1_iters=16)
    sched.query(np.asarray([heads[0], 3], np.int32))  # 1 survivor: serial
    sched.query(np.asarray(list(heads), np.int32))  # 3 survivors: gang
    st = sched.stats
    assert st.queries == 2 and st.hybrid_runs == 2
    assert st.redispatched == st.resumed_ganged + st.resumed_serial == 4
    assert st.resumed_serial == 1 and st.resumed_ganged == 3
    assert st.gangs == 1 and st.gang_slots == 4
    assert sched.cache.misses_by_kind["gang"] == 1
    assert sched.cache.misses_by_kind["resume"] == 1
    assert sched.cache.misses_by_kind["phase1"] >= 1
    # same shapes again: pure cache hits, including the gang engine
    h0 = sched.cache.hits_by_kind["gang"]
    sched.query(np.asarray(list(heads), np.int32))
    assert sched.cache.misses_by_kind["gang"] == 1
    assert sched.cache.hits_by_kind["gang"] == h0 + 1


def test_gang_ntkms_lane_morsels():
    """Gang resume over 64-lane MS-BFS morsels: two surviving lane morsels
    fold into one [rows, 2*64] lane tensor; results bit-match static
    nTkMS over the logical node range (padding differs per backend)."""
    csr, heads = skew_graph("powerlaw")
    n = csr.n_nodes
    sched = AdaptiveScheduler(mesh11(), csr, max_iters=64, phase1_iters=2)
    srcs = np.concatenate(
        [
            np.arange(60, dtype=np.int32) % n,
            np.asarray(list(heads), np.int32),
            np.arange(61, 120, dtype=np.int32) % 160,
            [heads[0]],
        ]
    ).astype(np.int32)
    out = sched.query(srcs, policy="ntkms")
    assert out.policy == "ntkms"
    assert out.redispatched == 2  # both lane morsels hold a path head
    assert out.resumed_ganged == 2 and out.gang_width == 2
    static = run_recursive_query(
        mesh11(), csr, srcs, policy_ntkms(), "msbfs_lengths"
    )
    np.testing.assert_array_equal(
        np.asarray(out.result.state.levels)[:, :n, :],
        np.asarray(static.state.levels)[:, :n, :],
    )


def test_gang_engine_direct_bellman_ford():
    """The gang engine is edge-compute generic: weighted relax (merge=min,
    no lane formulation => vmap batching) resumed from freshly-initialized
    states must match the BFS oracle on a unit-weight graph, with correct
    per-morsel trip counts and an inert pad slot."""
    from repro.core import build_gang_resume_engine
    from repro.core.edge_compute import EDGE_COMPUTES
    from repro.core.policies import hybrid_phases

    csr, heads = skew_graph("powerlaw")
    n = csr.n_nodes
    _, p2 = hybrid_phases()
    g2, n_pad = prepare_graph(csr, mesh11(), p2, pad_shards=1)
    ec = EDGE_COMPUTES["bellman_ford"]
    ks = [int(heads[0]), 3, int(heads[1])]
    state0 = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[ec.init(n_pad, jnp.asarray([s], jnp.int32)) for s in ks],
    )
    state0 = jax.tree.map(  # pow2 pad slot: all-zero state, must stay inert
        lambda x: jnp.concatenate(
            [x, jnp.zeros((1,) + x.shape[1:], x.dtype)]
        ),
        state0,
    )
    eng = build_gang_resume_engine(
        mesh11(), p2, "bellman_ford", n_pad, 64, operands=g2
    )
    res = eng(g2, state0, jnp.zeros((4,), jnp.int32))
    dist = np.asarray(res.state.dist)
    for i, s in enumerate(ks):
        lv = bfs_levels(csr, [s]).astype(np.float64)
        lv[lv < 0] = np.inf
        np.testing.assert_allclose(dist[i, :n], lv, err_msg=str(s))
    iters = np.asarray(res.iterations)
    assert iters[3] == 0  # pad slot never iterated
    assert iters[0] > iters[1]  # path head runs ~path-length iterations


# ---------------------------------------------------------------------------
# Online policy learning (ISSUE 5): deterministic replay, budget-model
# integration edge cases, and mispredict-counter invariants.
# ---------------------------------------------------------------------------


def _replay_stream(heads):
    """A fixed seeded batch stream mixing shallow main-component sources
    with straggler path heads (stable shapes per batch index)."""
    rng = np.random.default_rng(7)
    batches = []
    for b in range(5):
        fill = rng.integers(0, 160, 4).astype(np.int32)
        if b % 2 == 0:
            fill = np.concatenate(
                [[heads[b % len(heads)]], fill[:3]]
            ).astype(np.int32)
        batches.append(fill)
    return batches


@pytest.mark.slow
def test_online_learning_deterministic_replay():
    """The same seeded batch stream must yield bit-identical refitted
    thresholds, learned budgets, accumulated sample traces, and
    mispredict counters — across independent runs AND across
    gang_resume on/off (the learner holds no wall-clock/RNG hidden
    state, and the gang only changes how phase 2 executes, never what
    any morsel observes)."""
    csr, heads = skew_graph("powerlaw")

    def run(gang: bool):
        sched = AdaptiveScheduler(
            mesh11(), csr, max_iters=64, backend="dopt",
            family="powerlaw", online_adapt=True, refit_every=2,
            gang_resume=gang,
        )
        budgets = [
            int(sched.query(b).phase1_budget) for b in _replay_stream(heads)
        ]
        sched.refit_thresholds()
        return sched, budgets

    a, budgets_a = run(gang=True)
    b, budgets_b = run(gang=True)
    c, budgets_c = run(gang=False)
    assert budgets_a == budgets_b == budgets_c
    ta = dict(a.direction_thresholds.table)
    assert ta == dict(b.direction_thresholds.table)
    assert ta == dict(c.direction_thresholds.table)
    assert ta, "refit produced an empty table"
    for other in (b, c):
        assert a.budget_model.budgets(64) == other.budget_model.budgets(64)
        assert a.online_trace() == other.online_trace()
        for f in ("budget_too_low", "budget_too_high",
                  "budget_inert_slots", "budget_observed", "refits"):
            assert getattr(a.stats, f) == getattr(other.stats, f), f
        m, mo = a.budget_model.mispredicts, other.budget_model.mispredicts
        assert (m.too_low, m.too_high, m.inert_slots, m.observed) == (
            mo.too_low, mo.too_high, mo.inert_slots, mo.observed
        )


def test_phase1_budget_model_priority_and_fallbacks():
    """Budget source priority: pinned phase1_iters > warmed BudgetModel
    (covering max over the batch's buckets) > global pow2 p90 deque
    (the empty-model path) > cold-start default."""
    csr, _ = skew_graph("powerlaw", paths=())
    sched = AdaptiveScheduler(mesh11(), csr, max_iters=64, family="er")
    assert sched._phase1_budget([2]) == 8  # cold start
    sched._iter_p90s.extend([11.0, 12.0, 13.0])
    assert sched._phase1_budget([2]) == 16  # empty model -> pow2 p90 path
    sched.budget_model.observe("er", 2, [30, 30, 30])
    assert sched._phase1_budget([2]) == 32  # model supersedes the deque
    sched.budget_model.observe("er", 0, [3, 3])
    assert sched._phase1_budget([0]) == 4
    assert sched._phase1_budget([0, 2]) == 32  # covering max over buckets
    pinned = AdaptiveScheduler(
        mesh11(), csr, max_iters=64, phase1_iters=2, family="er"
    )
    pinned.budget_model.observe("er", 2, [30] * 4)
    assert pinned._phase1_budget([2]) == 2  # pin bypasses the learner


def test_pinned_budget_bypasses_learning_pads_never_update():
    """phase1_iters pins the budget AND keeps the model untouched; with
    learning on, the model sees exactly the real morsels of a chunked
    batch — chunk-pad morsels (0-iteration inert slots) never land in
    any bucket's window (the per-bucket form of the pad guard)."""
    csr, _ = skew_graph("powerlaw", paths=())
    srcs = np.asarray([3, 9, 17], np.int32)
    pinned = AdaptiveScheduler(
        mesh11(), csr, max_iters=64, phase1_iters=2, max_inflight=2
    )
    out = pinned.query(srcs)
    assert out.phase1_budget == 2
    assert pinned.budget_model.n_samples == 0  # learner bypassed
    assert pinned.budget_model.mispredicts.observed == 0
    assert out.budget_observed == 3  # counters still see the real morsels

    learning = AdaptiveScheduler(
        mesh11(), csr, max_iters=64, max_inflight=2
    )
    out2 = learning.query(srcs)  # chunks of 2: last chunk is 1 real + 1 pad
    assert learning.budget_model.n_samples == 3  # pads excluded
    assert out2.budget_observed == 3
    trips = np.asarray(out2.result.iterations)[:3]
    for (fam, bucket), win in learning.budget_model._windows.items():
        assert fam is None
        assert all(t in trips for t in win)
        assert 0 not in win  # no 0-iteration pad morsels


def test_budget_too_low_counts_every_real_morsel():
    """A budget forced to 1 sits below every real morsel's convergence
    depth: each one survives phase 1 and counts as a too_low mispredict
    (and nothing counts too_high / inert)."""
    csr, heads = skew_graph("powerlaw")
    srcs = np.asarray([heads[0], heads[1], 3, 9], np.int32)
    for s in srcs:  # premise: every source needs >= 2 IFE iterations
        assert bfs_levels(csr, [int(s)]).max() >= 2
    sched = AdaptiveScheduler(mesh11(), csr, max_iters=64, phase1_iters=1)
    out = sched.query(srcs)
    assert out.phase1_budget == 1
    assert out.budget_too_low == len(srcs) == out.budget_observed
    assert out.budget_too_high == 0 and out.budget_inert_slots == 0
    assert out.redispatched == len(srcs)
    assert sched.stats.budget_too_low == len(srcs)
    assert sched.stats.budget_mispredict_rate == 1.0


def test_budget_too_high_counts_inert_spin_slots():
    """A budget forced past every morsel's oracle trip count converges
    everything in phase 1 and books the slack as inert-spin slots; the
    morsels a strictly smaller pow2 budget would have covered count
    too_high. Counters accumulate across batches in SchedulerStats."""
    csr, _ = skew_graph("powerlaw", paths=())
    sched = AdaptiveScheduler(mesh11(), csr, max_iters=64, phase1_iters=64)
    srcs = np.asarray([3, 9, 17], np.int32)
    out = sched.query(srcs)
    trips = np.asarray(out.result.iterations)[: len(srcs)]
    assert (trips * 2 < 64).all()  # shallow component: far under budget
    assert out.redispatched == 0 and out.budget_too_low == 0
    assert out.budget_too_high == len(srcs)
    assert out.budget_inert_slots == int((64 - trips).sum())
    sched.query(srcs)  # accumulate
    assert sched.stats.budget_too_high == 2 * len(srcs)
    assert sched.stats.budget_inert_slots == 2 * int((64 - trips).sum())
    assert sched.stats.budget_observed == 2 * len(srcs)
    assert sched.stats.budget_mispredict_rate == 1.0
    fresh = AdaptiveScheduler(mesh11(), csr, max_iters=64)
    assert fresh.stats.budget_observed == 0  # fresh stats start clean
    assert fresh.stats.budget_mispredict_rate == 0.0


def test_online_refit_matches_offline_fit_and_serves():
    """The in-flight refit must equal fit_direction_thresholds run on the
    scheduler's own accumulated trace (same decision boundaries), and the
    refitted table must be served through backend="recommend" without
    moving results."""
    from repro.core import fit_direction_thresholds

    csr, heads = skew_graph("powerlaw")
    sched = AdaptiveScheduler(
        mesh11(), csr, max_iters=64, family="powerlaw",
        online_adapt=True, refit_every=2,
    )
    srcs = np.asarray([heads[0], 3, 9, 20], np.int32)
    before = np.asarray(sched.query(srcs).result.state.levels)
    sched.query(np.asarray([5, 11, 40], np.int32))  # triggers the refit
    assert sched.stats.refits >= 1
    fitted = sched.direction_thresholds
    assert fitted is not None and fitted.table
    offline = fit_direction_thresholds(sched.online_trace())
    assert dict(fitted.table) == dict(offline.table)
    # next batch serves the fitted alpha/beta (recommend path) — results
    # must stay bit-identical to the pre-refit run
    after = np.asarray(sched.query(srcs).result.state.levels)
    np.testing.assert_array_equal(before, after)


def test_explicit_thresholds_are_pinned_against_refit():
    """A caller-supplied threshold table must survive the auto-refit
    cadence untouched (serve --thresholds would otherwise be silently
    replaced by the live fit); a manual refit_thresholds() call still
    overrides the pin."""
    from repro.core import DirectionThresholds

    csr, _ = skew_graph("powerlaw", paths=())
    pinned_table = DirectionThresholds(table={("powerlaw", 2): (3.0, 5.0)})
    sched = AdaptiveScheduler(
        mesh11(), csr, max_iters=64, family="powerlaw",
        direction_thresholds=pinned_table, online_adapt=True, refit_every=1,
    )
    for _ in range(3):  # cadence would refit every batch if unpinned
        sched.query(np.asarray([3, 9], np.int32))
    assert sched.direction_thresholds is pinned_table
    assert sched.stats.refits == 0
    sched.refit_thresholds()  # manual override still works
    assert sched.direction_thresholds is not pinned_table
    assert sched.stats.refits == 1
