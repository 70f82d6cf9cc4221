"""Layered serving-core tests (ISSUE 6).

Covers the admission layer's edge cases (zero-source submit, duplicate
qid, quota-exhausted tenant, deadline expired at admission,
flush-during-drain) with every result asserted bit-identical to a
synchronous one-batch-at-a-time run of the same stream; the LanePacker
repack-on-arrival contract; the EngineCache public mapping surface;
deadline-aware pack eviction + hopeless-query shedding; the overlap
pipeline's occupancy/warm-cold telemetry; and the ISSUE-6 determinism
lock — the async overlapped loop replays a seeded stream bit-identically
(results, learned budgets, refit thresholds, mispredict counters) to the
strictly serial loop and the synchronous AdaptiveScheduler façade.
"""
import functools
from pathlib import Path

import numpy as np
import pytest

from oracle import bfs_levels

from repro.core.msbfs import LanePacker
from repro.graph.csr import csr_from_edges
from repro.graph.generators import powerlaw
from repro.launch.mesh import make_mesh
from repro.runtime.admission import (
    AdmissionQueue,
    SHED_EXPIRED,
    SHED_HOPELESS,
    SHED_QUOTA,
)
from repro.runtime.scheduler import AdaptiveScheduler
from repro.runtime.service import ServingLoop


@functools.lru_cache(maxsize=None)
def mesh11():
    return make_mesh((1, 1), ("data", "model"))


@functools.lru_cache(maxsize=None)
def serve_graph(n_main: int = 160, paths: tuple = (40,), seed: int = 0):
    """Small-diameter powerlaw main component plus long-path straggler
    components (same shape as test_scheduler.skew_graph): path-head
    sources are deep/low-degree, main-component sources shallow/denser —
    distinct budget-model buckets with very different learned depths,
    which is what the deadline-eviction math keys on."""
    main = powerlaw(n_main, 5.0, seed=seed)
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


class ManualClock:
    """Injectable clock for deterministic admission decisions."""

    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt_s: float) -> None:
        self.t += dt_s


def _loop(csr, **kw):
    kw.setdefault("backend", "dopt")
    kw.setdefault("family", "powerlaw")
    kw.setdefault("max_iters", 64)
    return ServingLoop(mesh11(), csr, **kw)


def _facade(csr, **kw):
    kw.setdefault("backend", "dopt")
    kw.setdefault("family", "powerlaw")
    kw.setdefault("max_iters", 64)
    return AdaptiveScheduler(mesh11(), csr, **kw)


def _sync_reference(csr, rounds, **kw):
    """The satellite's reference: the same stream served synchronously,
    one flush per submission round, through the AdaptiveScheduler façade."""
    sched = _facade(csr, **kw)
    out = {}
    for round_ in rounds:
        for qid, s in round_:
            sched.submit(s, qid=qid)
        out.update(sched.flush())
    return sched, out


# ---------------------------------------------------------------------------
# LanePacker: repack-on-arrival
# ---------------------------------------------------------------------------

def test_lane_packer_pack_evict_repack():
    pk = LanePacker(lanes=64)
    a = np.arange(5, dtype=np.int32)
    b = np.arange(10, 13, dtype=np.int32)
    c = np.arange(20, 24, dtype=np.int32)
    pk.add("qa", a)
    pk.add("qb", b)
    pk.add("qc", c)
    assert len(pk) == 3 and pk.n_sources == 12 and pk.n_morsels == 1
    assert "qb" in pk and pk.qids == ["qa", "qb", "qc"]
    flat, spans = pk.pack()
    np.testing.assert_array_equal(flat, np.concatenate([a, b, c]))
    assert spans == {"qa": (0, 5), "qb": (5, 8), "qc": (8, 12)}

    # eviction is a pure deletion: survivors keep arrival order, so their
    # sources (and therefore result rows) are byte-identical post-repack
    got = pk.evict("qb")
    np.testing.assert_array_equal(got, b)
    flat2, spans2 = pk.pack()
    np.testing.assert_array_equal(flat2, np.concatenate([a, c]))
    assert spans2 == {"qa": (0, 5), "qc": (5, 9)}
    assert pk.evict("missing") is None

    with pytest.raises(ValueError):
        pk.add("qa", a)  # duplicate qid in one pack


# ---------------------------------------------------------------------------
# EngineCache public mapping surface
# ---------------------------------------------------------------------------

def test_engine_cache_public_api():
    csr, _ = serve_graph()
    sched = _facade(csr, online_adapt=False)
    sched.query(np.arange(4, dtype=np.int32))
    cache = sched.cache
    assert len(cache) > 0
    keys = list(cache.keys())
    assert list(iter(cache)) == keys
    assert all(k in cache for k in keys)
    assert [k for k, _ in cache.items()] == keys
    assert all(cache.get(k) is not None for k in keys)
    assert cache.get("no-such-key", "fallback") == "fallback"
    assert sum(cache.count_by_kind(k.kind) for k in set(keys)) >= len(keys)
    # the public surface is a view, not a copy: a fresh compile shows up
    n = len(cache)
    sched.query(np.arange(4, dtype=np.int32), returns_paths=True)
    assert len(cache) > n and len(list(cache.keys())) == len(cache)


def test_pow2_morsel_padding_bit_identical_and_shape_tracked():
    """The serving dispatcher's pow2 morsel padding: a 3-morsel batch runs
    as 4 morsels (pad morsels inert), results bit-identical to the exact-
    shape dispatcher; first-seen morsel shapes are counted apart from
    build misses so serving's warm/cold split can see XLA retraces."""
    from repro.runtime.dispatch import QueryDispatcher

    csr, _ = serve_graph()
    # main-component sources only: everything converges inside the pinned
    # phase-1 budget, so the only engine in play is phase 1 and the miss
    # ledger below isn't confounded by resume/gang compiles
    srcs = np.asarray(
        np.random.default_rng(11).integers(0, 160, 160), np.int32
    )  # 160 sources / 64 lanes = 3 morsels -> pow2-padded to 4
    # phase1_iters pinned: the global-p90 fallback budget must not drift
    # between calls below (a budget change is a legitimate build miss,
    # but this test isolates the shape ledger from it)
    exact = QueryDispatcher(
        mesh11(), csr, backend="dopt", family="powerlaw",
        online_adapt=False, phase1_iters=16,
    )
    padded = QueryDispatcher(
        mesh11(), csr, backend="dopt", family="powerlaw",
        online_adapt=False, phase1_iters=16, pad_pow2_morsels=True,
    )
    out_e = exact.query(srcs, policy="ntkms")
    out_p = padded.query(srcs, policy="ntkms")
    lv_e = np.asarray(out_e.result.state.levels)
    lv_p = np.asarray(out_p.result.state.levels)
    assert lv_e.shape[0] == 3 and lv_p.shape[0] == 4
    np.testing.assert_array_equal(lv_e, lv_p[:3])
    # pad morsel: inert, zero iterations
    assert np.asarray(out_p.result.iterations)[3] == 0
    # shape ledger: first call noted one shape per engine used; replaying
    # the same batch adds none, a new morsel count adds one without a
    # build miss — and compile_events moves while misses does not
    cache = padded.cache
    shapes0, misses0 = cache.shape_misses, cache.misses
    assert shapes0 > 0
    padded.query(srcs, policy="ntkms")
    assert cache.shape_misses == shapes0 and cache.misses == misses0
    padded.query(srcs[:64], policy="ntkms")  # 1 morsel: new phase-1 shape
    assert cache.shape_misses > shapes0
    assert cache.misses == misses0
    assert cache.compile_events == cache.misses + cache.shape_misses


# ---------------------------------------------------------------------------
# Admission edge cases — each bit-identical to the synchronous reference
# ---------------------------------------------------------------------------

def test_zero_source_submit_completes_empty():
    csr, _ = serve_graph()
    s = np.arange(4, dtype=np.int32)
    loop = _loop(csr, overlap=True)
    t_empty = loop.submit(np.zeros(0, np.int32), qid="empty")
    t_real = loop.submit(s, qid="real")
    assert t_empty.admitted and t_empty.done and t_real.admitted
    results = loop.drain()
    assert results["empty"].shape == (0, csr.n_nodes)
    assert results["empty"].dtype == np.int32
    _, ref = _sync_reference(
        csr, [[("empty", np.zeros(0, np.int32)), ("real", s)]]
    )
    for qid in ("empty", "real"):
        np.testing.assert_array_equal(results[qid], ref[qid])
    assert loop.admission.stats.zero_source == 1


def test_duplicate_qid_raises_until_completed():
    csr, _ = serve_graph()
    s = np.arange(4, dtype=np.int32)
    loop = _loop(csr)
    loop.submit(s, qid="dup")
    with pytest.raises(ValueError):
        loop.submit(s + 1, qid="dup")  # still in flight
    loop.drain()
    loop.submit(s + 1, qid="dup")  # completed: the qid is free again
    loop.drain()
    sched = _facade(csr)
    sched.submit(s, qid="dup")
    with pytest.raises(ValueError):
        sched.submit(s, qid="dup")


def test_quota_exhausted_tenant_sheds_not_others():
    csr, _ = serve_graph()
    rng = np.random.default_rng(3)
    qs = [rng.integers(0, 160, 4).astype(np.int32) for _ in range(4)]
    loop = _loop(csr, tenant_quota=2)
    t0 = loop.submit(qs[0], tenant="busy", qid="a")
    t1 = loop.submit(qs[1], tenant="busy", qid="b")
    t2 = loop.submit(qs[2], tenant="busy", qid="c")  # over quota: shed
    t3 = loop.submit(qs[3], tenant="calm", qid="d")  # other tenant: fine
    assert t0.admitted and t1.admitted and t3.admitted
    assert not t2.admitted and t2.shed_reason == SHED_QUOTA
    results = loop.drain()
    assert "c" not in results
    assert loop.stats.tenant("busy").shed == 1
    assert loop.stats.tenant("calm").shed == 0
    assert loop.admission.stats.sheds_by_reason[SHED_QUOTA] == 1
    # quota is released on completion: the tenant can submit again
    assert loop.submit(qs[2], tenant="busy", qid="c2").admitted
    results = loop.drain()
    # admitted queries are served bit-identically to the sync reference
    _, ref = _sync_reference(
        csr,
        [[("a", qs[0]), ("b", qs[1]), ("d", qs[3])], [("c2", qs[2])]],
    )
    for qid in ("a", "b", "d", "c2"):
        np.testing.assert_array_equal(results[qid], ref[qid])


def test_deadline_expired_at_admission_and_at_plan():
    csr, _ = serve_graph()
    clock = ManualClock()
    s = np.arange(4, dtype=np.int32)
    loop = _loop(csr, clock=clock)
    # expired before it was even queued (non-positive SLO)
    t = loop.submit(s, deadline_ms=0.0, qid="late")
    assert not t.admitted and t.shed_reason == SHED_EXPIRED
    # expires while queued: admitted, then shed at plan time
    loop.submit(s, deadline_ms=5.0, qid="stale")
    loop.submit(s + 8, qid="live")
    clock.advance(0.050)  # 50 ms > 5 ms deadline
    results = loop.drain()
    assert "late" not in results and "stale" not in results
    assert "live" in results
    assert loop.admission.stats.sheds_by_reason[SHED_EXPIRED] == 2
    _, ref = _sync_reference(csr, [[("live", s + 8)]])
    np.testing.assert_array_equal(results["live"], ref["live"])


def test_flush_during_drain_serves_followup():
    csr, _ = serve_graph()
    s0 = np.arange(4, dtype=np.int32)
    s1 = np.arange(50, 54, dtype=np.int32)
    state = {"fired": False}

    def on_result(qid, levels):
        if not state["fired"]:  # submit from inside result delivery
            state["fired"] = True
            loop.submit(s1, qid="followup")

    loop = _loop(csr, overlap=True, on_result=on_result)
    loop.submit(s0, qid="first")
    results = loop.drain()
    assert state["fired"]
    assert set(results) >= {"first", "followup"}
    _, ref = _sync_reference(csr, [[("first", s0)], [("followup", s1)]])
    for qid in ("first", "followup"):
        np.testing.assert_array_equal(results[qid], ref[qid])


# ---------------------------------------------------------------------------
# Deadline-aware pack eviction / load shedding
# ---------------------------------------------------------------------------

def test_deadline_eviction_and_hopeless_shed():
    """A tight-deadline shallow query packed next to a deep straggler
    cannot survive the pack's slowest lane: it must be EVICTED to a solo
    batch (and still answer correctly); a query whose deadline even a
    solo batch would blow is shed as hopeless, not executed."""
    csr, heads = serve_graph()
    clock = ManualClock()
    loop = _loop(csr, clock=clock, refit_every=1000)
    rng = np.random.default_rng(5)
    # mid-degree main-component nodes: a degree bucket the straggler head
    # (degree 1) does NOT share, so the learned depths stay distinct
    deg = np.asarray(csr.degrees)[:160]
    mid = np.nonzero((deg >= 4) & (deg < 8))[0].astype(np.int32)
    assert len(mid) >= 8
    # warm the budget model: shallow mid-degree batches + one deep
    # straggler batch, served solo (no deadlines involved yet)
    loop.submit(mid[:8])
    for i in range(2):
        loop.submit(rng.integers(0, 160, 8).astype(np.int32))
    loop.submit(np.asarray([heads[0]], np.int32))
    loop.drain()
    assert loop.dispatcher.depth_hint(np.asarray([heads[0]]), 1) is not None
    # the manual clock froze wall time, so the measured ms-per-iteration
    # EWMA never warmed — pin it (white-box) to make predictions live
    loop._ms_per_iter = 1.0
    deep_depth = loop.dispatcher.depth_hint(np.asarray([heads[0]]), 1)
    shallow = mid[:4]
    shallow_depth = loop.dispatcher.depth_hint(shallow, 1)
    assert shallow_depth < deep_depth  # distinct buckets, distinct depths

    # pool > 64 sources so recommend_policy packs ntkms, with the deep
    # straggler inside: pack slowest-lane estimate = deep_depth ms
    fill = [rng.integers(0, 160, 31).astype(np.int32) for _ in range(2)]
    loop.submit(fill[0], qid="f0")
    loop.submit(fill[1], qid="f1")
    loop.submit(np.asarray([heads[0]], np.int32), qid="deep")
    # slack between solo time and pack time: must be evicted, then served
    mid_ms = (shallow_depth + deep_depth) / 2.0
    loop.submit(shallow, qid="tight", deadline_ms=mid_ms)
    # slack under even the solo estimate: hopeless, shed at plan
    loop.submit(shallow, qid="doomed",
                deadline_ms=max(0.5, shallow_depth / 2.0))
    results = loop.drain()
    assert loop.admission.stats.evictions == 1
    assert loop.admission.stats.sheds_by_reason[SHED_HOPELESS] == 1
    assert "doomed" not in results and "tight" in results
    assert loop.stats.deadline_misses == 0  # frozen clock: nothing late
    # the evicted query's solo answer is still the exact BFS
    ref = np.stack([bfs_levels(csr, int(x)) for x in shallow])
    np.testing.assert_array_equal(results["tight"], ref)
    # pack members unaffected by the eviction repack
    ref_f0 = np.stack([bfs_levels(csr, int(x)) for x in fill[0]])
    np.testing.assert_array_equal(results["f0"], ref_f0)


# ---------------------------------------------------------------------------
# Overlap pipeline telemetry
# ---------------------------------------------------------------------------

def test_overlap_occupancy_and_warm_cold_split():
    csr, _ = serve_graph()
    rng = np.random.default_rng(11)
    loop = _loop(csr, overlap=True)
    for r in range(3):
        for q in range(2):
            loop.submit(rng.integers(0, 160, 4).astype(np.int32),
                        tenant=f"t{q}")
        loop.pump()
    loop.drain()
    st = loop.stats
    assert st.batches >= 6 and st.finalizes == st.batches
    # sub-64-source solo batches pump in pairs: every first-of-pair
    # finalize hides behind the second's phase 1
    assert st.overlapped_finalizes > 0
    assert 0.0 < st.overlap_occupancy <= 1.0
    assert st.cold_batches >= 1  # first batch compiled
    warm = st._all(warm=True)
    assert len(warm) < len(st._all(warm=False))
    assert st.cold_ms > 0.0
    # strictly serial loop never overlaps
    serial = _loop(csr, overlap=False)
    serial.submit(rng.integers(0, 160, 4).astype(np.int32))
    serial.submit(rng.integers(0, 160, 4).astype(np.int32))
    serial.drain()
    assert serial.stats.overlapped_finalizes == 0
    assert serial.stats.overlap_occupancy == 0.0


# ---------------------------------------------------------------------------
# ISSUE-6 determinism lock: async loop ≡ serial loop ≡ synchronous façade
# ---------------------------------------------------------------------------

def _replay_rounds(heads):
    """Seeded multi-round stream mixing shallow sources with straggler
    heads — the PR-5 replay corpus shape, as (qid, sources) rounds."""
    rng = np.random.default_rng(7)
    rounds = []
    for r in range(5):
        round_ = []
        for q in range(2):
            fill = rng.integers(0, 160, 4).astype(np.int32)
            if (r + q) % 2 == 0:
                fill = np.concatenate(
                    [[heads[r % len(heads)]], fill[:3]]
                ).astype(np.int32)
            round_.append((f"r{r}q{q}", fill))
        rounds.append(round_)
    return rounds


@pytest.mark.slow
def test_replay_async_loop_bit_identical_to_sync_facade():
    """The determinism lock: the overlapped async loop, the strictly
    serial loop, and the synchronous AdaptiveScheduler façade must
    produce bit-identical results, learned budgets, accumulated sample
    traces, refit thresholds, and mispredict counters on the same seeded
    admission order — the overlap moves WHEN the host works, never what
    any batch computes or what the learners observe."""
    csr, heads = serve_graph()
    rounds = _replay_rounds(heads)
    kw = dict(online_adapt=True, refit_every=2)

    def run_loop(overlap):
        loop = _loop(csr, overlap=overlap, **kw)
        for round_ in rounds:
            for qid, s in round_:
                loop.submit(s, qid=qid)
            loop.pump()
        loop.drain()
        loop.dispatcher.refit_thresholds()
        return loop.dispatcher, loop.results

    async_d, async_res = run_loop(overlap=True)
    serial_d, serial_res = run_loop(overlap=False)
    facade, facade_res = _sync_reference(csr, rounds, **kw)
    facade.refit_thresholds()

    assert set(async_res) == set(serial_res) == set(facade_res)
    for qid in async_res:
        np.testing.assert_array_equal(async_res[qid], serial_res[qid])
        np.testing.assert_array_equal(async_res[qid], facade_res[qid])

    table = dict(async_d.direction_thresholds.table)
    assert table, "refit produced an empty table"
    for other in (serial_d, facade):
        assert table == dict(other.direction_thresholds.table)
        assert (
            async_d.budget_model.budgets(64)
            == other.budget_model.budgets(64)
        )
        assert async_d.online_trace() == other.online_trace()
        for f in ("queries", "hybrid_runs", "redispatched",
                  "budget_too_low", "budget_too_high",
                  "budget_inert_slots", "budget_observed", "refits"):
            assert getattr(async_d.stats, f) == getattr(other.stats, f), f
        m, mo = async_d.budget_model.mispredicts, other.budget_model.mispredicts
        assert (m.too_low, m.too_high, m.inert_slots, m.observed) == (
            mo.too_low, mo.too_high, mo.inert_slots, mo.observed
        )


# ---------------------------------------------------------------------------
# AdmissionQueue unit behavior
# ---------------------------------------------------------------------------

def test_admission_queue_plan_matches_legacy_batching():
    csr, _ = serve_graph()
    q = AdmissionQueue(
        n_nodes=csr.n_nodes, n_devices=1, avg_degree=csr.avg_degree
    )
    assert q.submit(np.arange(4)).qid == "q0"  # legacy qid naming
    assert q.submit(np.arange(4, 8)).qid == "q1"
    assert q.pending() == 2 and q.in_flight() == 2
    plan = q.plan()
    # 8 pooled sources: under the lane-saturation bar => one solo batch
    # per query, arrival order — the legacy per-query flush branch
    assert [pb.packed for pb in plan.batches] == [False, False]
    assert [pb.queries[0].qid for pb in plan.batches] == ["q0", "q1"]
    assert plan.batches[0].spans == {"q0": (0, 4)}
    assert q.pending() == 0 and q.in_flight() == 2  # still uncompleted
    q.complete("q0")
    q.complete("q1")
    assert q.in_flight() == 0
    # >= 64 pooled sources => ONE packed ntkms batch, spans in
    # submission order — the legacy pooled branch
    a = q.submit(np.arange(40)).qid
    b = q.submit(np.arange(40, 80)).qid
    plan = q.plan()
    assert len(plan.batches) == 1 and plan.batches[0].packed
    assert plan.batches[0].policy == "ntkms"
    assert plan.batches[0].spans == {a: (0, 40), b: (40, 80)}
    np.testing.assert_array_equal(
        plan.batches[0].sources, np.arange(80, dtype=np.int32)
    )


def test_admission_queue_capped_batches_order_and_bit_identity():
    """max_batch_sources bounds each plan round to an arrival-order
    prefix of the queue (saxml-style bucketed batching): pooled sources
    per round never exceed the cap, queries are served strictly in
    arrival order across rounds, and slicing a stream into capped
    batches does not move a single result bit."""
    csr, heads = serve_graph()
    q = AdmissionQueue(
        n_nodes=csr.n_nodes, n_devices=1, avg_degree=csr.avg_degree,
        max_batch_sources=128,
    )
    rng = np.random.default_rng(7)
    qids = [
        q.submit(rng.integers(0, 160, 32).astype(np.int32)).qid
        for _ in range(10)
    ]
    served, plans = [], 0
    while q.pending():
        plan = q.plan()
        plans += 1
        assert sum(len(pb.sources) for pb in plan.batches) <= 128
        for pb in plan.batches:
            served.extend(query.qid for query in pb.queries)
            for query in pb.queries:
                q.complete(query.qid)
    assert served == qids  # arrival order survives the capped rounds
    assert plans == 3  # 10 queries x 32 sources under a 4-query cap

    # end to end: a capped ServingLoop slices the same stream into three
    # packed batches; the uncapped synchronous façade serves it as one —
    # results must be bit-identical (straggler head included so the
    # phase-2 gang path crosses a batch boundary too)
    rng = np.random.default_rng(8)
    queries = [
        (f"c{i}", np.concatenate([
            [heads[0]] if i == 0 else np.zeros(0, np.int64),
            rng.integers(0, 160, 31 if i == 0 else 32),
        ]).astype(np.int32))
        for i in range(10)
    ]
    loop = _loop(csr, overlap=True, max_batch_sources=128,
                 online_adapt=False)
    for qid, s in queries:
        loop.submit(s, qid=qid)
    capped = loop.drain()
    assert loop.stats.batches == 3
    _, ref = _sync_reference(
        csr, [queries], online_adapt=False
    )
    for qid, _s in queries:
        np.testing.assert_array_equal(capped[qid], ref[qid])


# ---------------------------------------------------------------------------
# PPR epsilon-termination determinism (ISSUE 9 satellite)
# ---------------------------------------------------------------------------


def test_ppr_epsilon_termination_deterministic_across_stack_knobs():
    """PPR's iterate-until-epsilon exit is a pure function of the graph
    and seeds: the same seeded stream must settle to bit-identical mass,
    residuals, and iteration counts with online learning on or off and
    in the replicated or sharded engine state layout. The learners and
    layouts may move WHEN work happens (budget caps, resume, gang), but
    never the float trajectory of the converging diffusion."""
    from repro.runtime.dispatch import QueryDispatcher

    csr, heads = serve_graph()
    rng = np.random.default_rng(11)
    subs = [
        (f"p{i}", rng.integers(0, csr.n_nodes, 2).astype(np.int32))
        for i in range(4)
    ]

    # served stream: online-adapt on vs off, delivered mass rows bitwise
    def run_loop(online_adapt):
        loop = _loop(csr, online_adapt=online_adapt, max_iters=512)
        for qid, s in subs:
            loop.submit(s, qid=qid, query_kind="ppr")
        return loop.drain()

    adapt_on = run_loop(True)
    adapt_off = run_loop(False)
    assert set(adapt_on) == set(adapt_off) == {qid for qid, _ in subs}
    for qid in adapt_on:
        np.testing.assert_array_equal(adapt_on[qid], adapt_off[qid])

    # dispatcher level: every (online_adapt, layout) cell agrees on the
    # full state — mass, residual, AND the iteration count at which the
    # epsilon exit fired
    runs = {}
    for adapt in (True, False):
        for layout in ("replicated", "sharded"):
            d = QueryDispatcher(
                mesh11(), csr, max_iters=512, online_adapt=adapt,
                backend="dopt", family="powerlaw",
            )
            outs = [
                d.query(s, query_kind="ppr", state_layout=layout)
                for _qid, s in subs
            ]
            runs[(adapt, layout)] = [
                (
                    np.asarray(o.result.state.mass),
                    np.asarray(o.result.state.residual),
                    np.asarray(o.result.iterations),
                )
                for o in outs
            ]
    ref = runs[(True, "replicated")]
    from repro.core.edge_compute import PPRDiffusion

    for (mass, residual, iters) in ref:
        assert (residual <= PPRDiffusion.EPS).all()
        assert (iters < 512).all()
    for cell, got in runs.items():
        for (m0, r0, i0), (m1, r1, i1) in zip(ref, got):
            np.testing.assert_array_equal(m0, m1, err_msg=str(cell))
            np.testing.assert_array_equal(r0, r1, err_msg=str(cell))
            np.testing.assert_array_equal(i0, i1, err_msg=str(cell))


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir_fixed_or_from_env(monkeypatch, tmp_path, from_env):
    """Unset, JAX_COMPILATION_CACHE_DIR leaves the persistent cache at
    ``.jax_cache`` in the checkout, the same path on every run; set, JAX
    reads it at start-up and the helper sets no other directory."""
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        expect = str(tmp_path / "cache")
        monkeypatch.setenv(compile_cache.ENV_VAR, expect)
        jax.config.update("jax_compilation_cache_dir", expect)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        expect = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    try:
        assert compile_cache.enable_compile_cache() == expect
        assert compile_cache.enable_compile_cache() == expect
        assert jax.config.jax_compilation_cache_dir == expect
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
