"""Host reads of batch results, prefetched at launch.

``QueryDispatcher.begin_batch`` enqueues the host copy of every result
leaf that settle and finalize will read (``HostReads``), right behind the
engine launch, and ``SchedulerStats`` counts each leaf read to the host as
``d2h_prefetched`` (copy enqueued at launch) or ``d2h_blocking`` (a full
round trip). Which leaves follows from what the launch can see: the
state layout, whether stats are collected, the query kind's result
leaves. Results are unchanged; only the time of each copy moves.
"""
import numpy as np
import pytest

from oracle import bfs_levels

from repro.core import QUERY_KINDS
from repro.graph.csr import csr_from_edges
from repro.graph.generators import powerlaw
from repro.launch.mesh import make_mesh
from repro.runtime.dispatch import QueryDispatcher
from repro.runtime.service import ServingLoop


@pytest.fixture(scope="module")
def csr():
    src, dst = powerlaw(300, 8.0, alpha=1.8, seed=3).edge_list()
    return csr_from_edges(300, src, dst)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _closed_loop(loop, csr, queries, width=64, seed=0):
    """One caller: each 64-source query is sent once the last came back."""
    rng = np.random.default_rng(seed)
    sent = []
    for i in range(queries):
        srcs = rng.integers(0, csr.n_nodes, width).astype(np.int32)
        loop.submit(srcs, qid=f"q{i}")
        loop.drain()
        sent.append((f"q{i}", srcs))
    return sent


def test_closed_loop_reach_reads_only_prefetched_leaves(csr):
    # budget pinned at max_iters: no survivors, every batch one phase-1
    # launch with the stats tap on (online_adapt)
    loop = ServingLoop(_mesh(), csr, backend="ell_push", family="powerlaw",
                       max_iters=64, phase1_iters=64, online_adapt=True)
    sent = _closed_loop(loop, csr, queries=3)
    st = loop.dispatcher.stats
    assert loop.stats.batches == 3
    assert st.hybrid_runs == 3 and st.redispatched == 0
    assert all(k.policy.lanes == 64 for k in loop.dispatcher.cache.keys())
    # frontier, iterations, stats and levels, each copied at launch
    assert st.d2h_blocking == 0
    assert st.d2h_prefetched == 4 * loop.stats.batches
    for qid, srcs in sent:
        rows = loop.results[qid]
        for i, s in enumerate(srcs):
            np.testing.assert_array_equal(rows[i], bfs_levels(csr, [s]))


def test_begin_prefetch_set_replicated_reach(csr):
    d = QueryDispatcher(_mesh(), csr, backend="ell_push", family="powerlaw",
                        max_iters=64, phase1_iters=64)
    inflight = d.begin_batch(np.arange(64, dtype=np.int32))
    assert inflight.kind == "hybrid"
    assert list(inflight.reads.prefetched) == [
        "frontier", "iterations", "stats", "levels"
    ]
    d.settle_batch(inflight).finalize()


def test_survivor_batch_matches_static_engine(csr):
    srcs = np.arange(0, 256, 4, dtype=np.int32)
    served = {}
    for name, kw in {
        "survivors": dict(phase1_iters=1),  # every morsel outlives phase 1
        "static": dict(adaptive=False),
    }.items():
        loop = ServingLoop(_mesh(), csr, backend="ell_push",
                           family="powerlaw", max_iters=64, **kw)
        loop.submit(srcs, qid="q")
        served[name] = loop.drain()["q"]
        st = loop.dispatcher.stats
        if name == "survivors":
            assert st.redispatched > 0
            # phase 1's frontier, iterations, stats and levels, phase 2's
            # iterations and stats; the stitch's reads are round trips
            assert st.d2h_prefetched == 6
            assert st.d2h_blocking > 0
        else:
            # iterations and levels
            assert (st.d2h_prefetched, st.d2h_blocking) == (2, 0)
    assert served["survivors"].dtype == served["static"].dtype
    np.testing.assert_array_equal(served["survivors"], served["static"])
    for i, s in enumerate(srcs):
        np.testing.assert_array_equal(served["static"][i],
                                      bfs_levels(csr, [s]))


@pytest.mark.parametrize("kind", ["ppr", "pattern_counts"])
def test_non_reach_kind_prefetches_its_result_leaves(csr, kind):
    d = QueryDispatcher(_mesh(), csr, max_iters=512, phase1_iters=512,
                        pad_pow2_morsels=True)
    loop = ServingLoop(dispatcher=d)
    seen = []
    begin = d.begin_batch
    d.begin_batch = lambda *a, **k: seen.append(begin(*a, **k)) or seen[-1]
    loop.submit([5, 9], query_kind=kind, qid="q")
    loop.drain()
    (inflight,) = seen
    leaves = QUERY_KINDS[kind].result_leaves
    state = set(type(inflight.payload["out1"][0].state)._fields)
    prefetched = set(inflight.reads.prefetched)
    assert prefetched & state == {"frontier", *leaves}
    assert prefetched - state == {"iterations", "stats"}
    assert d.stats.d2h_blocking == 0
    assert d.stats.d2h_prefetched == len(prefetched)


def test_sharded_layout_keeps_state_on_device(csr):
    d = QueryDispatcher(_mesh(), csr, backend="ell_push", family="powerlaw",
                        max_iters=64, phase1_iters=64)
    srcs = np.arange(8, dtype=np.int32)
    inflight = d.begin_batch(srcs, state_layout="sharded")
    assert inflight.kind == "hybrid"
    assert set(inflight.reads.prefetched) == {"iterations", "stats"}
    out = d.settle_batch(inflight).finalize()
    levels = np.asarray(out.result.state.levels)
    for i, s in enumerate(srcs):
        np.testing.assert_array_equal(levels[i, : csr.n_nodes],
                                      bfs_levels(csr, [s]))
    # iterations and stats came from the enqueued copies; the survivor
    # test read its on-device any()
    assert (d.stats.d2h_prefetched, d.stats.d2h_blocking) == (2, 1)


def test_begin_span_carries_the_prefetch_count(csr, tmp_path):
    import jax
    from jax.profiler import ProfileData

    d = QueryDispatcher(_mesh(), csr, backend="ell_push", family="powerlaw",
                        max_iters=64, phase1_iters=64)
    srcs = np.arange(64, dtype=np.int32)
    d.query(srcs)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        d.query(srcs)
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.glob("**/*.xplane.pb")
    begins = [
        dict(e.stats)
        for plane in ProfileData.from_file(str(pb)).planes
        for line in plane.lines
        for e in line.events
        if e.name == "repro.dispatch.begin"
    ]
    assert begins == [{"batch": 1, "prefetch": 4}]
