"""The serving loop's tracing counters and engine program names:
``ServingStats.queue_wait_s`` / ``dispatched_queries`` (submit to the launch
of a query's batch), ``EngineCache.compile_s`` (host seconds of launches
that compile), and the ``engine_<kind>_<policy>_<backend>`` names the
profiler's ``XLA Modules`` line shows."""
import numpy as np
import pytest

from repro.graph.csr import csr_from_edges
from repro.graph.generators import powerlaw
from repro.launch.mesh import make_mesh
from repro.runtime.dispatch import EngineCache, QueryDispatcher
from repro.runtime.service import ServingLoop


class ManualClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


@pytest.fixture(scope="module")
def csr():
    src, dst = powerlaw(200, 6.0, seed=1).edge_list()
    return csr_from_edges(200, src, dst)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_queue_wait_is_submit_to_launch(csr):
    clock = ManualClock()
    loop = ServingLoop(_mesh(), csr, backend="ell_push", family="powerlaw",
                       max_iters=64, clock=clock)
    loop.submit(np.array([1, 2], np.int32), qid="a")
    clock.t += 0.25
    loop.submit(np.array([3], np.int32), qid="b")
    clock.t += 0.5
    loop.pump()
    loop.drain()
    # solo batches dispatched at t0 = 1000.75: a waited 0.75 s, b 0.5 s
    assert loop.stats.dispatched_queries == 2
    assert loop.stats.queue_wait_s == pytest.approx(1.25)
    loop.submit(np.array([4], np.int32), qid="c")
    loop.pump()
    loop.drain()
    assert loop.stats.dispatched_queries == 3
    assert loop.stats.queue_wait_s == pytest.approx(1.25)


def test_compile_s_counts_only_launches_that_compile(csr):
    disp = QueryDispatcher(_mesh(), csr, backend="ell_push",
                           family="powerlaw", online_adapt=False,
                           phase1_iters=16, pad_pow2_morsels=True)
    assert disp.cache.compile_s == 0.0
    srcs = np.arange(8, dtype=np.int32)
    disp.query(srcs, policy="ntks")
    first = disp.cache.compile_s
    events = disp.cache.compile_events
    assert first > 0.0 and events > 0
    disp.query(srcs[::-1].copy(), policy="ntks")  # same engines, same shape
    assert disp.cache.compile_events == events
    assert disp.cache.compile_s == first


def test_launch_adds_time_only_when_compile_events_rose():
    cache = EngineCache()
    with cache.launch(cache.compile_events, "static"):
        pass
    assert cache.compile_s == 0.0
    before = cache.compile_events
    cache.note_shape("k", (1,))  # a first-seen shape: the next call compiles
    with cache.launch(before, "static"):
        pass
    assert cache.compile_s > 0.0


def test_engine_programs_are_named_by_kind_policy_backend(csr):
    disp = QueryDispatcher(_mesh(), csr, backend="block_mxu",
                           family="powerlaw", pad_pow2_morsels=True)
    disp.query(np.arange(64, dtype=np.int32))  # one nTkMS lane morsel
    static = QueryDispatcher(_mesh(), csr, backend="dopt", family="powerlaw",
                             adaptive=False)
    static.query(np.array([0], np.int32), policy="ntks")
    names = {eng.fn.__name__ for _, eng in disp.cache.items()}
    names |= {eng.fn.__name__ for _, eng in static.cache.items()}
    assert "engine_phase1_ntkms_block_mxu" in names
    assert "engine_static_ntks_dopt_binned" in names
    for key, eng in list(disp.cache.items()) + list(static.cache.items()):
        assert eng.fn.__name__.startswith(f"engine_{key.kind}_")
