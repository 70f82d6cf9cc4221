"""A whole run on the CPU with the harness's look for a chip skipped: sound,
it reads correct; with the timed path broken underneath, or with the
control in the program's place, it reads not correct.

Faults that a one-chip BFS cell can have: a step that returns its state
unchanged; half of a batch left out; an answer altered where it is
produced, or an unreached node given an int32 sentinel instead of -1.
(There is no exchange between chips on one chip.)
"""
import time

import numpy as np
import pytest

from chip_bench import harness

TINY = {"generator": "powerlaw", "family": "powerlaw", "n_nodes": 300,
        "avg_degree_per_direction": 22.0, "alpha": 1.8, "symmetric": True,
        "graph_seed": 0}
MIXES = {
    "open": {"kind": "poisson", "rate_qps": 20.0, "sources_per_query": 1},
    "closed": {"kind": "closed", "clients": 1, "sources_per_query": 64},
}
# so sparse that many nodes are never reached
SPARSE = dict(TINY, avg_degree_per_direction=1.0)
SEED = 2**31 + 17


def _run(kind, seconds=1.5, config=TINY, **kw):
    cell = harness.Cell(f"tiny-{kind}", config, MIXES[kind], 1, [], [])
    return harness.run_cell(cell, SEED, seconds, False, time.perf_counter(),
                            require_chip=False, **kw)


@pytest.mark.parametrize("kind", ["open", "closed"])
def test_sound_run_is_correct_and_the_control_is_not(kind):
    r = _run(kind, control=True)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["correct"] is True
    assert r["checks"]["mismatched_levels"]["value"] == 0
    assert r["checks"]["missing_results"]["value"] == 0
    assert r["control"]["mismatched_levels"] > r["control"]["limit"]


def _state_unchanged(monkeypatch):
    from repro.core import edge_compute

    for cls in (edge_compute.SPLengths, edge_compute.MSBFSLengths):
        monkeypatch.setattr(cls, "apply",
                            staticmethod(lambda state, reached, it: state))


def _answer_altered(monkeypatch):
    from repro.runtime import service

    real = service.unpack_levels

    def altered(levels, spans, n_nodes, packed):
        out = real(levels, spans, n_nodes, packed)
        for rows in out.values():
            rows[0, -1] += 1
        return out

    monkeypatch.setattr(service, "unpack_levels", altered)


def _unreached_as_int32_max(monkeypatch):
    from repro.runtime import service

    real = service.unpack_levels

    def sentinel(levels, spans, n_nodes, packed):
        out = real(levels, spans, n_nodes, packed)
        for rows in out.values():
            rows[...] = np.where(rows < 0, np.iinfo(np.int32).max, rows)
        return out

    monkeypatch.setattr(service, "unpack_levels", sentinel)


def _half_batch_left_out(monkeypatch):
    from repro.runtime.dispatch import QueryDispatcher

    real = QueryDispatcher.begin_batch

    def half(self, sources, *a, **k):
        s = np.asarray(sources)
        keep = s[: max(1, len(s) // 2)]
        return real(self, np.resize(keep, len(s)), *a, **k)

    monkeypatch.setattr(QueryDispatcher, "begin_batch", half)


@pytest.mark.parametrize("kind,fault", [
    ("open", _state_unchanged),
    ("closed", _state_unchanged),
    ("open", _answer_altered),
    ("closed", _answer_altered),
    ("closed", _half_batch_left_out),
])
def test_broken_timed_path_reads_not_correct(kind, fault, monkeypatch):
    fault(monkeypatch)
    r = _run(kind, seconds=1.0)
    assert r["correct"] is False
    assert r["checks"]["mismatched_levels"]["value"] > 0
    assert r["failed"] > 0


@pytest.mark.parametrize("kind", ["open", "closed"])
def test_unreached_sentinel_reads_not_correct(kind, monkeypatch):
    """A wrong value that a narrowing cast would wrap onto -1."""
    sound = _run(kind, seconds=1.0, config=SPARSE)
    assert sound["correct"] is True
    _unreached_as_int32_max(monkeypatch)
    r = _run(kind, seconds=1.0, config=SPARSE)
    assert r["correct"] is False
    assert r["checks"]["mismatched_levels"]["value"] > 0
