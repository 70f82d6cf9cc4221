"""Trace reduction: interval union, idle gaps and their host-span labels,
on synthetic intervals and on a small trace recorded on a v5e chip."""
from pathlib import Path

import pytest

from chip_bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [
        (0, 4), (5, 7), (10, 11)]


def test_gaps_are_the_complement_within_bounds():
    busy = [(2, 4), (6, 7)]
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([], 0, 3) == [(0, 3)]


def test_clip():
    assert tr.clip([(0, 5), (8, 12), (20, 30)], 2, 10) == [(2, 5), (8, 10)]


def test_leaves_drop_enclosing_ops_and_names_are_short():
    ops = [(0, 100, "%while.1 = (...) while(...)"), (10, 20, "%fusion.2 = x"),
           (30, 90, "%cond.3 = y"), (40, 50, "%sort = z"), (95, 99, "%a = w")]
    assert [tr.short_name(n) for _, _, n in tr.leaves(ops)] == [
        "fusion.2", "sort", "a"]


def _events():
    spans = [("bench.window", 0, 100), ("bench.pump", 0, 40),
             ("bench.wait_arrival", 40, 70), ("bench.pump", 70, 100)]
    ops = [(0, "%fusion.1 = a", 5, 15), (0, "%fusion.2 = b", 20, 30),
           (0, "%fusion.1 = a", 15, 20), (0, "%while.4 = c", 75, 95),
           (0, "%scatter = d", 76, 95), (0, "%fusion.1 = a", 120, 130)]
    return tr.TraceEvents(ops, spans)


def test_reduce_busy_top_ops_and_labelled_gaps():
    r = tr.reduce_events(_events(), n_devices=1)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(45e-9)  # (5..30) + (75..95)
    assert r["device_ops"][0] == ["scatter", pytest.approx(19e-9)]
    assert {n for n, _ in r["device_ops"]} == {"fusion.1", "fusion.2",
                                               "scatter"}
    # gaps: 0-5 pump, 30-75 (mostly wait_arrival), 95-100 pump
    assert r["idle_gaps"][0] == ["bench.wait_arrival", pytest.approx(45e-9)]
    assert sorted(n for n, _ in r["idle_gaps"][1:]) == ["bench.pump",
                                                         "bench.pump"]


def test_busy_is_averaged_over_devices():
    ev = _events()
    ev.device_ops.append((1, "fusion.9", 0, 100))
    r = tr.reduce_events(ev, n_devices=2)
    assert r["busy_s"] == pytest.approx((45e-9 + 100e-9) / 2)


def test_no_window_or_no_ops_gives_nothing():
    ev = _events()
    assert tr.reduce_events(tr.TraceEvents(ev.device_ops, []), 1) is None
    assert tr.reduce_events(tr.TraceEvents([], ev.host_spans), 1) is None


def test_recorded_chip_trace():
    path = tr.find_xplane(str(DATA))
    assert path is not None, "the recorded v5e trace is missing"
    r = tr.reduce_events(tr.load_events(path), n_devices=1)
    assert r is not None
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and all(t > 0 for _, t in r["device_ops"])
    assert all(name.startswith(("bench.", "host."))
               for name, _ in r["idle_gaps"])
