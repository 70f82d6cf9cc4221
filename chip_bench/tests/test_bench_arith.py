"""Due-time percentiles, window rates, spreads and generator lateness."""
import statistics

import numpy as np
import pytest

from chip_bench import stats


def test_percentile_is_linear_and_empty_is_none():
    assert stats.percentile([], 50) is None
    vals = list(range(1, 11))  # 1..10
    assert stats.percentile(vals, 50) == pytest.approx(5.5)
    assert stats.percentile(vals, 90) == pytest.approx(9.1)
    assert stats.percentile([7.0], 90) == 7.0


def test_rate_spans_the_whole_window():
    assert stats.rate(1000, 10.0) == 100.0
    assert stats.rate(5, 0.0) is None


def test_spread_uses_statistics_quartiles():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, q2, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / q2)


def test_lateness_summary_in_ms():
    s = stats.lateness_summary(np.array([0.001, 0.002, 0.010]))
    assert s == {"p50_ms": pytest.approx(2.0), "max_ms": pytest.approx(10.0)}
    assert stats.lateness_summary([]) == {"p50_ms": 0.0, "max_ms": 0.0}


@pytest.mark.parametrize("name,expect", [
    ("latency_p50_ms", 5.5),
    ("edges_per_s", 250.0), ("setup_s", 3.0),
])
def test_end_to_end_readers(name, expect):
    from chip_bench.harness import load_reader

    ctx = {"latencies_ms": list(range(1, 11)), "traversed_edges": 1000,
           "seconds": 4.0, "setup_s": 3.0}
    assert load_reader("end_to_end", name)(ctx) == pytest.approx(expect)


def _ctx(**kw):
    ctx = {"batches": 4, "completed": 8, "source_rows": 256, "compiles": 1,
           "trace": {"busy_s": 2.0, "window_s": 8.0}}
    ctx.update(kw)
    return ctx


@pytest.mark.parametrize("name,expect", [
    ("queries_per_batch.lat", 2.0), ("sources_per_batch.tput", 64.0),
    ("compiles_in_window.lat", 1), ("compiles_in_window.tput", 1),
    ("device_ms_per_query.lat", 250.0), ("device_ms_per_source.tput", 7.8125),
    ("device_idle_share.lat", 0.75), ("device_idle_share.tput", 0.75),
])
def test_per_layer_readers(name, expect):
    from chip_bench.harness import load_reader

    assert load_reader("metrics", name)(_ctx()) == pytest.approx(expect)


@pytest.mark.parametrize("name", [
    "queries_per_batch.lat", "device_ms_per_query.lat",
    "device_idle_share.tput", "device_ms_per_source.tput",
])
def test_per_layer_readers_return_nothing_without_a_reading(name):
    from chip_bench.harness import load_reader

    assert load_reader("metrics", name)(
        _ctx(batches=0, completed=0, source_rows=0, trace=None)) is None


def test_spread_tool_reads_result_lines(tmp_path):
    import json

    from chip_bench import spread as tool

    files = []
    for i, v in enumerate([100.0, 102.0, 98.0, 101.0, 99.0, 140.0]):
        f = tmp_path / f"r{i}.out"
        f.write_text("log line\n" + json.dumps(
            {"metrics": {"latency_p50_ms": {"value": v, "unit": "ms"}}}))
        files.append(str(f))
    d = tool.describe(tool.load(files)["latency_p50_ms"])
    assert d["n"] == 6 and d["median"] == pytest.approx(100.5)
    assert d["spread_without_farthest"] < d["spread"]
