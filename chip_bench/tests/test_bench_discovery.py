"""A configuration, a traffic mix and a metric are found by the names
BENCHMARK.json gives them, so a later change adds files and edits none."""
import json
import shutil

import pytest

from chip_bench import harness

NEW_METRIC = '''
def read(ctx):
    return ctx["batches"] * 10
'''


@pytest.fixture()
def root_with_new_cell(tmp_path):
    bench = harness.load_benchmark()
    shutil.copytree(harness.BENCH_DIR, tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cb = tmp_path / "chip_bench"
    (cb / "configs" / "tiny-ring.json").write_text(json.dumps({
        "generator": "erdos_renyi", "family": "er", "n_nodes": 64,
        "avg_degree_per_direction": 2.0, "symmetric": True, "graph_seed": 1,
    }))
    (cb / "traffic" / "poisson-2src.json").write_text(json.dumps({
        "kind": "poisson", "rate_qps": 4.0, "sources_per_query": 2,
    }))
    (cb / "metrics" / "batches_times_ten.tput.py").write_text(NEW_METRIC)
    bench["configs"].append({"name": "tiny-ring", "source": "x",
                             "file": "chip_bench/configs/tiny-ring.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny-pairs", "config": "tiny-ring",
                               "traffic": "poisson-2src", "chips": 1,
                               "why": "x"})
    bench["end_to_end"].append({
        "name": "latency_p50_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock", "workloads": ["tiny-pairs"]})
    bench["per_layer"].append({
        "name": "batches_times_ten.tput", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "dispatch",
        "moves": "latency_p50_ms", "workloads": ["tiny-pairs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_new_files_are_found_by_name(root_with_new_cell):
    root = root_with_new_cell
    cell = harness.load_cell(harness.load_benchmark(root), "tiny-pairs", root)
    assert cell.config["n_nodes"] == 64
    assert cell.mix["rate_qps"] == 4.0 and cell.mix["sources_per_query"] == 2
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                   "latency_p50_ms"]
    assert [m["name"] for m in cell.per_layer] == ["batches_times_ten.tput"]
    read = harness.load_reader("metrics", "batches_times_ten.tput", root)
    assert read({"batches": 3}) == 30


@pytest.mark.parametrize("cell,e2e,layer", [
    ("ldbc-64src-closed", {"setup_s", "edges_per_s"},
     {"sources_per_batch.tput", "compiles_in_window.tput",
      "device_ms_per_source.tput", "device_idle_share.tput",
      "admission_ms_per_batch.tput", "dispatch_host_ms_per_batch.tput",
      "device_wait_ms_per_batch.tput", "refit_ms_per_batch.tput",
      "finalize_ms_per_batch.tput", "idle_in_program_share.tput",
      "queue_wait_ms_per_query.tput", "setup_compile_s.tput",
      "d2h_blocking_per_batch.tput"}),
])
def test_committed_cells_resolve_every_piece(cell, e2e, layer):
    c = harness.load_cell(harness.load_benchmark(), cell)
    assert {m["name"] for m in c.end_to_end} == e2e
    assert {m["name"] for m in c.per_layer} == layer
    for m in c.end_to_end:
        assert callable(harness.load_reader("end_to_end", m["name"]))
    for m in c.per_layer:
        assert callable(harness.load_reader("metrics", m["name"]))


def test_unknown_names_are_errors():
    bench = harness.load_benchmark()
    with pytest.raises(KeyError):
        harness.load_cell(bench, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        harness.load_reader("metrics", "no_such_metric")


COUNTERS = {"queue_wait_s": 0.5, "dispatched_queries": 10,
            "compile_s": 0.25, "d2h_prefetched": 40, "d2h_blocking": 0}
CTX = {"seconds": 50.0, "setup_s": 12.5, "latencies_ms": [3.0, 1.0, 2.0],
       "completed": 3, "source_rows": 192, "traversed_edges": 1000,
       "batches": 3, "compiles": 0,
       "program": {"open": COUNTERS, "close": dict(
           COUNTERS, queue_wait_s=0.53, dispatched_queries=13,
           d2h_prefetched=52)},
       "trace": {"busy_s": 0.5, "window_s": 2.0, "batches": 3,
                 "idle_in_program_s": 1.25,
                 "per_batch_ms": {"admission": 1.25, "dispatch_host": 1.75,
                                  "refit": 0.75, "device_wait": 2.5,
                                  "finalize": 0.5}}}


@pytest.mark.parametrize("path", sorted(
    p for kind in ("end_to_end", "metrics")
    for p in (harness.BENCH_DIR / kind).glob("*.py")), ids=lambda p: p.stem)
def test_every_reader_reads_a_run(path):
    read = harness.load_reader(path.parent.name, path.stem)
    value = read(CTX)
    assert isinstance(value, (int, float)) and value >= 0
    if "trace" in path.stem or "device" in path.stem:
        assert read(dict(CTX, trace=None)) is None
