"""The LiveJournal deployment: its generated counts, the backend the
program picks for its 64-source queries, the metrics its cell resolves,
and the reader of the direction switch's device time."""
import json

import pytest

from chip_bench import graphs, harness, oracle

CELL = "lj-64src-closed"
LAYER = {"sources_per_batch.tput", "compiles_in_window.tput",
         "device_ms_per_source.tput", "device_idle_share.tput",
         "admission_ms_per_batch.tput", "dispatch_host_ms_per_batch.tput",
         "device_wait_ms_per_batch.tput", "refit_ms_per_batch.tput",
         "finalize_ms_per_batch.tput", "idle_in_program_share.tput",
         "queue_wait_ms_per_query.tput", "setup_compile_s.tput",
         "d2h_blocking_per_batch.tput", "dopt_device_ms_per_source.tput"}


@pytest.fixture(scope="module")
def config():
    return json.loads((harness.BENCH_DIR / "configs" / "livejournal.json")
                      .read_text())


def test_generated_counts_match_the_config(config):
    src, dst = graphs.structural_edges(config)
    ref = oracle.Reference(config["n_nodes"], src, dst)
    assert int(ref.indptr[-1]) == config["n_edges"] == 5678358
    assert int(ref.degrees.max()) == config["max_out_degree"] == 5512
    published = config["published"]
    assert abs(config["n_edges"] / config["n_nodes"]
               - published["avg_degree"]) < 0.01 * published["avg_degree"]


def test_64_lanes_take_the_binned_direction_switch(config):
    from repro.core import as_spec, recommend_backend

    avg = config["n_edges"] / config["n_nodes"]
    rec = recommend_backend("msbfs_lengths", avg, n_nodes=config["n_nodes"],
                            lanes=64)
    assert rec == "dopt_binned"
    spec = as_spec(rec)
    assert spec.direction == "auto" and spec.needs_binned


def test_cell_resolves_every_piece():
    c = harness.load_cell(harness.load_benchmark(), CELL)
    assert c.chips == 1 and c.config["name"] == "livejournal"
    assert c.mix["kind"] == "closed" and c.mix["sources_per_query"] == 64
    assert {m["name"] for m in c.end_to_end} == {"setup_s", "edges_per_s"}
    assert {m["name"] for m in c.per_layer} == LAYER
    for m in c.per_layer:
        assert callable(harness.load_reader("metrics", m["name"]))


def test_dopt_device_reader():
    read = harness.load_reader("metrics", "dopt_device_ms_per_source.tput")
    trace = {"busy_s": 9.0, "window_s": 50.0, "modules": {
        "jit_engine_phase1_ntkms_dopt_binned": 6.0,
        "jit_engine_resume_nt1s_dopt_binned": 1.5,
        "jit_engine_phase1_ntkms_block_mxu": 1.0}}
    ctx = {"source_rows": 3000, "trace": trace}
    assert read(ctx) == pytest.approx(7.5e3 / 3000)
    assert read(dict(ctx, trace=dict(trace, modules={}))) == 0.0
    assert read(dict(ctx, trace=None)) is None
    assert read(dict(ctx, source_rows=0)) is None
