"""The per-layer readers of the program's spans and counters: what each
takes from the run, and that a run without it reads as no metric."""
import pytest

from chip_bench.harness import load_reader, program_counters

OPEN = {"queue_wait_s": 1.0, "dispatched_queries": 100, "compile_s": 0.27,
        "d2h_prefetched": 400, "d2h_blocking": 8}
CLOSE = {"queue_wait_s": 1.5, "dispatched_queries": 350, "compile_s": 0.27,
         "d2h_prefetched": 1400, "d2h_blocking": 8}
TRACE = {"busy_s": 6.6, "window_s": 50.0, "batches": 250,
         "idle_in_program_s": 42.2,
         "per_batch_ms": {"admission": 1.27, "dispatch_host": 1.87,
                          "refit": 0.9, "device_wait": 2.44,
                          "finalize": 0.54}}


def _ctx(**kw):
    ctx = {"batches": 250, "program": {"open": OPEN, "close": CLOSE},
           "trace": TRACE}
    ctx.update(kw)
    return ctx


@pytest.mark.parametrize("name,expect", [
    ("admission_ms_per_batch.tput", 1.27),
    ("dispatch_host_ms_per_batch.tput", 1.87),
    ("device_wait_ms_per_batch.tput", 2.44),
    ("refit_ms_per_batch.tput", 0.9),
    ("finalize_ms_per_batch.tput", 0.54),
    ("idle_in_program_share.tput", 42.2 / 50.0),
    ("queue_wait_ms_per_query.tput", 0.5e3 / 250),
    ("setup_compile_s.tput", 0.27),
    ("d2h_blocking_per_batch.tput", 0.0),
])
def test_program_readers(name, expect):
    assert load_reader("metrics", name)(_ctx()) == pytest.approx(expect)


@pytest.mark.parametrize("name", [
    "admission_ms_per_batch.tput", "dispatch_host_ms_per_batch.tput",
    "device_wait_ms_per_batch.tput", "refit_ms_per_batch.tput",
    "finalize_ms_per_batch.tput", "idle_in_program_share.tput",
])
def test_span_readers_read_nothing_without_program_spans(name):
    read = load_reader("metrics", name)
    assert read(_ctx(trace=None)) is None
    # a trace of a program without spans: no dispatch span, no batch
    bare = dict(TRACE, batches=0, idle_in_program_s=0.0, per_batch_ms={})
    assert read(_ctx(trace=bare)) is None


def test_a_program_without_the_d2h_counters_reads_no_metric():
    parent = {k: v for k, v in OPEN.items() if not k.startswith("d2h_")}
    ctx = _ctx(program={"open": parent, "close": parent})
    assert load_reader("metrics", "d2h_blocking_per_batch.tput")(ctx) is None
    assert load_reader("metrics", "queue_wait_ms_per_query.tput")(
        _ctx(program={"open": {}, "close": {}})) is None
    assert load_reader("metrics", "setup_compile_s.tput")(
        _ctx(program={"open": {}, "close": {}})) is None


def test_program_counters_leave_out_what_the_program_lacks():
    class Obj:
        pass

    loop, disp, stats, cache = Obj(), Obj(), Obj(), Obj()
    loop.stats, loop.dispatcher = stats, disp
    disp.stats, disp.cache = Obj(), cache  # no d2h_* counters
    stats.queue_wait_s, stats.dispatched_queries = 0.5, 7
    cache.compile_s = 1.5
    assert program_counters(loop) == {"queue_wait_s": 0.5,
                                      "dispatched_queries": 7,
                                      "compile_s": 1.5}


def test_a_traced_run_reads_the_trace_through_program_spans(tmp_path,
                                                           monkeypatch):
    """``run_cell``'s traced branch on the CPU, with the profiler's trace
    replaced by one recorded on a v5e chip before the program had spans:
    busy time and window are ``trace_reduce``'s reading of it, the device
    and counter readers report, the span readers report nothing."""
    import shutil
    import time

    import jax

    from chip_bench import harness
    from chip_bench import trace_reduce as tr

    data = harness.BENCH_DIR / "tests" / "data"
    trace_dir = tmp_path / "trace"
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: shutil.copytree(data, trace_dir))
    bench = harness.load_benchmark()
    layer = harness.load_cell(bench, "ldbc-64src-closed").per_layer
    cell = harness.Cell("tiny", {
        "generator": "powerlaw", "family": "powerlaw", "n_nodes": 300,
        "avg_degree_per_direction": 22.0, "alpha": 1.8, "symmetric": True,
        "graph_seed": 0}, {"kind": "closed", "clients": 1,
                           "sources_per_query": 64}, 1, [], layer)
    r = harness.run_cell(cell, 2**31 + 9, 0.5, True, time.perf_counter(),
                         require_chip=False, trace_dir=trace_dir)
    before = tr.reduce_events(
        tr.load_events(tr.find_xplane(str(data))), n_devices=1)
    assert r["correct"] is True
    assert (r["device"]["busy_s"], r["device"]["window_s"]) == (
        before["busy_s"], before["window_s"])
    assert r["breakdown"]["device_ops"] == before["device_ops"]
    assert set(r["metrics"]) == {
        "sources_per_batch.tput", "compiles_in_window.tput",
        "device_ms_per_source.tput", "device_idle_share.tput",
        "queue_wait_ms_per_query.tput", "setup_compile_s.tput",
        "d2h_blocking_per_batch.tput"}
