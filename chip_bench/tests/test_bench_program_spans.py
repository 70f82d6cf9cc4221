"""Program spans: self time and device idle time under program spans on
synthetic intervals, the recorded v5e trace read as before, and a served
loop on the CPU under the profiler, whose ``repro.*`` spans nest as the
program documents them and leave its results unchanged."""
from pathlib import Path

import jax
import numpy as np
import pytest

from chip_bench import program_spans as ps
from chip_bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"

NESTED = [("bench.window", 0, 100), ("repro.serve.pump", 10, 60),
          ("repro.admission.plan", 10, 20), ("repro.serve.dispatch", 20, 60),
          ("repro.dispatch.begin", 20, 30), ("repro.dispatch.settle", 40, 60),
          ("repro.dispatch.wait_device", 45, 55)]


def test_innermost_pieces_are_self_time():
    pieces = ps.innermost(NESTED)
    own = {}
    for name, s, e in pieces:
        own[name] = own.get(name, 0) + e - s
    assert own == {"bench.window": 50, "repro.admission.plan": 10,
                   "repro.serve.dispatch": 10, "repro.dispatch.begin": 10,
                   "repro.dispatch.settle": 10,
                   "repro.dispatch.wait_device": 10}
    # the pieces tile the outermost span: no moment counted twice
    assert sum(e - s for _, s, e in pieces) == 100
    ordered = sorted(pieces, key=lambda p: p[1])
    assert all(a[2] <= b[1] for a, b in zip(ordered, ordered[1:]))


def test_span_table_clips_to_the_window():
    t = ps.span_table([NESTED], 15, 50)
    assert t["repro.serve.pump"] == {"count": 1, "total_s": 35e-9,
                                     "self_s": 0.0, "max_s": 35e-9}
    assert t["repro.admission.plan"]["total_s"] == pytest.approx(5e-9)
    assert t["repro.dispatch.settle"]["total_s"] == pytest.approx(10e-9)
    assert t["repro.dispatch.settle"]["self_s"] == pytest.approx(5e-9)
    assert t["repro.dispatch.wait_device"]["self_s"] == pytest.approx(5e-9)
    assert "bench.window" in t and t["bench.window"]["self_s"] == 0.0


def test_idle_in_program_leaves_out_the_callback():
    spans = [("bench.window", 0, 100), ("repro.serve.pump", 0, 100),
             ("repro.serve.finalize", 10, 50),
             ("repro.serve.callback", 30, 40)]
    ops = [(0, "%fusion.1 = a", 0, 10), (0, "%fusion.2 = b", 50, 60),
           (1, "%fusion.3 = c", 10, 100)]  # device 1 is not read
    ev = ps.ProgramEvents(ops, [], [spans])
    gaps = ps.device_gaps(ev, 0, 100)
    assert gaps == [(10, 50), (60, 100)]  # device 0's idle time
    # the callback holds 10 of it
    pieces = ps.self_pieces(ev.host_lines, 0, 100)
    assert ps.idle_in_program_ns(gaps, pieces) == 70
    bench_only = [("bench.window", 0, 100), ("bench.pump", 0, 100)]
    assert ps.idle_in_program_ns(
        gaps, ps.self_pieces([bench_only], 0, 100)) == 0


def test_gaps_are_labelled_by_the_span_whose_own_code_held_them():
    pieces = ps.self_pieces([NESTED], 0, 100)
    # settle overlaps [44, 60) wholly, but its own code holds 6 of it and
    # the wait it encloses 10
    assert tr.label_gap((44, 60), NESTED) == "repro.dispatch.settle"
    assert ps.label_gap((44, 60), pieces) == "repro.dispatch.wait_device"
    assert ps.label_gap((22, 45), pieces) == "repro.serve.dispatch"
    assert ps.label_gap((60, 100), pieces) == "bench.window"
    assert ps.label_gap((200, 300), pieces) == "host.other"


def test_module_times_drop_the_hash_and_clip():
    mods = [(0, "jit_engine_phase1_ntkms_block_mxu(123)", 0, 10),
            (0, "jit_engine_phase1_ntkms_block_mxu(456)", 20, 40),
            (0, "jit_other(7)", 90, 120)]
    assert ps.module_times(mods, 0, 100) == {
        "jit_engine_phase1_ntkms_block_mxu": pytest.approx(30e-9),
        "jit_other": pytest.approx(10e-9)}


def test_per_batch_ms_subtracts_the_callback():
    spans = {"repro.serve.finalize": {"total_s": 0.010, "self_s": 0.004},
             "repro.serve.callback": {"total_s": 0.002, "self_s": 0.002},
             "repro.dispatch.begin": {"total_s": 0.009, "self_s": 0.001},
             "repro.dispatch.settle": {"total_s": 0.009, "self_s": 0.003}}
    got = ps.per_batch_ms(spans, batches=2)
    assert got["finalize"] == pytest.approx(4.0)
    assert got["dispatch_host"] == pytest.approx(2.0)
    assert got["admission"] == 0.0 and got["refit"] == 0.0
    assert ps.per_batch_ms(spans, batches=0) == {}


def test_recorded_chip_trace_reads_as_before():
    """The reduction the harness makes of the recorded trace (busy time,
    window and device operations) is what this reading gives too."""
    path = tr.find_xplane(str(DATA))
    before = tr.reduce_events(tr.load_events(path), n_devices=1)
    r = ps.report(path)
    for key in ("busy_s", "window_s", "device_ops"):
        assert r[key] == before[key]
    # recorded before the program had spans: nothing of it is named
    assert r["idle_in_program_s"] == 0.0
    assert not any(n.startswith(ps.PROGRAM) for n in r["spans"])
    assert r["batches"] == 0 and r["per_batch_ms"] == {}
    assert r["modules"] and all(t > 0 for t in r["modules"].values())
    assert sum(r["modules"].values()) <= r["window_s"]


# --------------------------------------------------- a served loop, traced

# allowed immediate parents of each program span
PARENTS = {
    "repro.serve.pump": {"bench.window"},
    "repro.admission.plan": {"repro.serve.pump"},
    "repro.serve.dispatch": {"repro.serve.pump"},
    "repro.dispatch.begin": {"repro.serve.dispatch"},
    "repro.dispatch.compile": {"repro.dispatch.begin",
                               "repro.dispatch.settle"},
    "repro.dispatch.settle": {"repro.serve.dispatch"},
    "repro.dispatch.wait_device": {"repro.dispatch.settle"},
    "repro.dispatch.refit": {"repro.dispatch.settle"},
    "repro.dispatch.cost_probe": {"repro.dispatch.refit"},
    "repro.serve.finalize": {"repro.serve.dispatch", "bench.window"},
    "repro.serve.fetch": {"repro.serve.finalize"},
    "repro.serve.callback": {"repro.serve.finalize"},
}


def _serve(n_queries=6):
    """A closed loop of 64-source queries over a small power-law graph,
    refitting every 2 batches with the measured-cost probe."""
    from repro.graph.csr import csr_from_edges
    from repro.graph.generators import powerlaw
    from repro.launch.mesh import make_mesh
    from repro.runtime.service import ServingLoop

    src, dst = powerlaw(300, 8.0, seed=0).edge_list()
    got = {}
    loop = ServingLoop(
        make_mesh((1, 1), ("data", "model")), csr_from_edges(300, src, dst),
        backend="recommend", family="powerlaw", online_adapt=True,
        overlap=True, cost="measured", refit_every=2,
        on_result=lambda qid, levels: got.__setitem__(qid, np.array(levels)),
    )
    rng = np.random.default_rng(3)
    for i in range(n_queries):
        loop.submit(rng.integers(0, 300, 64).astype(np.int32), qid=f"q{i}")
        loop.pump()
    loop.drain()
    return loop, got


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        with jax.profiler.TraceAnnotation("bench.window"):
            loop, got = _serve()
    path = tr.find_xplane(str(d))
    assert path is not None
    return loop, got, path


def _parent(span, line):
    """The smallest other span of the line that contains ``span``."""
    name, s, e = span
    best = None
    for other in line:
        if other is span or not (other[1] <= s and e <= other[2]):
            continue
        if best is None or other[2] - other[1] < best[2] - best[1]:
            best = other
    return best


def test_served_spans_nest_as_documented(traced):
    loop, _, path = traced
    ev = ps.load_events(path)
    line = next(line for line in ev.host_lines
                if any(n == "bench.window" for n, _, _ in line))
    names = {n for n, _, _ in line}
    assert set(PARENTS) <= names
    for span in line:
        if span[0] in PARENTS:
            parent = _parent(span, line)
            assert parent is not None and parent[0] in PARENTS[span[0]], (
                span, parent)
    dispatches = [sp for sp in line if sp[0] == "repro.serve.dispatch"]
    assert len(dispatches) == loop.stats.batches
    window = next(sp for sp in line if sp[0] == "bench.window")
    assert all(window[1] <= s and e <= window[2]
               for n, s, e in line if n.startswith(ps.PROGRAM))
    table = ps.span_table(ev.host_lines, window[1], window[2])
    assert table["repro.serve.dispatch"]["count"] == loop.stats.batches
    assert table["bench.window"]["self_s"] < table["bench.window"]["total_s"]


def test_spans_of_one_batch_share_its_number(traced):
    from jax.profiler import ProfileData

    loop, _, path = traced
    batch = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("repro.serve.dispatch", "repro.dispatch.begin",
                              "repro.dispatch.settle"):
                    batch.setdefault(e.name, []).append(dict(e.stats)["batch"])
    n = loop.stats.batches
    assert {k: sorted(v) for k, v in batch.items()} == {
        k: list(range(n)) for k in ("repro.serve.dispatch",
                                    "repro.dispatch.begin",
                                    "repro.dispatch.settle")}


def test_results_equal_an_untraced_run(traced):
    loop, got, _ = traced
    loop2, got2 = _serve()
    assert sorted(got) == sorted(got2) == [f"q{i}" for i in range(6)]
    for qid in got:
        np.testing.assert_array_equal(got[qid], got2[qid])
    assert loop2.stats.batches == loop.stats.batches
