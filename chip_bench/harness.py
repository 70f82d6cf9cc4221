"""Run one cell once: build the deployment, warm the served path up, measure
a window of its traffic, check every answer against the reference, and
print the result line.

The system under test is the program's served path with the deployment
defaults of ``serve``: ``ServingLoop`` (``submit`` / ``pump`` / ``drain``,
``on_result``) over ``QueryDispatcher`` with ``backend="recommend"``,
``online_adapt=True``, ``overlap=True``, ``cost="auto"``,
``refit_every=16``, no cap on a batch's sources, and a ``(1, chips)`` mesh of
``("data", "model")``. The benchmark takes from the program only that path
and its counters; traffic, clock, reference and trace reduction live here.

``run.py`` arms a ``SetupWatchdog`` at process start: a set-up that has not
reached the window ``SETUP_DEADLINE_S`` seconds in ends the process, with
the stage it reached and no result line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path

import numpy as np

from . import graphs, oracle, program_spans, stats, trace_reduce, traffic
from .peaks import peaks_for

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
REFIT_EVERY = 16
# answers due in the window are awaited this long after it closes
LATE_WAIT_S = 60.0
# source rows of a window compared with the reference, at most: past it a
# reservoir sample drawn from the seed
MAX_COMPARED_ROWS = 4096
# seconds from process start to the window, at most: five times the cold
# (compiling) set-up of ldbc-64src-closed
SETUP_DEADLINE_S = 300.0
SETUP_EXPIRED_EXIT = 70


class NoChip(SystemExit):
    """Raised when JAX finds no accelerator or too few chips."""


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    root: Path = ROOT  # where its metric readers are found


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def load_cell(bench: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell's entry with its configuration, traffic mix and metrics,
    each found by the name ``BENCHMARK.json`` gives it."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = traffic.load(w["traffic"], root / "chip_bench" / "traffic")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, config, mix, int(w["chips"]), e2e, layer, root)


def load_reader(kind: str, metric: str, root: Path = ROOT):
    """``read(ctx)`` of ``<kind>/<metric>.py`` (kind: end_to_end or metrics)."""
    path = root / "chip_bench" / kind / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chip_bench_{kind}_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_devices(chips: int):
    """The chips of this run; exits non-zero when there is no TPU or too
    few, and when the device kind has no published peaks."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"chip_bench: no TPU (JAX platform "
                     f"{devices[0].platform!r}); refusing to run")
    if len(devices) < chips:
        raise NoChip(f"chip_bench: needs {chips} chips, found {len(devices)}")
    try:
        peaks_for(devices[0].device_kind)
    except KeyError as e:
        raise NoChip(f"chip_bench: {e}") from None
    return devices[:chips]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(jax.config.jax_compilation_cache_dir)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class SetupWatchdog:
    """Ends the process (``os._exit``, no result line) when the window has
    not opened ``deadline_s`` seconds after ``t_start``, naming the set-up
    stage it reached: a set-up that would run for tens of minutes fails
    with its cause instead of hanging. ``run_cell`` reports its stages
    (``reached``), each logged with its time since ``t_start``, and
    disarms it as the window opens."""

    def __init__(self, deadline_s: float, t_start: float):
        self.deadline_s = deadline_s
        self.t_start = t_start
        self.stage = "start"
        self._lock = threading.Lock()
        self._armed = True
        self._timer = threading.Timer(
            max(0.0, t_start + deadline_s - time.perf_counter()), self._expire)
        self._timer.daemon = True
        self._timer.start()

    def reached(self, stage: str) -> None:
        self.stage = stage
        log(f"set-up: {stage} at {time.perf_counter() - self.t_start:.3f} s")

    def disarm(self) -> None:
        with self._lock:
            self._armed = False
        self._timer.cancel()

    def _expire(self) -> None:
        with self._lock:
            if self._armed:
                log(f"chip_bench: set-up passed its {self.deadline_s:g} s "
                    f"deadline; stage reached: {self.stage}")
                os._exit(SETUP_EXPIRED_EXIT)


def narrow(levels) -> np.ndarray:
    """A result row as int16 where every value fits, else in its own dtype:
    no value changes, so a wrong level (say an int32 sentinel for
    unreached) cannot wrap onto a right one."""
    row = np.asarray(levels)
    small = np.iinfo(np.int16)
    if (row.dtype.kind in "iu" and row.size
            and small.min <= row.min() and row.max() <= small.max):
        return row.astype(np.int16)
    return np.array(row)


class Recorder:
    """``on_result`` of the served loop: delivery times, and the result rows
    of a reservoir sample of the window's queries drawn from the seed
    (all of them while they fit ``MAX_COMPARED_ROWS`` source rows)."""

    def __init__(self, clock, seed: int = 0, sources_per_query: int = 1):
        self.clock = clock
        self.loop = None
        self.submitted = 0
        self.delivered: dict[str, float] = {}
        self.rows: dict[str, np.ndarray] = {}
        self.window: set[str] = set()
        self.need_next = 0
        self._slots = max(1, MAX_COMPARED_ROWS // sources_per_query)
        self._kept: list[str] = []
        self._seen = 0
        self._rng = np.random.default_rng([seed, 2])

    def __call__(self, qid: str, levels: np.ndarray) -> None:
        self.delivered[qid] = self.clock()
        self.loop.results.pop(qid, None)
        self.need_next += 1
        if qid not in self.window:
            return
        self._seen += 1
        if len(self._kept) < self._slots:
            self._kept.append(qid)
        else:
            j = int(self._rng.integers(self._seen))
            if j >= self._slots:
                return
            del self.rows[self._kept[j]]
            self._kept[j] = qid
        self.rows[qid] = narrow(levels)

    @property
    def undelivered(self) -> int:
        return self.submitted - len(self.delivered)


class LoadGen:
    """Drives ``ServingLoop`` with the benchmark's host spans."""

    def __init__(self, loop, recorder: Recorder,
                 warm_deck: traffic.SourceDeck, deck: traffic.SourceDeck):
        import jax

        self.loop, self.rec = loop, recorder
        self.warm_deck, self.deck = warm_deck, deck
        self.span = jax.profiler.TraceAnnotation
        self.queries: dict[str, np.ndarray] = {}  # qid -> sources
        self.due: dict[str, float] = {}
        self.lateness: list[float] = []
        self.window: list[str] = []  # qids of the measured window

    def submit(self, qid: str, due: float, keep: bool = True) -> None:
        sources = (self.deck if keep else self.warm_deck).deal()
        self.queries[qid] = sources
        self.due[qid] = due
        if keep:
            self.rec.window.add(qid)
            self.window.append(qid)
        self.rec.submitted += 1
        self.loop.submit(sources, qid=qid)
        self.lateness.append(self.rec.clock() - due)

    def step(self) -> bool:
        """Serve what is there; False when nothing is queued or in flight."""
        if self.loop.admission.pending():
            with self.span("bench.pump"):
                self.loop.pump()
        elif self.rec.undelivered:
            with self.span("bench.deliver"):
                self.loop.drain()
        else:
            return False
        return True

    def warm_up(self, reached=lambda stage: None) -> int:
        """Serve warm-up queries back to back until the first threshold
        refit has run (``REFIT_EVERY`` batches) and one batch has been
        served under it, which builds the engines it asks for: a server
        that has run a while is in that state. Each batch of these cells
        holds one morsel (a query of one source is its own batch until 64
        sources are queued; a 64-source query is one 64-lane morsel), so
        that compiles every pow2 morsel count the window pools. Later
        refits, and the compiles they cause, fall in the window as the
        system's own behaviour and are counted there. ``reached`` is told
        each query's number; the first places the operands."""
        disp = self.loop.dispatcher
        n, after_refit = 0, 0
        while after_refit < 1:
            reached(f"warm-up query {n}"
                    + (" (places the operands)" if n == 0 else ""))
            after_refit += disp.stats.refits >= 1
            self.submit(f"warm{n}", self.rec.clock(), keep=False)
            while self.step():
                pass
            n += 1
        return n

    def open_window(self, offsets: np.ndarray, seconds: float) -> float:
        clock = self.rec.clock
        t0 = clock()
        close = t0 + seconds
        i, n = 0, len(offsets)
        with self.span("bench.window"):
            while clock() < close:
                now = clock()
                while i < n and t0 + offsets[i] <= now:
                    self.submit(f"w{i}", t0 + offsets[i])
                    i += 1
                if not self.step():
                    nxt = t0 + offsets[i] if i < n else close
                    with self.span("bench.wait_arrival"):
                        time.sleep(max(0.0, min(nxt - clock(), 0.002)))
        while i < n:  # due before the close, submitted late
            self.submit(f"w{i}", t0 + offsets[i])
            i += 1
        return t0

    def closed_window(self, clients: int, seconds: float) -> float:
        clock = self.rec.clock
        t0 = clock()
        close = t0 + seconds
        self.rec.need_next = 0
        k = 0
        with self.span("bench.window"):
            for _ in range(clients):
                self.submit(f"w{k}", clock())
                k += 1
            while clock() < close:
                self.step()
                while self.rec.need_next and clock() < close:
                    self.rec.need_next -= 1
                    self.submit(f"w{k}", clock())
                    k += 1
        return t0

    def finish(self, deadline: float) -> None:
        """Serve what is still queued or in flight, up to ``deadline``."""
        while self.rec.clock() < deadline and self.step():
            pass


def build_loadgen(cell: Cell, seed: int
                 ) -> tuple[LoadGen, np.ndarray, np.ndarray]:
    """The cell's deployment under this seed's node ids, served by
    ``ServingLoop`` with serve's defaults (module docstring), behind a
    ``LoadGen`` that draws the warm-up's and the window's sources from the
    seed. Also returns the edge list, from which the reference builds its
    own adjacency."""
    from repro.graph.csr import csr_from_edges
    from repro.launch.mesh import make_mesh
    from repro.runtime.service import ServingLoop

    cfg, mix = cell.config, cell.mix
    src_s, dst_s = graphs.structural_edges(cfg)
    relabel = graphs.relabelling(cfg, seed)
    src, dst = relabel[src_s], relabel[dst_s]
    csr = csr_from_edges(cfg["n_nodes"], src, dst)
    log(f"{cell.name}: {csr.n_nodes} nodes, {csr.n_edges} edges, max "
        f"out-degree {int(csr.degrees.max())}")
    recorder = Recorder(time.perf_counter, seed,
                        int(mix["sources_per_query"]))
    loop = ServingLoop(
        make_mesh((1, cell.chips), ("data", "model")), csr,
        backend="recommend", family=cfg["family"], online_adapt=True,
        overlap=True, cost="auto", refit_every=REFIT_EVERY,
        on_result=recorder,
    )
    recorder.loop = loop
    degrees = np.bincount(src_s, minlength=cfg["n_nodes"])
    warm_deck, deck = (traffic.SourceDeck(mix, degrees, relabel, seed, k)
                       for k in (0, 1))
    return LoadGen(loop, recorder, warm_deck, deck), src, dst


def program_counters(loop) -> dict:
    """The program's counters that per-layer readers take, by name; one
    that this program does not have is left out."""
    disp = loop.dispatcher
    owners = {"queue_wait_s": loop.stats, "dispatched_queries": loop.stats,
              "compile_s": disp.cache, "d2h_prefetched": disp.stats,
              "d2h_blocking": disp.stats}
    return {name: getattr(owner, name) for name, owner in owners.items()
            if hasattr(owner, name)}


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             trace_dir: Path | None = None, control: bool = False,
             watchdog: SetupWatchdog | None = None) -> dict:
    """One run of one cell; returns the result object (see ``run.py``).

    ``control`` also reads the control (``oracle.CONTROL_ROW_CAP``) in the
    program's place on the same compared queries, under ``control`` beside
    ``checks``; the benchmark's own runs never do. ``watchdog`` is told the
    set-up's stages and disarmed as the window opens."""
    reached = watchdog.reached if watchdog else lambda stage: None
    reached("devices")
    if require_chip:
        devices = require_devices(cell.chips)
    import jax

    if require_chip:
        cache_dir = enable_compile_cache()
    else:
        devices = jax.devices()[: cell.chips]
        cache_dir = "off"
    clock = time.perf_counter
    mix = cell.mix
    seed = int(seed) % 2**63
    log(f"{cell.name}: compile cache {cache_dir}")
    closed = mix["kind"] == "closed"
    offsets = (None if closed
               else traffic.arrival_offsets(mix, seconds, seed))
    reached("graph")
    gen, src, dst = build_loadgen(cell, seed)
    loop, recorder = gen.loop, gen.rec
    n_warm = gen.warm_up(reached)
    disp = loop.dispatcher
    log(f"warm-up: {n_warm} queries, {loop.stats.batches} batches, "
        f"{disp.stats.refits} refits, {disp.cache.compile_events} compile "
        f"events")

    traced = trace_dir is not None and trace
    if traced:
        reached("profiler start")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    batches0 = loop.stats.batches
    compiles0 = disp.cache.compile_events
    refits0 = disp.stats.refits
    program = {"open": program_counters(loop)}
    setup_s = clock() - t_start
    if watchdog:
        watchdog.disarm()
    if closed:
        t0 = gen.closed_window(int(mix.get("clients", 1)), seconds)
    else:
        t0 = gen.open_window(offsets, seconds)
    close = t0 + seconds
    batches_w = loop.stats.batches - batches0
    compiles_w = disp.cache.compile_events - compiles0
    refits_w = disp.stats.refits - refits0
    program["close"] = program_counters(loop)
    gen.finish(close + LATE_WAIT_S)
    if traced:
        jax.profiler.stop_trace()
    memory_peak = _memory_peak(devices)
    report = disp.device_report()
    for n_pad, rates in report["cost_rates"].items():
        for name, r in rates.items():
            log(f"cost probe (n_pad {n_pad}): {name} step "
                f"{r['probe_ms']:.4f} ms over {r['slots']} slots")
    log(f"window: {batches_w} batches, {compiles_w} compile events, "
        f"{refits_w} refits; hybrid batches {disp.stats.hybrid_runs}, "
        f"re-dispatched {disp.stats.redispatched}; operand bytes "
        f"{ {str(k): v for k, v in report['operand_bytes'].items()} }")
    late = stats.lateness_summary(gen.lateness)
    log(f"generator lateness: p50 {late['p50_ms']:.4f} ms, max "
        f"{late['max_ms']:.4f} ms")
    kind = devices[0].device_kind
    log(f"memory peak {memory_peak} bytes "
        f"({memory_peak / peaks_for(kind)['hbm_bytes']:.4%} of HBM)"
        if require_chip else f"memory peak {memory_peak} bytes")

    # the program's state goes before the reference runs
    window_q = gen.window
    delivered = recorder.delivered
    rows = recorder.rows
    del loop, gen.loop, recorder.loop, disp
    gc.collect()

    n_nodes = cell.config["n_nodes"]
    reference = oracle.Reference(n_nodes, src, dst)
    done_in_window = [q for q in window_q
                      if q in delivered and delivered[q] <= close]
    latencies = [(delivered[q] - gen.due[q]) * 1e3
                 for q in window_q if q in delivered]
    source_rows = sum(len(gen.queries[q]) for q in done_in_window)
    edges = sum(reference.reached_edges(gen.queries[q])
                for q in done_in_window)
    missing = [q for q in window_q if q not in delivered]
    compared = list(rows)
    compared_sources = [gen.queries[q] for q in compared]
    wrong = dict(zip(compared, oracle.mismatches_by_query(
        reference, compared_sources, [rows[q] for q in compared])))
    checks = {
        "mismatched_levels": {"value": int(sum(wrong.values())), "limit": 0},
        "missing_results": {"value": len(missing), "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    control_reading = None
    if control:
        capped = oracle.Reference(n_nodes, src, dst,
                                  row_cap=oracle.CONTROL_ROW_CAP)
        control_reading = oracle.control_mismatches(reference, capped,
                                                    compared_sources)
        log(f"control: mismatched_levels {control_reading} (limit 0)")
    log(f"window: {len(latencies)} latencies, p50 "
        f"{stats.percentile(latencies, 50)} ms, p90 "
        f"{stats.percentile(latencies, 90)} ms; {edges} traversed edges in "
        f"{len(done_in_window)} queries delivered in the window")
    log(f"compared {sum(len(s) for s in compared_sources)} source rows "
        f"of {len(compared)} queries with the reference")

    ctx = {
        "cell": cell.name, "seconds": seconds, "setup_s": setup_s,
        "latencies_ms": latencies, "completed": len(done_in_window),
        "source_rows": source_rows, "traversed_edges": edges,
        "batches": batches_w, "compiles": compiles_w, "refits": refits_w,
        "program": program, "trace": None,
    }
    device = {"platform": devices[0].platform, "kind": kind,
              "count": cell.chips, "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(window_q),
              "failed": len(missing) + sum(1 for v in wrong.values() if v),
              "metrics": {}, "device": device}
    if traced:
        path = trace_reduce.find_xplane(str(trace_dir))
        reduced = program_spans.report(path, cell.chips) if path else None
        if reduced is None:
            raise RuntimeError(f"no device operations in the trace at "
                               f"{trace_dir}")
        ctx["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    kind_dir, metrics = (("metrics", cell.per_layer) if trace
                         else ("end_to_end", cell.end_to_end))
    for m in metrics:
        value = load_reader(kind_dir, m["name"], cell.root)(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    if control:
        result["control"] = {"mismatched_levels": control_reading,
                             "limit": 0}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result
