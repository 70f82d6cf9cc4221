"""Service layer of the serving core: the always-on overlapped loop.

Bottom of the three-layer runtime (see docs/serving.md): the admission
layer (runtime/admission.py) decides who runs in which morsel pack, the
dispatch layer (runtime/dispatch.py) executes one batch, and this module
keeps the machine *busy* across batches. ``ServingLoop`` is the paper's
robustness story made continuous: an open-loop arrival stream is admitted,
packed, dispatched, and accounted per tenant, with batch i's deferred host
work overlapped against batch i+1's device work.

**The overlap.** The dispatch layer's split-phase API makes one batch three
steps: ``begin_batch`` (jax async dispatch of phase 1 — device futures,
host returns immediately), ``settle_batch`` (device sync points + phase-2
re-dispatch + learning), ``finalize_batch`` (deferred host materialization:
state transfers and the survivor stitch). The loop pipelines them
double-buffered — at most one settled-but-unfinalized batch rides behind
the in-flight one:

    begin(i)            # device starts scanning batch i
    finalize(i-1)       # host stitches batch i-1 while the device runs
    settle(i)           # host blocks on batch i

so the host-side result materialization (the dominant non-device cost of a
served batch) is hidden behind phase-1 compute, and the phase-1 buffers
batch i-1 consumed are dropped (donated) the moment its stitch completes.
Learning order is untouched — ``settle(i)`` still precedes ``begin(i+1)``,
so budgets/thresholds/results are bit-identical to the synchronous façade
on the same admission order (``overlap=False`` runs the same code strictly
serially; the replay lock in tests/test_serving.py compares the two).

**Telemetry.** Per-tenant submitted/completed/shed/deadline-miss counters
and latency records, split warm/cold: a batch that compiled a new engine
(EngineCache miss during its dispatch) is a *cold* batch, its wall is
compile time, and the queries it served are excluded from warm percentiles
— the serving tail must not be reported as compile time (the p99 fix this
layer exists to make honest). ``overlap_occupancy`` reports how many
finalizes actually hid behind a later batch's device work.

**Trace spans.** Each step is a ``jax.profiler.TraceAnnotation`` (about a
microsecond when no profiler runs), nested as the profiler's host plane
shows them::

    repro.serve.pump
      repro.admission.plan          (queued)
      repro.serve.dispatch          (batch)
        repro.dispatch.begin        (batch; see runtime/dispatch.py)
        repro.serve.finalize        (batch of the tail, overlapped)
          repro.serve.fetch         device state to host (copies enqueued
                                    at begin), deferred stitch
          repro.serve.callback      ``on_result``: the caller's code
        repro.dispatch.settle       (batch)

``batch`` is the batch's index in ``ServingStats.batches``; a finalize
run by ``drain`` (or with ``overlap=False``) sits outside the next
dispatch.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import numpy as np
from jax.profiler import TraceAnnotation

from ..core import QUERY_KINDS
from .admission import AdmissionQueue, AdmissionTicket, PlannedBatch
from .dispatch import QueryDispatcher, SettledBatch


def unpack_levels(
    levels: np.ndarray,
    spans: dict[str, tuple[int, int]],
    n_nodes: int,
    packed: bool,
) -> dict[str, np.ndarray]:
    """Per-query result rows out of one batch's levels tensor.

    Packed (nTkMS) batches carry levels as [morsels, n_pad, lanes] uint8
    with 255 = unreached: lane-major flatten to one row per source, map the
    sentinel to -1, slice each query's span. Solo batches carry [rows,
    n_pad] with one row per source already. Both slice off graph padding
    columns. This is the single extraction path shared by the synchronous
    façade's ``flush`` and the serving loop — bit-identical by
    construction."""
    n = n_nodes
    levels = np.asarray(levels)
    if packed:
        per_src = (
            levels[:, :n, :].transpose(0, 2, 1).reshape(-1, n)
        ).astype(np.int32)
        per_src[per_src == 255] = -1
        return {qid: per_src[a:b] for qid, (a, b) in spans.items()}
    return {
        qid: levels[a:b, :n].astype(np.int32)
        for qid, (a, b) in spans.items()
    }


def _pctl(values: list[float], p: float) -> float:
    return float(np.percentile(np.asarray(values), p)) if values else float("nan")


@dataclasses.dataclass
class TenantStats:
    """One tenant's serving record. ``latencies_ms`` is every completed
    query (submit -> result delivered); ``warm_latencies_ms`` excludes
    queries served by a cold (engine-compiling) batch — SLO percentiles
    read the warm list."""

    submitted: int = 0
    completed: int = 0
    shed: int = 0
    deadline_misses: int = 0
    latencies_ms: list = dataclasses.field(default_factory=list)
    warm_latencies_ms: list = dataclasses.field(default_factory=list)

    def p50(self, warm: bool = True) -> float:
        return _pctl(self.warm_latencies_ms if warm else self.latencies_ms, 50)

    def p99(self, warm: bool = True) -> float:
        return _pctl(self.warm_latencies_ms if warm else self.latencies_ms, 99)


@dataclasses.dataclass
class ServingStats:
    """Loop-level counters. A *finalize* is one batch's deferred host
    materialization; it is *overlapped* when it ran while a later batch's
    phase 1 was in flight on device. ``cold_ms`` accumulates the wall of
    compiling batches — the cold-start cost reported separately from warm
    percentiles."""

    batches: int = 0
    cold_batches: int = 0
    finalizes: int = 0
    overlapped_finalizes: int = 0
    cold_ms: float = 0.0
    deltas_applied: int = 0  # graph mutations served mid-stream
    # submit -> launch of its batch, summed over dispatched queries
    queue_wait_s: float = 0.0
    dispatched_queries: int = 0
    tenants: dict = dataclasses.field(default_factory=dict)

    @property
    def overlap_occupancy(self) -> float:
        """Fraction of finalizes hidden behind a later batch's device
        work (0.0 in synchronous mode / single-batch streams)."""
        return (
            self.overlapped_finalizes / self.finalizes
            if self.finalizes
            else 0.0
        )

    def tenant(self, name: str) -> TenantStats:
        return self.tenants.setdefault(name, TenantStats())

    def _all(self, warm: bool) -> list:
        out: list = []
        for ts in self.tenants.values():
            out.extend(ts.warm_latencies_ms if warm else ts.latencies_ms)
        return out

    def p50(self, warm: bool = True) -> float:
        return _pctl(self._all(warm), 50)

    def p99(self, warm: bool = True) -> float:
        return _pctl(self._all(warm), 99)

    @property
    def completed(self) -> int:
        return sum(ts.completed for ts in self.tenants.values())

    @property
    def shed(self) -> int:
        return sum(ts.shed for ts in self.tenants.values())

    @property
    def deadline_misses(self) -> int:
        return sum(ts.deadline_misses for ts in self.tenants.values())


class ServingLoop:
    """Always-on serving loop over one graph: open-loop admission in,
    per-tenant results + SLO telemetry out.

    ``overlap=True`` (default) runs the double-buffered pipeline described
    in the module docstring; ``overlap=False`` is the strictly serial
    baseline (begin/settle/finalize back-to-back per batch) used as the
    differential side of the replay lock and the synchronous-flush
    baseline in benchmarks/serving_slo.py.

    ``max_batch_sources`` (forwarded to the admission queue) bounds one
    batch's pooled sources: under backlog the queue then drains as a
    SEQUENCE of capped batches with re-admission between them, so a new
    arrival joins the next batch's lane packing instead of waiting for
    the whole backlog — the knob that keeps an always-on stream's tail
    at O(batch) instead of O(backlog), and the pipeline fed with real
    inter-batch boundaries to overlap.

    ``clock`` is injectable (shared with the admission queue) so replay
    tests drive deadlines with a manual clock; ``on_result`` fires once
    per delivered query — submissions from inside the callback are legal
    and join the next plan round (the flush-during-drain path)."""

    def __init__(
        self,
        mesh=None,
        csr=None,
        *,
        dispatcher: QueryDispatcher | None = None,
        overlap: bool = True,
        tenant_quota: int | None = None,
        max_queue: int | None = None,
        max_batch_sources: int | None = None,
        clock: Callable[[], float] = time.perf_counter,
        on_result: Callable[[str, np.ndarray], None] | None = None,
        **dispatcher_kw,
    ):
        if dispatcher is None:
            # serving default: pow2-pad morsel counts so the stream's
            # variable pool sizes hit a bounded, pre-warmable set of
            # compiled shapes (one-shot query paths keep exact shapes)
            dispatcher_kw.setdefault("pad_pow2_morsels", True)
            dispatcher = QueryDispatcher(mesh, csr, **dispatcher_kw)
        self.dispatcher = dispatcher
        self.overlap = overlap
        self.clock = clock
        self.on_result = on_result
        self.admission = AdmissionQueue(
            n_nodes=dispatcher.csr.n_nodes,
            n_devices=dispatcher.mesh.size,
            avg_degree=dispatcher.csr.avg_degree,
            tenant_quota=tenant_quota,
            max_queue=max_queue,
            max_batch_sources=max_batch_sources,
            depth_hint=dispatcher.depth_hint,
            ms_per_iter=lambda: self._ms_per_iter,
            clock=clock,
        )
        self.stats = ServingStats()
        self.results: dict[str, np.ndarray] = {}
        # (settled batch, its plan entry, begin time, cold?) — the one
        # settled-but-unfinalized batch the pipeline carries
        self._tail: tuple[SettledBatch, PlannedBatch, float, bool] | None = None
        # measured serving rate for the admission layer's deadline math:
        # EWMA of warm-batch wall per slowest-lane iteration
        self._ms_per_iter: float | None = None
        # submit-time record per in-flight qid: (tenant, t_submit, t_deadline)
        self._meta: dict[str, tuple[str, float, float | None]] = {}
        # DeltaReports of every apply_delta served by this loop, in order
        self.delta_reports: list = []

    @property
    def graph_version(self) -> int:
        """The dispatcher's current ``operands_version`` (0 = unmutated)."""
        return self.dispatcher.operands_version

    # ------------------------------------------------------------- intake

    def submit(
        self,
        sources,
        tenant: str = "default",
        deadline_ms: float | None = None,
        qid: str | None = None,
        query_kind: str = "reach",
    ) -> AdmissionTicket:
        """Admit one query into the stream (see AdmissionQueue.submit).
        Shed submissions are counted against the tenant and never run.

        ``query_kind`` selects the scenario family (``core.QUERY_KINDS``):
        "reach" delivers per-source level rows as before; other kinds
        deliver their own result leaves — a [rows, n(, k)] array for
        single-leaf kinds ("topk_paths" dists, "ppr" mass), a dict of
        such arrays for multi-leaf kinds ("pattern_counts")."""
        now = self.clock()
        ticket = self.admission.submit(
            sources, tenant=tenant, deadline_ms=deadline_ms, qid=qid,
            now=now, query_kind=query_kind,
        )
        ts = self.stats.tenant(tenant)
        ts.submitted += 1
        if not ticket.admitted:
            ts.shed += 1
        else:
            t_deadline = (
                now + deadline_ms / 1e3 if deadline_ms is not None else None
            )
            self._meta[ticket.qid] = (tenant, now, t_deadline)
        return ticket

    # ------------------------------------------------------------ pipeline

    def pump(self) -> int:
        """One plan round: drain the admission queue into batches and push
        them through the pipeline. Returns the number of batches
        dispatched. The pipeline tail (the last settled batch) stays
        unfinalized so the NEXT pump's first batch can overlap it —
        ``drain()`` flushes it when the stream ends."""
        with TraceAnnotation("repro.serve.pump"):
            plan = self.admission.plan(now=self.clock())
            for qid, levels in plan.instant.items():
                self._deliver(qid, levels, cold=False)
            for qid, reason in plan.shed:
                meta = self._meta.pop(qid, None)
                if meta is not None:
                    self.stats.tenant(meta[0]).shed += 1
            for pb in plan.batches:
                self._dispatch(pb)
            return len(plan.batches)

    def _dispatch(self, pb: PlannedBatch) -> None:
        batch = self.stats.batches
        with TraceAnnotation("repro.serve.dispatch", batch=batch):
            t0 = self.clock()
            self.stats.queue_wait_s += sum(t0 - q.t_submit for q in pb.queries)
            self.stats.dispatched_queries += len(pb.queries)
            compiles0 = self.dispatcher.cache.compile_events
            inflight = self.dispatcher.begin_batch(
                pb.sources, policy=pb.policy, query_kind=pb.query_kind,
            )
            if self._tail is not None and self.overlap:
                # batch i's phase 1 is now in flight on device: the host is
                # free to stitch batch i-1 — the overlap this loop exists for
                self._finalize_tail(overlapped=True)
            settled = self.dispatcher.settle_batch(inflight)
            # compile_events (builds + first-seen morsel shapes), not
            # misses: a cached engine retracing on a new morsel count
            # stalls this batch on XLA exactly like a fresh build would
            cold = self.dispatcher.cache.compile_events > compiles0
            self.stats.batches += 1
            if cold:
                self.stats.cold_batches += 1
            self._tail = (settled, pb, t0, cold, batch)
            if not self.overlap:
                self._finalize_tail(overlapped=False)

    def _finalize_tail(self, overlapped: bool) -> None:
        settled, pb, t0, cold, batch = self._tail
        self._tail = None
        with TraceAnnotation("repro.serve.finalize", batch=batch,
                             overlapped=overlapped):
            self._finalize(settled, pb, t0, cold, overlapped)

    def _finalize(self, settled: SettledBatch, pb: PlannedBatch, t0: float,
                  cold: bool, overlapped: bool) -> None:
        with TraceAnnotation("repro.serve.fetch"):
            outcome = settled.finalize()
            t1 = self.clock()
            # counted reads: copies begin_batch enqueued are found in place
            read = settled.reads.read
            iters = read(outcome.result.iterations)
            if pb.query_kind == "reach":
                levels = read(outcome.result.state.levels)
            else:
                # non-reach kinds are never lane-packed (admission's
                # lanes_ok carve-out), so the state leaves are already one
                # row per source
                assert not pb.packed, pb.query_kind
                leaves = QUERY_KINDS[pb.query_kind].result_leaves
                arrs = {
                    leaf: read(getattr(outcome.result.state, leaf))
                    for leaf in leaves
                }
        self.stats.finalizes += 1
        if overlapped:
            self.stats.overlapped_finalizes += 1
        wall_ms = (t1 - t0) * 1e3
        depth = float(iters.max()) if iters.size else 0.0
        if cold:
            self.stats.cold_ms += wall_ms
        elif depth > 0:
            rate = wall_ms / depth
            self._ms_per_iter = (
                rate
                if self._ms_per_iter is None
                else 0.5 * self._ms_per_iter + 0.5 * rate
            )
        n = self.dispatcher.csr.n_nodes
        if pb.query_kind == "reach":
            out = unpack_levels(levels, pb.spans, n, pb.packed)
        else:
            # slice each query's span and the graph padding off every
            # result leaf the kind declares
            out = {
                qid: (
                    arrs[leaves[0]][a:b, :n]
                    if len(leaves) == 1
                    else {
                        leaf: arrs[leaf][a:b, :n] for leaf in leaves
                    }
                )
                for qid, (a, b) in pb.spans.items()
            }
        for q in pb.queries:
            self._deliver(q.qid, out[q.qid], cold)

    def _deliver(self, qid: str, levels: np.ndarray, cold: bool) -> None:
        t_done = self.clock()
        tenant, t_sub, t_deadline = self._meta.pop(
            qid, ("default", t_done, None)
        )
        ts = self.stats.tenant(tenant)
        ts.completed += 1
        lat_ms = (t_done - t_sub) * 1e3
        ts.latencies_ms.append(lat_ms)
        if not cold:
            ts.warm_latencies_ms.append(lat_ms)
        if t_deadline is not None and t_done > t_deadline:
            ts.deadline_misses += 1
        self.results[qid] = levels
        self.admission.complete(qid)
        if self.on_result is not None:
            with TraceAnnotation("repro.serve.callback"):
                self.on_result(qid, levels)

    # ------------------------------------------------------------ mutation

    def apply_delta(self, delta):
        """Mutate the served graph mid-stream, with a defined fence:
        every query admitted BEFORE this call is planned, dispatched and
        settled against the pre-delta graph (the queue drains through
        the normal pipeline first), and every query admitted after sees
        the post-delta graph — no batch is ever torn across versions
        (the dispatcher additionally pins each in-flight batch's operand
        buffers at begin time, so even the overlapped pipeline can never
        mix graphs inside one batch). The settled-but-unfinalized
        pipeline tail may ride through the delta: its device work is
        already complete against the old buffers, which its payload
        keeps alive until the stitch.

        Same-shape deltas keep every compiled engine warm — the serving
        stream sees a buffer swap, not a cold start. Returns the
        dispatcher's ``DeltaReport``."""
        while self.admission.pending():
            self.pump()
        report = self.dispatcher.apply_delta(delta)
        # stale-state sweep: the admission planner's pooled-policy and
        # deadline math key on avg_degree, captured at construction —
        # refresh it against the mutated graph
        self.admission.avg_degree = float(self.dispatcher.csr.avg_degree)
        self.stats.deltas_applied += 1
        self.delta_reports.append(report)
        return report

    # ------------------------------------------------------------- driving

    def drain(self) -> dict[str, np.ndarray]:
        """Serve until the queue is empty and the pipeline tail is
        finalized. Queries submitted from ``on_result`` mid-drain join the
        stream and are served before drain returns."""
        while self.admission.pending() or self._tail is not None:
            if self.admission.pending():
                self.pump()
            elif self._tail is not None:
                self._finalize_tail(overlapped=False)
        return self.results

    def run_stream(self, arrivals: list[dict]) -> dict[str, np.ndarray]:
        """Serve an open-loop arrival schedule: each entry is a dict with
        ``t_ms`` (offset from stream start) and either ``sources`` (a
        query arrival, with optional ``tenant`` / ``deadline_ms`` /
        ``qid``) or ``delta`` (a ``GraphDelta`` mutation applied at its
        scheduled time through ``apply_delta``'s version fence — queries
        scheduled before it are served on the old graph, after it on the
        new). Arrivals are admitted when their time comes whether or not
        the loop is keeping up — queueing delay under overload is the
        point of open-loop measurement — and the stream is drained at
        the end."""
        order = sorted(range(len(arrivals)), key=lambda i: arrivals[i]["t_ms"])
        t0 = self.clock()
        i = 0
        while i < len(order):
            now_ms = (self.clock() - t0) * 1e3
            while i < len(order) and arrivals[order[i]]["t_ms"] <= now_ms:
                a = arrivals[order[i]]
                i += 1
                if "delta" in a:
                    self.apply_delta(a["delta"])
                    continue
                self.submit(
                    a["sources"], tenant=a.get("tenant", "default"),
                    deadline_ms=a.get("deadline_ms"), qid=a.get("qid"),
                    query_kind=a.get("query_kind", "reach"),
                )
            if self.admission.pending():
                self.pump()
            elif self._tail is not None:
                self._finalize_tail(overlapped=False)
            elif i < len(order):
                wait = arrivals[order[i]]["t_ms"] / 1e3 - (self.clock() - t0)
                if wait > 0:
                    time.sleep(min(wait, 0.005))
        self.drain()
        return self.results
