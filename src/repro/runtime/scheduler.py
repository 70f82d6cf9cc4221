"""Synchronous façade over the layered serving core (paper §5.4/§5.6).

The adaptive morsel runtime that used to live in this one module is now
three layers (docs/serving.md maps them to the paper's concepts):

- **admission** (``runtime/admission.py``) — multi-tenant queue, quotas,
  the Fig 14 pack-vs-solo rule, deadline-aware lane packing with eviction,
  load shedding;
- **dispatch** (``runtime/dispatch.py``) — the engine cache and the
  two-phase hybrid (learned phase-1 budgets, gang-scheduled phase-2
  resume, online threshold refits), plus the split-phase batch API
  (``begin_batch`` / ``settle_batch`` / ``finalize_batch``);
- **service** (``runtime/service.py``) — the always-on ``ServingLoop``
  overlapping batch i's deferred host work with batch i+1's device work,
  with per-tenant SLO telemetry.

``AdaptiveScheduler`` survives here as the thin synchronous façade every
pre-split caller (tests, benchmarks, the closed-loop driver) keeps using
unmodified: it IS the dispatch layer (subclass — ``query``, the engine
cache, stats, and the learners are inherited, semantics unchanged), and
its ``submit``/``flush`` run the admission layer's planner with no quotas
and no deadlines, which reproduces the legacy pooled batching bit-for-bit
(same qid naming, same arrival-order packing, same per-query result rows
— the replay corpus in tests/test_serving.py pins façade == ServingLoop).
"""
from __future__ import annotations

import numpy as np

from .admission import AdmissionQueue
from .dispatch import (  # noqa: F401  (re-exported: pre-split import site)
    EngineCache,
    EngineKey,
    QueryDispatcher,
    QueryOutcome,
    SchedulerStats,
    _pow2ceil,
)
from .service import unpack_levels


class AdaptiveScheduler(QueryDispatcher):
    """Compile-once, serve-many recursive-query runtime over one graph —
    the dispatch layer (see ``QueryDispatcher`` for the execution/learning
    contract) plus the legacy synchronous ``submit``/``flush`` admission
    surface. For the always-on overlapped loop with tenant SLOs, drive the
    same dispatcher through ``runtime.service.ServingLoop`` instead."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # no quotas, no deadlines, no estimators: the admission planner in
        # this configuration is exactly the legacy flush batching
        self._admission = AdmissionQueue(
            n_nodes=self.csr.n_nodes,
            n_devices=self.mesh.size,
            avg_degree=self.csr.avg_degree,
        )
        self.admissions = {"ntkms": 0, "per_query": 0}

    def apply_delta(self, delta):
        """Graph mutation through the dispatcher, plus the façade's own
        stale-state refresh: its private admission queue captured
        ``avg_degree`` at construction and the pooled-policy decision in
        ``flush`` would keep keying on the pre-delta density."""
        report = super().apply_delta(delta)
        self._admission.avg_degree = float(self.csr.avg_degree)
        return report

    # ----------------------------------------------------------- admission

    def submit(self, sources, qid: str | None = None) -> str:
        """Queue one tenant's query for the next ``flush``."""
        return self._admission.submit(sources, qid=qid).qid

    def flush(self) -> dict[str, np.ndarray]:
        """Run all queued queries; returns {qid: levels [k, n_nodes] int32}
        (-1 = unreached), one row per submitted source.

        Admission rule (paper Fig 14): pack every tenant's sources into
        shared 64-wide MS-BFS lane morsels only when ``recommend_policy``
        says the pooled batch saturates the lanes; otherwise each query
        runs by itself under the hybrid (packing with too few sources
        would scan the graph for mostly-empty lanes).
        """
        if not self._admission.pending():
            return {}
        plan = self._admission.plan()
        out: dict[str, np.ndarray] = dict(plan.instant)
        packed = any(pb.packed for pb in plan.batches)
        if plan.batches:
            self.admissions["ntkms" if packed else "per_query"] += 1
        for pb in plan.batches:
            outcome = self.query(pb.sources, policy=pb.policy)
            out.update(unpack_levels(
                np.asarray(outcome.result.state.levels), pb.spans,
                self.csr.n_nodes, pb.packed,
            ))
            for q in pb.queries:
                self._admission.complete(q.qid)
        for qid in plan.instant:
            self._admission.complete(qid)
        return out
