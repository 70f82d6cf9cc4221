"""Dispatch layer of the serving core: engine cache + two-phase hybrid.

This is the middle layer of the three-layer runtime (see docs/serving.md):

    admission  (runtime/admission.py) — who runs, when, in which morsel pack
    dispatch   (this module)          — how one admitted batch executes
    service    (runtime/service.py)   — the always-on loop overlapping batches

``QueryDispatcher`` owns everything about *executing* one batch of source
nodes: the compiled-engine cache, the paper's two-phase hybrid (nTkS phase 1
under a learned budget, gang-scheduled phase-2 re-dispatch of survivors),
backend recommendation, and the online policy learners (per-bucket budget
model + in-flight direction-threshold refits). Semantics are unchanged from
the pre-split ``AdaptiveScheduler`` — that class survives in
``runtime/scheduler.py`` as a thin synchronous façade over this layer plus
the admission queue, so every existing caller sees the same surface.

What is new here is the **split-phase batch API** the serving loop pipelines
on:

- ``begin_batch``  — choose policy/backend/budget and *dispatch* phase 1
  asynchronously (no ``block_until_ready``): jax async dispatch returns
  immediately with device futures, so the host is free while the device
  scans.
- ``settle_batch`` — block on the phase-1 frontier, re-dispatch survivors
  (phase 2, also async), block only on the tiny per-morsel iteration
  counters, run post-batch learning, and return a ``SettledBatch`` whose
  full result state is still on device.
- ``finalize_batch`` — the deferred host work: materialize the final state,
  stitch phase-2 survivors back over the phase-1 state, and hand back the
  completed ``QueryOutcome``. The serving loop runs this *after* dispatching
  the next batch's phase 1, so host-side stitching overlaps device compute
  (the double-buffered invocation: at most one settled-but-unfinalized batch
  rides behind the in-flight one, and the phase-1 buffers it consumed are
  dropped — donated — as soon as the stitch completes).

``query()`` composes the three steps back-to-back, which is bit-identical
to the pre-split synchronous path: the split only moves *when* the host
blocks, never what any morsel computes. Learning stays host-serial —
``settle_batch(i)`` always precedes ``begin_batch(i+1)`` — so budgets,
thresholds, traces, and counters are a deterministic function of the batch
stream regardless of overlap (the seeded-replay lock in
tests/test_serving.py).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import (
    BackendCostProbe,
    BudgetModel,
    DirectionThresholds,
    POLICIES,
    ExtendSpec,
    IFEResult,
    MorselPolicy,
    QUERY_KINDS,
    as_spec,
    build_engine,
    build_gang_resume_engine,
    build_resume_engine,
    count_budget_mispredicts,
    degree_bucket,
    fit_direction_thresholds,
    gang_handoff,
    gang_scatter_back,
    hybrid_phases,
    pad_sources,
    pow2ceil as _pow2ceil,
    prepare_graph,
    recommend_backend,
    recommend_k,
    recommend_policy,
)
from ..core.dispatcher import _axes_size
from ..core.extend import GraphOperands, effective_csr
from ..graph.csr import CSRGraph
from ..graph.delta import (
    DeltaReport,
    GraphDelta,
    apply_delta_csr,
    diff_effective,
    fold_operands,
)


@dataclasses.dataclass(frozen=True)
class EngineKey:
    """Cache identity of one compiled engine. ``kind`` distinguishes the
    static single-phase program, the per-shard-sync phase-1 program, and
    the state-resuming phase-2 program — same policy tuple, different HLO.
    ``extend`` carries the extension backend + direction mode (an
    ``ExtendSpec``): each backend is a different scan program. ``stats``
    marks the sample-tapped flavor (``build_engine(collect_stats=True)``
    returns ``(result, per-iteration stats)`` — same result state,
    different HLO).

    ``operands_epoch`` is the mutable-graph shape generation of the
    operand structures this engine scans: a ``GraphDelta`` that folds
    in place (same shapes, buffers swapped) leaves the epoch alone — the
    compiled engine stays warm and simply receives the new buffers at
    call time — while a delta that forces a structure rebuild with new
    shapes bumps it, so stale keys are invalidated and the next query
    compiles against the new shapes. Deliberately NOT the full
    ``operands_version``: keying on the version would cold-compile on
    every delta, which is the exact cliff this design removes."""

    kind: str  # "static" | "phase1" | "resume"
    policy: MorselPolicy
    edge_compute: str
    n_nodes_padded: int
    max_iters: int
    state_layout: str
    extend: ExtendSpec = ExtendSpec()
    stats: bool = False
    operands_epoch: int = 0


class EngineCache:
    """Compiled-QueryEngine cache: bounded LRU with hit/miss accounting
    and a public mapping surface. Hits and misses are additionally
    counted per engine kind (static/phase1/resume/gang) so the gang
    path's compile footprint is observable.

    ``max_entries`` bounds the store (None = unbounded): a shape-diverse
    serving stream — many (policy, backend, morsel-shape) combinations —
    previously grew both the engine dict and the ``note_shape`` ledger
    without bound. Least-recently-*used* entries evict first
    (``get_or_build`` hits refresh recency), the evicted key's shape
    ledger goes with it, and a later rebuild of an evicted key is a
    fresh ``miss`` + fresh shape misses — exactly what it costs the
    serving loop, so ``compile_events`` stays an honest cold counter.

    Iteration/lookup is part of the API — callers that count or inspect
    compiles use ``len(cache)``, ``iter(cache)`` / ``keys()``, ``key in
    cache``, ``get(key)`` and ``items()`` instead of reaching into the
    private store."""

    # Default bound: far above any one graph's engine population (a full
    # backend × policy × kind × budget sweep compiles a few dozen), so
    # eviction only engages on genuinely unbounded key streams.
    DEFAULT_MAX_ENTRIES = 128

    def __init__(self, max_entries: int | None = DEFAULT_MAX_ENTRIES):
        if max_entries is not None and max_entries < 1:
            raise ValueError(f"max_entries must be >= 1: {max_entries}")
        self.max_entries = max_entries
        self._engines: collections.OrderedDict[EngineKey, Any] = (
            collections.OrderedDict()
        )
        self.hits = 0
        self.misses = 0
        self.hits_by_kind: collections.Counter = collections.Counter()
        self.misses_by_kind: collections.Counter = collections.Counter()
        # morsel-count shapes each engine has been called with: a cached
        # engine hit can still pay a full XLA retrace when the batch's
        # morsel count is new — invisible to hit/miss, so tracked apart
        self._shapes: dict[EngineKey, set] = {}
        self.shape_misses = 0
        self.evictions = 0  # LRU capacity evictions
        self.invalidations = 0  # entries dropped by invalidate()

    @property
    def compile_events(self) -> int:
        """Engine builds plus first-time input shapes: everything that
        stalls a batch on XLA. Serving's warm/cold split keys off the
        delta of this, not ``misses`` — a hit engine retracing on a new
        morsel count is just as cold as a fresh build."""
        return self.misses + self.shape_misses

    def note_shape(self, key: EngineKey, shape) -> bool:
        """Record that ``key``'s engine is about to run with input
        ``shape`` (any hashable; callers pass the morsel-axis tuple).
        Returns True — and counts a ``shape_miss`` — the first time this
        (engine, shape) pair is seen."""
        seen = self._shapes.setdefault(key, set())
        if shape in seen:
            return False
        seen.add(shape)
        self.shape_misses += 1
        return True

    def __len__(self) -> int:
        return len(self._engines)

    def __iter__(self):
        return iter(self._engines)

    def __contains__(self, key: EngineKey) -> bool:
        return key in self._engines

    def keys(self):
        """The cached ``EngineKey``s, in compile order."""
        return self._engines.keys()

    def items(self):
        """(EngineKey, engine) pairs, in compile order."""
        return self._engines.items()

    def get(self, key: EngineKey, default=None):
        """Cached engine for ``key`` (no hit/miss accounting, no build)."""
        return self._engines.get(key, default)

    def count_by_kind(self, kind: str) -> int:
        """How many compiled engines of one ``EngineKey.kind`` are cached."""
        return sum(1 for k in self._engines if k.kind == kind)

    def get_or_build(self, key: EngineKey, builder: Callable[[], Any]):
        kind = getattr(key, "kind", "?")
        eng = self._engines.get(key)
        if eng is not None:
            self.hits += 1
            self.hits_by_kind[kind] += 1
            self._engines.move_to_end(key)  # LRU recency refresh
            return eng
        self.misses += 1
        self.misses_by_kind[kind] += 1
        eng = builder()
        self._engines[key] = eng
        if (
            self.max_entries is not None
            and len(self._engines) > self.max_entries
        ):
            old_key, _ = self._engines.popitem(last=False)
            self._shapes.pop(old_key, None)
            self.evictions += 1
        return eng

    def invalidate(self, predicate: Callable[[EngineKey], bool]) -> int:
        """Drop every cached engine whose key matches ``predicate`` (and
        its shape ledger). Returns the number of entries removed. The
        dispatcher calls this after a shape-changing ``GraphDelta`` with
        an epoch-mismatch predicate, so exactly the engines compiled
        against rebuilt structures recompile — a re-query of an
        invalidated key accounts as a fresh miss + fresh shape misses,
        like any other cold compile."""
        stale = [k for k in self._engines if predicate(k)]
        for k in stale:
            del self._engines[k]
            self._shapes.pop(k, None)
        self.invalidations += len(stale)
        return len(stale)


@dataclasses.dataclass
class QueryOutcome:
    """One served batch: result + how the runtime chose to execute it.

    ``redispatched`` counts the morsels *handed* to phase 2 (the phase-1
    survivors); ``resumed_ganged``/``resumed_serial`` split it by how they
    actually ran (one batched gang dispatch vs the per-morsel engine), so
    ``redispatched == resumed_ganged + resumed_serial`` always holds.
    ``gang_width`` is the pow2-padded width of the gang dispatch (0 when no
    gang ran; the max across chunks for chunked batches).

    The ``budget_*`` counters classify this batch's REAL morsels against
    the phase-1 budget (``core.policies.count_budget_mispredicts``
    semantics: too_low = survivors that paid a re-dispatch, too_high =
    morsels that converged strictly under half the budget, inert_slots =
    budget slack over converged morsels); zero on static runs."""

    result: IFEResult
    policy: str  # base policy name ("ntks", "ntkms", ...)
    hybrid: bool  # did the two-phase hybrid path run?
    redispatched: int  # morsels handed to phase 2
    phase_ms: dict  # {"phase1": ms, "phase2": ms}; static runs use phase1
    phase1_budget: int  # iteration cap phase 1 ran under (0 = static)
    resumed_ganged: int = 0  # survivors resumed in a gang dispatch
    resumed_serial: int = 0  # survivors resumed one-morsel-at-a-time
    gang_width: int = 0  # padded gang width (0 = no gang dispatch)
    budget_too_low: int = 0  # real morsels the budget undershot
    budget_too_high: int = 0  # real morsels a smaller pow2 budget covered
    budget_inert_slots: int = 0  # budget slack over converged real morsels
    budget_observed: int = 0  # real morsels the counters classified


@dataclasses.dataclass
class SchedulerStats:
    """Cumulative runtime counters across every served batch.

    The ``redispatched = resumed_ganged + resumed_serial`` split mirrors
    QueryOutcome; ``gangs``/``gang_slots`` make gang occupancy observable
    (survivors actually ganged over padded slots dispatched)."""

    queries: int = 0
    hybrid_runs: int = 0  # batches that took the two-phase path
    redispatched: int = 0  # survivors handed to phase 2
    resumed_ganged: int = 0
    resumed_serial: int = 0
    gangs: int = 0  # gang dispatches issued
    gang_slots: int = 0  # padded gang widths summed over dispatches
    phase1_ms: float = 0.0
    phase2_ms: float = 0.0
    budget_too_low: int = 0  # phase-1 budget mispredicts (QueryOutcome)
    budget_too_high: int = 0
    budget_inert_slots: int = 0
    budget_observed: int = 0
    refits: int = 0  # in-flight direction-threshold refits
    deltas: int = 0  # GraphDeltas applied (apply_delta calls)

    @property
    def gang_occupancy(self) -> float:
        """Real survivors per padded gang slot (1.0 = pow2-tight gangs)."""
        return self.resumed_ganged / self.gang_slots if self.gang_slots else 0.0

    @property
    def budget_mispredict_rate(self) -> float:
        """Mispredicted real morsels per observed real morsel (too_low +
        too_high over observed; 0.0 before any hybrid batch)."""
        if not self.budget_observed:
            return 0.0
        return (self.budget_too_low + self.budget_too_high) / (
            self.budget_observed
        )

    def record(self, outcome: "QueryOutcome") -> None:
        self.queries += 1
        if outcome.hybrid:
            self.hybrid_runs += 1
        self.redispatched += outcome.redispatched
        self.resumed_ganged += outcome.resumed_ganged
        self.resumed_serial += outcome.resumed_serial
        self.phase1_ms += outcome.phase_ms.get("phase1", 0.0)
        self.phase2_ms += outcome.phase_ms.get("phase2", 0.0)
        self.budget_too_low += outcome.budget_too_low
        self.budget_too_high += outcome.budget_too_high
        self.budget_inert_slots += outcome.budget_inert_slots
        self.budget_observed += outcome.budget_observed


@dataclasses.dataclass
class OperandBundle:
    """One device-placed operand bundle plus its mutability bookkeeping.

    ``version`` is the ``operands_version`` the buffers currently hold;
    ``epochs`` counts, per structure slot, how many times a delta had to
    REBUILD that structure with new shapes (in-place folds don't bump
    it) — ``EngineKey.operands_epoch`` derives from these. ``host`` is
    the lazily created writable numpy mirror deltas fold into (one
    device→host copy on the first delta, then reused forever).

    ``policy``/``spec`` record which (policy, ExtendSpec) pair first
    materialized the bundle — provenance for tooling that needs to
    rebuild the same operand set from scratch (benchmarks/mutable_ops.py
    prices the rebuild baseline off it).

    Iterates as ``(ops, n_pad)`` so the historical
    ``g, n_pad = self._graph_for(...)`` unpacking keeps working."""

    ops: GraphOperands
    n_pad: int
    version: int = 0
    epochs: dict = dataclasses.field(default_factory=dict)
    host: Any = None
    policy: Any = None
    spec: Any = None

    def __iter__(self):
        return iter((self.ops, self.n_pad))


@dataclasses.dataclass
class InflightBatch:
    """A batch whose phase 1 (or static engine) has been *dispatched* but
    not blocked on: the device futures ride in ``payload`` until
    ``settle_batch``. ``kind`` routes the settle path:

    - "hybrid"  — phase-1 futures from the sync="shard" engine
    - "static"  — single-engine futures (non-hybrid-eligible batch)
    - "chunked" — oversized batch that will run the synchronous chunked
      loop at settle time (the in-flight cap splits it; serving streams
      rarely hit this — admission packs under the cap)."""

    kind: str
    name: str  # resolved policy name for QueryOutcome.policy
    n_real: int
    buckets: np.ndarray
    payload: Any


@dataclasses.dataclass
class SettledBatch:
    """A batch past its device sync points: iterations, counters, and
    learning are done; the final result *state* may still live on device.
    ``finalize()`` (idempotent) runs the deferred host stitch and returns
    the completed ``QueryOutcome``."""

    outcome: QueryOutcome
    _materialize: Callable[[], IFEResult] | None = None

    @property
    def finalized(self) -> bool:
        return self._materialize is None

    def finalize(self) -> QueryOutcome:
        if self._materialize is not None:
            self.outcome.result = self._materialize()
            self._materialize = None
        return self.outcome


class QueryDispatcher:
    """Compile-once, serve-many execution layer over one graph.

    ``adaptive=True`` enables two-phase hybrid dispatch for any policy
    with source morsels (nTkS/nTkMS/1T1S) — pinning a policy picks WHICH
    morsels are issued, not the execution mode, and the hybrid is
    bit-identical in result state. Replicated state always qualifies; the
    sharded layout qualifies when ``gang_resume`` is on (its phase 2 is
    the gang engine + reduce-scatter merge — there is no serial sharded
    resume). ``adaptive=False`` degrades everything to the static
    dispatcher (one engine per policy), which is also the fallback for
    nT1S (no source morsels to re-dispatch).

    ``gang_resume=False`` pins phase 2 to the legacy one-morsel-at-a-time
    resume (kept as the differential baseline the parity corpus compares
    the gang against).

    ``online_adapt=True`` (the default) closes the policy feedback loop
    on the live stream:

    - the phase-1 iteration budget comes from a per-(dataset-family,
      source-degree-bucket) ``BudgetModel`` updated with every flushed
      batch's real-morsel convergence depths (the legacy global pow2 p90
      deque remains the empty-model cold path, and ``phase1_iters``
      still pins the budget outright, bypassing the learner);
    - phase-1 AND phase-2 (resume/gang) engines run with the
      ``collect_stats`` sample tap, and the accumulated per-iteration
      (m_frontier, m_unexplored, scan-cost / measured-cost)
      records are refit into ``direction_thresholds`` every
      ``refit_every`` batches (``fit_direction_thresholds`` over
      ``online_trace()``), so ``backend="recommend"`` serves alpha/beta
      tracking the live stream instead of a stale bench trace — unless
      a table was supplied explicitly, which pins it (only a manual
      ``refit_thresholds()`` call overrides a pin).

    Both loops only move iteration slots / scan layouts — results stay
    bit-identical with the learner on, off, or mid-refit — and both are
    deterministic functions of the served batch stream (same seeded
    stream => bit-identical budgets, thresholds, and mispredict
    counters, with or without ``gang_resume`` and with or without the
    serving loop's phase overlap — ``settle_batch(i)`` always precedes
    ``begin_batch(i+1)``, so the learners never see a reordered stream).
    ``online_adapt=False`` pins the legacy static behavior (global-p90
    budget, fixed thresholds) as the differential baseline.
    """

    def __init__(
        self,
        mesh,
        csr: CSRGraph,
        max_deg: int | None = None,
        max_iters: int = 64,
        adaptive: bool = True,
        phase1_iters: int | None = None,
        max_inflight: int | None = None,
        backend="recommend",
        direction_thresholds: DirectionThresholds | str | Path | None = None,
        family: str | None = None,
        gang_resume: bool = True,
        online_adapt: bool = True,
        budget_model: BudgetModel | None = None,
        refit_every: int = 16,
        sample_window: int = 2048,
        pad_pow2_morsels: bool = False,
        cost: str = "auto",
        stream: bool | None = None,
    ):
        self.mesh = mesh
        self.csr = csr
        self.max_deg = max_deg
        self.max_iters = max_iters
        # streamed (shard-at-a-time, multi-host-aware) operand placement;
        # None = prepare_graph's auto rule (stream iff multi-process)
        self.stream = stream
        self.adaptive = adaptive
        self.phase1_iters = phase1_iters  # pin the phase-1 budget (tests)
        self.max_inflight = max_inflight  # override recommend_k (tests)
        # default extension backend; per-query override via query(backend=).
        # The default IS "recommend": recommend_backend picks the scan
        # layout per batch (direction-optimized binned pull for the
        # BFS family), bit-identical to any explicit choice.
        self.backend = backend
        # fitted per-(family, degree-bucket) alpha/beta for the direction
        # switch (core.policies.fit_direction_thresholds); a path loads a
        # BENCH_direction_opt.json trace file. None = Beamer defaults.
        if isinstance(direction_thresholds, (str, Path)):
            direction_thresholds = fit_direction_thresholds(
                direction_thresholds
            )
        self.direction_thresholds = direction_thresholds
        # an explicitly supplied table is a pin: the auto-refit cadence
        # must not silently replace what the caller asked to serve (an
        # explicit refit_thresholds() call still overrides)
        self._thresholds_pinned = direction_thresholds is not None
        self.family = family  # dataset family key for threshold lookup
        self.gang_resume = gang_resume
        self.online_adapt = online_adapt
        # per-(family, source-degree-bucket) phase-1 budget learner; the
        # global deque below remains its empty-model cold path
        self.budget_model = (
            budget_model
            if budget_model is not None
            else (BudgetModel() if online_adapt else None)
        )
        self.refit_every = max(1, int(refit_every))
        # serving knob: round every batch's morsel count up to a pow2 so a
        # stream of arbitrary pool sizes hits O(log max-pool) compiled
        # shapes instead of one XLA retrace per distinct queue depth; pad
        # morsels are inert (0-iteration) and invisible to learning
        # (n_real) and extraction (spans). Off by default: the one-shot
        # query paths keep their historical exact shapes.
        self.pad_pow2_morsels = pad_pow2_morsels
        # threshold-fit cost model: "slots" scores directions by scan-slot
        # counts (deterministic, the only mode that existed before the
        # measured-cost tap); "measured" converts slots to wall-ms via the
        # BackendCostProbe's per-backend ms/slot rates; "auto" = measured
        # on real TPUs, slots on CPU/interpret (where probe timings are
        # noise and replay determinism matters more than calibration)
        if cost == "auto":
            cost = "measured" if jax.default_backend() == "tpu" else "slots"
        if cost not in ("slots", "measured"):
            raise ValueError(f"unknown cost mode: {cost!r}")
        self.cost_mode = cost
        self.cost_probe = BackendCostProbe()
        self._cost_rates: dict[int, dict] = {}  # n_pad -> probe rates
        self.stats = SchedulerStats()
        self.cache = EngineCache()
        self._graphs: dict[tuple, OperandBundle] = {}
        # monotonically increasing graph-mutation counter: bumped by every
        # apply_delta and stamped on each bundle's (host-side) version tag
        self.operands_version = 0
        # global pow2-p90 fallback budget (cold start / online_adapt off):
        # p90 per-morsel iteration count of recent batches — the per-bucket
        # BudgetModel supersedes it as soon as it holds samples.
        self._iter_p90s: collections.deque = collections.deque(maxlen=32)
        # per-iteration (n_f, m_f, m_u, pull-cost) samples from the phase-1
        # stats tap, grouped by the n_pad they were measured against (the
        # beta predicate compares n_f*beta to the PADDED row count)
        self._dir_samples: dict[int, collections.deque] = {}
        self._sample_window = int(sample_window)
        self._batches_since_refit = 0

    # ------------------------------------------------------------- engines

    @staticmethod
    def _bundle_key(policy: MorselPolicy, spec: ExtendSpec) -> tuple:
        return (
            policy.graph_axes,
            spec.needs_rev,
            spec.needs_binned,
            spec.needs_binned_pack,
            spec.needs_blocks,
            spec.pad_block,
        )

    def _graph_for(
        self, policy: MorselPolicy, spec: ExtendSpec = ExtendSpec()
    ) -> OperandBundle:
        # operand bundles are shared by every spec needing the same physical
        # structures (rev/blocks), not per backend string. Sharing is safe
        # across graph versions because a delta folds into the SHARED bundle
        # and bumps its version/epochs once: a spec can never observe a
        # bundle pinned at a different operands_version than its siblings —
        # in-flight batches instead pin the resolved (ops, epoch) pair at
        # begin time (see _begin_hybrid), so they keep their pre-delta
        # buffers without ever re-resolving through this cache.
        key = self._bundle_key(policy, spec)
        if key not in self._graphs:
            # pad for mesh.size so every policy's graph shares one n_pad and
            # phase-1 state can resume on the phase-2 graph unchanged
            ops, n_pad = prepare_graph(
                self.csr, self.mesh, policy, self.max_deg,
                pad_shards=self.mesh.size, extend=spec,
                version=self.operands_version, stream=self.stream,
            )
            self._graphs[key] = OperandBundle(
                ops=ops, n_pad=n_pad, version=self.operands_version,
                policy=policy, spec=spec,
            )
        return self._graphs[key]

    def _spec_epoch(self, bundle: OperandBundle, spec: ExtendSpec) -> int:
        """The shape generation an engine scanning ``spec``'s structures
        out of ``bundle`` compiles against: the max epoch over exactly
        the structures the spec scans — a rebuild of the blocks operand
        must not invalidate push engines sharing the bundle."""
        e = bundle.epochs
        v = e.get("fwd", 0)
        if spec.needs_rev:
            v = max(v, e.get("rev", 0))
        if spec.needs_binned:
            v = max(v, e.get("rev_binned", 0))
        if spec.needs_binned_pack:
            v = max(v, e.get("rev_binned_pack", 0))
        if spec.needs_blocks:
            v = max(v, e.get("blocks", 0))
        return v

    # ------------------------------------------------------- graph mutation

    def apply_delta(self, delta: GraphDelta) -> DeltaReport:
        """Mutate the served graph in place: fold ``delta`` into every
        cached operand bundle instead of rebuilding from scratch.

        Per bundle, only the structures whose content actually changed
        are re-placed on device (untouched device arrays are reused),
        and only structures whose SHAPES changed (a row overflowed its
        ELL width, a degree left every existing bucket's invariant
        range, a new block tile found no free slot) bump their epoch —
        so a same-shape delta leaves every compiled engine warm and
        ``cache.compile_events`` flat, while a shape-changing delta
        invalidates exactly the engine keys whose scanned structures
        were rebuilt. Queries planned after this call see the new graph;
        batches already in flight keep the operand buffers they pinned
        at begin time (never torn)."""
        new_csr = apply_delta_csr(self.csr, delta)
        old_eff = effective_csr(self.csr, self.max_deg)
        new_eff = effective_csr(new_csr, self.max_deg)
        diff = diff_effective(old_eff, new_eff, delta)
        self.operands_version += 1
        n_changed = n_rebuilt = moves = 0
        for key, bundle in self._graphs.items():
            if bundle.host is None:
                # first delta against this bundle: one device->host copy
                # into a writable mirror (np.array, not asarray — jax
                # buffer views are read-only), reused by every later fold
                bundle.host = jax.tree.map(
                    lambda x: np.array(x), bundle.ops
                )
            structs, rep = fold_operands(
                bundle.host, old_eff, new_eff, diff
            )
            bundle.host = GraphOperands(
                **structs, version=self.operands_version
            )
            bundle.ops = self._place_structures(key[0], bundle, rep)
            bundle.version = self.operands_version
            for s, r in rep.reshaped.items():
                if r:
                    bundle.epochs[s] = bundle.epochs.get(s, 0) + 1
            n_changed += rep.n_changed
            n_rebuilt += rep.n_reshaped
            moves += rep.binned_moves
        self.csr = new_csr
        # stale-state sweep: measured cost rates and probes were taken
        # against the pre-delta operands, and the online learners are
        # keyed to the PRE-delta degree buckets — serving them across the
        # fence would budget/steer post-delta batches with buckets their
        # sources no longer belong to
        self._cost_rates.clear()
        self.invalidate_learned_state()
        invalidated = self.cache.invalidate(self._engine_stale)
        self.stats.deltas += 1
        return DeltaReport(
            version=self.operands_version,
            n_adds=delta.n_adds,
            n_dels=delta.n_dels,
            changed_edges=diff.n_changed_edges,
            dirty_fwd_rows=int(len(diff.fwd_dirty)),
            dirty_rev_rows=int(len(diff.rev_dirty)),
            bundles=len(self._graphs),
            structures_changed=n_changed,
            structures_rebuilt=n_rebuilt,
            binned_moves=moves,
            engines_invalidated=invalidated,
        )

    def invalidate_learned_state(self) -> None:
        """Reset the online learners whose keys or samples embed the
        pre-delta degree distribution: the per-bucket budget windows,
        the global-p90 fallback deque, and the direction-threshold
        sample store (plus the refitted table itself, unless the caller
        pinned one — a pin is an explicit instruction to serve that
        table regardless of the stream). Part of ``apply_delta``'s
        fence; callers that rebuild operands out-of-band can invoke it
        directly."""
        if self.budget_model is not None:
            self.budget_model.reset()
        self._iter_p90s.clear()
        self._dir_samples.clear()
        self._batches_since_refit = 0
        if not self._thresholds_pinned:
            self.direction_thresholds = None

    def _place_structures(
        self, graph_axes, bundle: OperandBundle, rep
    ) -> GraphOperands:
        """Device-place exactly the structures a fold changed, with
        ``prepare_graph``'s sharding rule (leading row/stacked-shard axis
        over the policy's graph axes, everything else replicated);
        unchanged structures keep their existing device arrays."""
        ga = graph_axes
        mesh = self.mesh
        shard = lambda x: NamedSharding(
            mesh, P(ga if ga else None, *(None,) * (np.ndim(x) - 1))
        )
        old, host = bundle.ops, bundle.host
        # one batched transfer for every changed structure (a device_put
        # per leaf pays a dispatch round-trip each; the pytree form issues
        # them together)
        dirty = {
            name: getattr(host, name)
            for name in ("fwd", "rev", "rev_binned", "rev_binned_pack",
                         "blocks")
            if rep.changed[name]
        }
        placed = jax.device_put(dirty, jax.tree.map(shard, dirty))
        pick = lambda name, old_s: placed.get(name, old_s)
        return GraphOperands(
            fwd=pick("fwd", old.fwd),
            rev=pick("rev", old.rev),
            rev_binned=pick("rev_binned", old.rev_binned),
            rev_binned_pack=pick("rev_binned_pack", old.rev_binned_pack),
            blocks=pick("blocks", old.blocks),
            version=self.operands_version,
        )

    def _engine_stale(self, key: EngineKey) -> bool:
        """True when ``key`` was compiled against operand shapes an
        applied delta has since rebuilt (its epoch no longer matches the
        bundle's current epoch for the structures it scans)."""
        bundle = self._graphs.get(self._bundle_key(key.policy, key.extend))
        if bundle is None:
            return False
        return key.operands_epoch != self._spec_epoch(bundle, key.extend)

    def engine(
        self,
        kind: str,
        policy: MorselPolicy,
        edge_compute: str,
        n_pad: int,
        max_iters: int | None = None,
        state_layout: str = "replicated",
        extend: ExtendSpec = ExtendSpec(),
        operands=None,
        collect_stats: bool = False,
        morsel_shape=None,
        epoch: int | None = None,
    ):
        cap = int(max_iters if max_iters is not None else self.max_iters)
        if operands is None and (
            extend.needs_binned or extend.needs_rev or extend.needs_blocks
        ):
            bundle = self._graph_for(policy, extend)
            operands = bundle.ops
            if epoch is None:
                epoch = self._spec_epoch(bundle, extend)
        key = EngineKey(
            kind, policy, edge_compute, n_pad, cap, state_layout, extend,
            collect_stats, int(epoch) if epoch else 0,
        )
        if kind == "static":
            builder = lambda: build_engine(
                self.mesh, policy, edge_compute, n_pad, cap,
                state_layout=state_layout, extend=extend, operands=operands,
                collect_stats=collect_stats,
            )
        elif kind == "phase1":
            builder = lambda: build_engine(
                self.mesh, policy, edge_compute, n_pad, cap,
                state_layout=state_layout, sync="shard", extend=extend,
                operands=operands, collect_stats=collect_stats,
            )
        elif kind == "resume":
            builder = lambda: build_resume_engine(
                self.mesh, policy, edge_compute, n_pad, cap, extend=extend,
                operands=operands, collect_stats=collect_stats,
            )
        elif kind == "gang":
            builder = lambda: build_gang_resume_engine(
                self.mesh, policy, edge_compute, n_pad, cap, extend=extend,
                operands=operands, state_layout=state_layout,
                collect_stats=collect_stats,
            )
        else:
            raise ValueError(f"unknown engine kind: {kind}")
        eng = self.cache.get_or_build(key, builder)
        if morsel_shape is not None:
            # a hit engine still retraces on a new morsel count; record it
            # so serving can classify this batch as cold (compile_events)
            self.cache.note_shape(key, tuple(morsel_shape))
        return eng

    # ------------------------------------------------------------ dispatch

    def _phase1_budget(self, buckets=()) -> int:
        """Iteration cap for phase 1, pow2-quantized so the budget only
        compiles O(log max_iters) distinct phase-1 engines.

        Priority: a pinned ``phase1_iters`` bypasses learning outright;
        then the per-(family, source-degree-bucket) ``BudgetModel``
        serves the covering budget for this batch's ``buckets``; an
        empty model falls back to the global pow2 p90 of recent batches
        (the legacy path, and ``online_adapt=False``'s only path)."""
        if self.phase1_iters is not None:
            return max(1, min(self.phase1_iters, self.max_iters))
        if self.budget_model is not None:
            b = self.budget_model.budget_for(
                self.family, buckets, self.max_iters
            )
            if b is not None:
                return b
        if self._iter_p90s:
            b = _pow2ceil(int(np.median(self._iter_p90s)) + 1)
        else:
            # cold start: small-world graphs converge in a few hops
            b = (
                self.budget_model.cold_budget
                if self.budget_model is not None
                else 8
            )
        return max(4, min(b, self.max_iters))

    def _record_iters(self, iters: np.ndarray):
        if iters.size:
            self._iter_p90s.append(float(np.percentile(iters, 90)))

    def _morsel_buckets(self, sources: np.ndarray, lanes: int) -> np.ndarray:
        """pow2 source-degree bucket per REAL morsel: the budget model's
        key, from the mean out-degree of each morsel's (real) sources."""
        if len(sources) == 0:
            return np.zeros(0, np.int64)
        deg = self.csr.degrees[
            np.clip(sources, 0, self.csr.n_nodes - 1)
        ].astype(np.float64)
        n_m = -(-len(sources) // lanes)
        pad = np.full(n_m * lanes - len(sources), np.nan)
        mean = np.nanmean(
            np.concatenate([deg, pad]).reshape(n_m, lanes), axis=1
        )
        return np.asarray([degree_bucket(float(m)) for m in mean], np.int64)

    def depth_hint(self, sources, lanes: int = 1) -> int | None:
        """Predicted convergence depth (iterations) for a prospective
        batch of sources — the admission layer's deadline-packing signal.
        Serves the learned per-bucket budget when the model has samples;
        None when nothing has been learned yet (cold admission must not
        evict/shed on a guess)."""
        if self.budget_model is None or len(sources) == 0:
            return None
        buckets = self._morsel_buckets(
            np.asarray(sources, np.int64).reshape(-1), lanes
        )
        return self.budget_model.budget_for(
            self.family, buckets, self.max_iters
        )

    # ---------------------------------------------------- online adaptation

    def _record_samples(self, stats: np.ndarray, trips: np.ndarray,
                        n_pad: int, push_slots: int,
                        start: np.ndarray | None = None,
                        phase: int = 1) -> None:
        """Drain one batch's stats-tap buffer into the sample store: one
        fit-consumable record per (real morsel, iteration). ``start``
        gives each morsel's first recorded row (phase-2 taps resume at
        the survivor's absolute phase-1 exit counter; rows below it are
        zero-padding, not samples); ``phase`` labels the records so
        consumers can split head/tail iteration populations."""
        store = self._dir_samples.setdefault(
            int(n_pad), collections.deque(maxlen=self._sample_window)
        )
        for i in range(stats.shape[0]):
            j0 = int(start[i]) if start is not None else 0
            for j in range(j0, int(trips[i])):
                n_f, m_f, m_u, pull, _wall, pbytes = (
                    float(v) for v in stats[i, j]
                )
                store.append({
                    "it": j,
                    "phase": phase,
                    "frontier": n_f,
                    "m_frontier": m_f,
                    "m_unexplored": m_u,
                    "push_slots": float(push_slots),
                    "pull_slots_binned": None if pull < 0 else pull,
                    "pull_bytes_binned": None if pbytes < 0 else pbytes,
                })

    def _rates_for(self, n_pad: int) -> dict:
        """Measured per-backend ms/slot rates for ``n_pad``, probed lazily
        on first use (the probe jit-compiles one extension per backend —
        doing it at trace-READ time keeps the serving hot path and every
        slots-mode run probe-free) and cached for the dispatcher's life."""
        if n_pad in self._cost_rates:
            return self._cost_rates[n_pad]
        best = None
        score = lambda o: (
            (o.rev_binned is not None) + (o.rev_binned_pack is not None)
        )
        for b in self._graphs.values():
            ops = b.ops
            if int(b.n_pad) == int(n_pad) and (
                best is None or score(ops) > score(best)
            ):
                best = ops
        rates = (
            {} if best is None else self.cost_probe.rates(best, int(n_pad))
        )
        self._cost_rates[n_pad] = rates
        return rates

    def device_report(self) -> dict:
        """What the dispatcher holds on the device and learned there:
        ``operand_bytes`` per placed bundle (keyed by the graph axes it is
        split over; bundles with equal axes are summed), ``cost_rates``
        per n_pad as probed so far, and the number of engine iterations
        ``sampled`` for threshold refits."""
        operand_bytes: dict = {}
        for key, b in self._graphs.items():
            n = sum(x.nbytes for x in jax.tree.leaves(b.ops))
            operand_bytes[key[0]] = operand_bytes.get(key[0], 0) + n
        return {
            "operand_bytes": operand_bytes,
            "cost_rates": {k: dict(v) for k, v in self._cost_rates.items()},
            "sampled": sum(len(v) for v in self._dir_samples.values()),
        }

    def online_trace(self, cost: str | None = None) -> dict:
        """The accumulated live samples as a ``BENCH_direction_opt``-shaped
        trace document: one workload per observed n_pad (this graph's
        family/avg-degree), records under the canonical ``ell_push``
        backend key — exactly what ``fit_direction_thresholds`` consumes,
        so the offline fit of this trace IS the online refit.

        Scope: the phase-1 tap plus the resume/gang phase-2 taps — a
        survivor's post-budget tail iterations (``phase == 2`` records,
        starting at its absolute phase-1 exit counter) land in the same
        store, so deep-straggler tails are represented like a full
        offline bench trace.

        ``cost`` (default: the dispatcher's ``cost_mode``): "measured"
        annotates each record with ``push_wall_ms`` /
        ``pull_wall_ms_binned`` / ``pull_wall_ms_fused`` — slot counts
        converted through the lazily-probed per-backend ms/slot rates —
        so ``fit_direction_thresholds(..., cost="measured")`` can
        consume the document; "slots" emits the historical slots-only
        records."""
        c = self.cost_mode if cost is None else cost
        workloads = []
        for n_pad, recs in sorted(self._dir_samples.items()):
            records = [dict(r) for r in recs]
            if c == "measured":
                rates = self._rates_for(n_pad)
                pr = rates.get("ell_push", {}).get("ms_per_slot")
                br = rates.get("pull_binned", {}).get("ms_per_slot")
                fr = rates.get("pull_binned_fused", {}).get("ms_per_slot")
                for r in records:
                    ps = r.get("pull_slots_binned")
                    r["push_wall_ms"] = (
                        None if pr is None else pr * r["push_slots"]
                    )
                    r["pull_wall_ms_binned"] = (
                        None if (br is None or ps is None) else br * ps
                    )
                    r["pull_wall_ms_fused"] = (
                        None if (fr is None or ps is None) else fr * ps
                    )
            workloads.append({
                "graph": f"online_npad{n_pad}",
                "kind": self.family or "unknown",
                "n": int(self.csr.n_nodes),
                "n_pad": int(n_pad),
                "n_edges": int(self.csr.n_edges),
                "avg_degree": float(self.csr.avg_degree),
                "backends": {"ell_push": {"iterations": records}},
            })
        return {"workloads": workloads}

    def refit_thresholds(self, cost: str | None = None) -> (
        DirectionThresholds | None
    ):
        """Refit ``direction_thresholds`` from the accumulated live
        samples (no-op before any sample lands). ``backend="recommend"``
        serves the refitted alpha/beta on the next batch. ``cost``
        overrides the dispatcher's ``cost_mode`` for this one refit
        (measured-cost fits degrade per-record to slots parity when a
        backend's rate could not be probed)."""
        if not any(len(r) for r in self._dir_samples.values()):
            return None
        c = self.cost_mode if cost is None else cost
        self.direction_thresholds = fit_direction_thresholds(
            self.online_trace(cost=c), cost=c
        )
        self.stats.refits += 1
        return self.direction_thresholds

    def _learn(self, outcome: "QueryOutcome", buckets: np.ndarray,
               n_real: int) -> None:
        """Post-batch learning: feed the budget model (real morsels only
        — the per-bucket form of the pad-morsel guard; skipped entirely
        when ``phase1_iters`` pins the budget) and the global-p90
        fallback, then refit thresholds on the ``refit_every`` cadence."""
        iters = np.asarray(outcome.result.iterations)[:n_real]
        self._record_iters(iters)
        if (
            self.budget_model is not None
            and self.phase1_iters is None
            and n_real > 0
        ):
            self.budget_model.observe_batch(
                self.family, buckets[:n_real], iters
            )
            if outcome.hybrid:
                self.budget_model.mispredicts.count(
                    outcome.budget_too_low, outcome.budget_too_high,
                    outcome.budget_inert_slots, outcome.budget_observed,
                )
        if self.online_adapt and not self._thresholds_pinned:
            self._batches_since_refit += 1
            if self._batches_since_refit >= self.refit_every:
                self._batches_since_refit = 0
                self.refit_thresholds()

    # ------------------------------------------ split-phase hybrid internals

    def _begin_hybrid(self, pol, ec, g, n_pad, morsels, state_layout,
                      extend=ExtendSpec(), n_real=0, buckets=(), epoch=0):
        """Choose the budget, then DISPATCH phase 1 without blocking: jax
        async dispatch returns device futures immediately, so the caller's
        host thread is free until ``_settle_hybrid`` blocks on them.

        The phase-2 operand bundle is resolved and PINNED here, at begin
        time, even though it is only consumed at settle time: resolving
        it inside ``_settle_hybrid`` (the historical path) re-read the
        shared bundle cache, so an ``apply_delta`` landing between begin
        and settle would have torn the batch across graph versions —
        phase 1 on the old edges, phase 2 on the new. The pinned ops
        keep the pre-delta device buffers alive for exactly as long as
        the in-flight batch needs them."""
        p1, p2 = hybrid_phases(
            pol.source_axes, pol.graph_axes, lanes=pol.lanes,
            or_impl=pol.or_impl,
        )
        budget = self._phase1_budget(buckets)
        collect = bool(self.online_adapt)
        eng1 = self.engine(
            "phase1", p1, ec, n_pad, max_iters=budget,
            state_layout=state_layout, extend=extend, operands=g,
            collect_stats=collect, morsel_shape=morsels.shape[:1],
            epoch=epoch,
        )
        b2 = self._graph_for(p2, extend)
        t0 = time.perf_counter()
        out1 = eng1(g, morsels)  # async: no block_until_ready
        return {
            "pol": pol, "p2": p2, "ec": ec, "g": g, "n_pad": n_pad,
            "state_layout": state_layout, "extend": extend,
            "n_real": n_real, "budget": budget, "collect": collect,
            "out1": out1, "t0": t0, "epoch": epoch,
            "g2": b2.ops, "n_pad2": b2.n_pad,
            "epoch2": self._spec_epoch(b2, extend),
        }

    def _settle_hybrid(self, inf) -> SettledBatch:
        """Block on phase 1, re-dispatch survivors (phase 2), block only
        on the per-morsel iteration counters, and defer the final state
        stitch into ``SettledBatch.finalize`` — the host work the serving
        loop overlaps with the next batch's phase 1."""
        pol, p2, ec = inf["pol"], inf["p2"], inf["ec"]
        g, n_pad = inf["g"], inf["n_pad"]
        state_layout, extend = inf["state_layout"], inf["extend"]
        n_real, budget, collect = inf["n_real"], inf["budget"], inf["collect"]
        sharded = state_layout == "sharded"
        out1 = jax.block_until_ready(inf["out1"])
        t1 = time.perf_counter()
        res1, stats1 = out1 if collect else (out1, None)

        # survivor test reads ONLY the frontier leaf — and under the
        # sharded layout only a per-morsel any() reduction (the full state
        # never gathers to host; the handoff below stays on device)
        f1 = res1.state.frontier
        if sharded:
            active = np.asarray(
                jnp.any(f1 != 0, axis=tuple(range(1, f1.ndim)))
            )
        else:
            frontier1 = np.asarray(f1)
            m = frontier1.shape[0]
            active = frontier1.reshape(m, -1).any(axis=1)
        idx = np.nonzero(active)[0]
        phase_ms = {"phase1": (t1 - inf["t0"]) * 1e3, "phase2": 0.0}
        iters1 = np.asarray(res1.iterations)
        n_real = int(min(n_real, iters1.shape[0]))
        too_low, too_high, inert = count_budget_mispredicts(
            budget, iters1[:n_real], active[:n_real],
            floor=(
                self.budget_model.floor
                if self.budget_model is not None
                else 4
            ),
        )
        if stats1 is not None and n_real > 0:
            self._record_samples(
                np.asarray(stats1)[:n_real], iters1[:n_real], n_pad,
                push_slots=int(np.prod(g.fwd.indices.shape)),
            )
        if idx.size == 0:
            return SettledBatch(QueryOutcome(
                result=res1, policy=pol.name, hybrid=True, redispatched=0,
                phase_ms=phase_ms, phase1_budget=budget,
                budget_too_low=too_low, budget_too_high=too_high,
                budget_inert_slots=inert, budget_observed=n_real,
            ))
        use_gang = self.gang_resume and (idx.size > 1 or sharded)

        # pad survivors to a pow2 morsel count: stable resume-trace shapes
        # (pad morsels are all-zero state => inert / zero-trip loops)
        kp = _pow2ceil(idx.size)
        sub_it = np.zeros((kp,), iters1.dtype)
        sub_it[: idx.size] = iters1[idx]

        # the phase-2 operands pinned at begin time (never re-resolved:
        # a delta applied while this batch was in flight must not swap
        # the graph under phase 2 — see _begin_hybrid)
        g2, n_pad2 = inf["g2"], inf["n_pad2"]
        assert n_pad2 == n_pad, (n_pad2, n_pad)

        state1 = None
        if not sharded:
            state1 = jax.tree.map(np.asarray, res1.state)

            def pick(x):
                out = np.zeros((kp,) + x.shape[1:], np.asarray(x).dtype)
                out[: idx.size] = np.asarray(x)[idx]
                return out

            sub_state = jax.tree.map(pick, state1)
        else:
            # all-gather/slice handoff: phase-1 rows (policy graph axes)
            # -> phase-2 rows (every mesh axis), survivors gathered and
            # pow2-padded on device
            sub_state = gang_handoff(
                res1.state, idx, kp, self.mesh, p2.graph_axes
            )

        if use_gang:
            eng2 = self.engine(
                "gang", p2, ec, n_pad, state_layout=state_layout,
                extend=extend, operands=g2, collect_stats=collect,
                morsel_shape=(kp,), epoch=inf["epoch2"],
            )
            self.stats.gangs += 1
            self.stats.gang_slots += kp
        else:
            eng2 = self.engine(
                "resume", p2, ec, n_pad, extend=extend, operands=g2,
                collect_stats=collect, epoch=inf["epoch2"],
            )
        out2 = eng2(g2, sub_state, jnp.asarray(sub_it))  # async dispatch
        res2, stats2 = out2 if collect else (out2, None)
        # block only the tiny per-morsel counters: phase 2 has then fully
        # executed on device, but the state leaves stay there — the stitch
        # below is deferred host work
        iters2 = np.asarray(res2.iterations)
        t2 = time.perf_counter()
        phase_ms["phase2"] = (t2 - t1) * 1e3
        if stats2 is not None and idx.size > 0:
            # survivors' post-budget tails: rows run from each morsel's
            # absolute phase-1 exit counter to its final trip count
            self._record_samples(
                np.asarray(stats2)[: idx.size], iters2[: idx.size], n_pad,
                push_slots=int(np.prod(g.fwd.indices.shape)),
                start=sub_it[: idx.size], phase=2,
            )

        final_iters = iters1.copy()
        final_iters[idx] = iters2[: idx.size]

        def materialize() -> IFEResult:
            if sharded:
                final_state = gang_scatter_back(res1.state, res2.state, idx)
            else:
                state2 = jax.tree.map(np.asarray, res2.state)

                def put(full, sub):
                    out = np.asarray(full).copy()
                    out[idx] = sub[: idx.size]
                    return out

                final_state = jax.tree.map(
                    jnp.asarray, jax.tree.map(put, state1, state2)
                )
            return IFEResult(
                state=final_state, iterations=jnp.asarray(final_iters)
            )

        outcome = QueryOutcome(
            result=IFEResult(state=None, iterations=jnp.asarray(final_iters)),
            policy=pol.name, hybrid=True, redispatched=int(idx.size),
            phase_ms=phase_ms, phase1_budget=budget,
            resumed_ganged=int(idx.size) if use_gang else 0,
            resumed_serial=0 if use_gang else int(idx.size),
            gang_width=kp if use_gang else 0,
            budget_too_low=too_low, budget_too_high=too_high,
            budget_inert_slots=inert, budget_observed=n_real,
        )
        return SettledBatch(outcome, materialize)

    def _run_hybrid(self, pol, ec, g, n_pad, morsels, state_layout,
                    extend=ExtendSpec(), n_real=0, buckets=(), epoch=0):
        """Two-phase hybrid on one morsel batch, synchronously: begin +
        settle + finalize back-to-back. Returns a QueryOutcome whose
        result state is bit-identical to the static engine's.

        Phase-2 dispatch: >1 survivor => one gang-scheduled multi-frontier
        resume (pow2-padded batch, per-survivor convergence masks — see the
        module docstring's gang contract); exactly 1 survivor => the serial
        per-morsel engine (no packing win to pay for); ``gang_resume=False``
        pins the serial baseline (replicated layout only — the sharded
        phase 2 IS the gang engine).

        ``n_real``/``buckets``: this batch's real (non-pad) morsel count
        and their source-degree buckets — the budget model's prediction
        key and the mispredict counters' population. Under
        ``online_adapt`` phase 1 runs the stats-tapped engine and its
        per-iteration samples land in the threshold-refit store."""
        inf = self._begin_hybrid(
            pol, ec, g, n_pad, morsels, state_layout, extend=extend,
            n_real=n_real, buckets=buckets, epoch=epoch,
        )
        return self._settle_hybrid(inf).finalize()

    def _begin_static(self, pol, ec, g, n_pad, morsels, state_layout,
                      extend=ExtendSpec(), epoch=0):
        eng = self.engine(
            "static", pol, ec, n_pad, state_layout=state_layout,
            extend=extend, operands=g, morsel_shape=morsels.shape[:1],
            epoch=epoch,
        )
        t0 = time.perf_counter()
        res = eng(g, morsels)  # async: no block_until_ready
        return {"pol": pol, "res": res, "t0": t0}

    def _settle_static(self, inf) -> SettledBatch:
        res = jax.block_until_ready(inf["res"])
        t1 = time.perf_counter()
        return SettledBatch(QueryOutcome(
            result=res, policy=inf["pol"].name, hybrid=False, redispatched=0,
            phase_ms={"phase1": (t1 - inf["t0"]) * 1e3, "phase2": 0.0},
            phase1_budget=0,
        ))

    def _run_static(self, pol, ec, g, n_pad, morsels, state_layout,
                    extend=ExtendSpec(), n_real=0, buckets=(), epoch=0):
        inf = self._begin_static(
            pol, ec, g, n_pad, morsels, state_layout, extend=extend,
            epoch=epoch,
        )
        return self._settle_static(inf).finalize()

    # ------------------------------------------------------ batch planning

    def _plan_query(self, sources, returns_paths, policy, backend,
                    query_kind="reach"):
        """Shared preamble of query/begin_batch: resolve policy, edge
        compute, extension spec, operands, morsels, chunking, and the
        budget model's bucket keys for one source batch.

        ``query_kind`` selects the scenario family (``QUERY_KINDS``):
        "reach" is the historical BFS/MS-BFS surface; the other kinds
        name their edge compute directly and, when the compute has no
        saturating lane form (``lanes_ok=False``), must not run under a
        lane-packed multi-source policy — an auto-recommended one
        degrades to nTkS, an explicitly pinned one is an error."""
        kind = QUERY_KINDS.get(query_kind)
        if kind is None:
            raise ValueError(
                f"unknown query_kind: {query_kind!r} "
                f"(known: {sorted(QUERY_KINDS)})"
            )
        if query_kind != "reach" and returns_paths:
            raise ValueError(
                f"returns_paths is a reach-family option; "
                f"query_kind={query_kind!r} has its own result leaves"
            )
        sources = np.asarray(sources, np.int32).reshape(-1)
        name = policy or recommend_policy(
            len(sources),
            self.mesh.size,
            self.csr.avg_degree,
            returns_paths=returns_paths,
            n_nodes=self.csr.n_nodes,
        )
        pol = POLICIES[name]()
        if pol.is_multi_source and not kind.lanes_ok:
            if policy is not None:
                raise ValueError(
                    f"policy {policy!r} lane-packs sources but "
                    f"query_kind={query_kind!r} has no lane form"
                )
            # recommend_policy pooled >=64 sources into a lane policy;
            # this kind's state has no lane axis, so serve the same
            # batch as per-source morsels instead
            name = "ntks"
            pol = POLICIES[name]()
        if kind.edge_compute is not None:
            ec = kind.edge_compute
        elif pol.is_multi_source:
            ec = "msbfs_parents" if returns_paths else "msbfs_lengths"
        else:
            ec = "sp_parents" if returns_paths else "sp_lengths"
        backend = backend if backend is not None else self.backend
        if backend == "recommend":
            backend = recommend_backend(
                ec, self.csr.avg_degree, n_nodes=self.csr.n_nodes,
                lanes=pol.lanes, family=self.family,
                thresholds=self.direction_thresholds,
            )
        spec = as_spec(backend)
        bundle = self._graph_for(pol, spec)
        g, n_pad = bundle.ops, bundle.n_pad
        # pin the operand epoch at plan time: everything this batch
        # dispatches (phase 1, static, every chunk) keys its engines on
        # the shape generation of the buffers resolved HERE
        epoch = self._spec_epoch(bundle, spec)
        src_shards = _axes_size(self.mesh, pol.source_axes)
        morsels = pad_sources(sources, src_shards, pol.lanes, n_pad)
        # paper Fig 13: dense graphs cap concurrent source morsels (k);
        # oversized batches run in fixed-size chunks, stitched on host.
        k = (
            self.max_inflight
            if self.max_inflight is not None
            else recommend_k(self.csr.avg_degree)
        )
        chunk = max(src_shards, k * src_shards)
        if self.pad_pow2_morsels and 0 < morsels.shape[0] <= chunk:
            # serving: quantize the morsel count so a stream of arbitrary
            # pool sizes hits a bounded, pre-warmable set of XLA shapes
            # ({1, 2, 4, ..., chunk}) instead of retracing per queue
            # depth. Only below the chunk threshold: the chunked path
            # already normalizes its shapes (every chunk, including the
            # last, is padded to the chunk size), and pow2-rounding a big
            # pool would waste up to 2x device work. Capped at ``chunk``
            # so padding never flips a batch into the chunked path.
            m2 = min(_pow2ceil(morsels.shape[0]), chunk)
            if m2 > morsels.shape[0]:
                inert = np.full(
                    (m2 - morsels.shape[0], pol.lanes), n_pad, np.int32
                )
                morsels = np.concatenate([morsels, inert], axis=0)
        # budget learning and mispredict accounting see only the real
        # morsels: pad/inert ones exit at 0 iterations and would drag every
        # bucket's learned budget below its true convergence depth
        # (permanent re-dispatch)
        n_real = max(1, -(-len(sources) // pol.lanes))
        # buckets feed only the model's predict/observe; skip the host
        # work (degrees gather + per-morsel bucketing) when no model will
        # consume them (online_adapt off, or the budget pinned)
        buckets = (
            self._morsel_buckets(sources, pol.lanes)
            if self.budget_model is not None and self.phase1_iters is None
            else np.zeros(0, np.int64)
        )
        return sources, name, pol, ec, spec, g, n_pad, morsels, chunk, \
            n_real, buckets, epoch

    def _hybrid_eligible(self, pol, state_layout: str) -> bool:
        return (
            self.adaptive
            and bool(pol.source_axes)  # nT1S has no source morsels to split
            # sharded phase 2 is the gang engine; without it, fall back to
            # the static sharded dispatch (there is no serial sharded resume)
            and (state_layout == "replicated" or self.gang_resume)
        )

    # -------------------------------------------------- split-phase surface

    def begin_batch(
        self,
        sources,
        returns_paths: bool = False,
        policy: str | None = None,
        state_layout: str = "replicated",
        backend=None,
        query_kind: str = "reach",
    ) -> InflightBatch:
        """Plan one batch and dispatch its phase 1 (or static engine)
        asynchronously. The returned ``InflightBatch`` MUST be settled via
        ``settle_batch`` before the next ``begin_batch`` — learning is
        host-serial, and the budget/threshold state a later batch reads is
        only current once the earlier batch has settled."""
        (sources, name, pol, ec, spec, g, n_pad, morsels, chunk, n_real,
         buckets, epoch) = self._plan_query(
             sources, returns_paths, policy, backend, query_kind)
        if morsels.shape[0] > chunk:
            # oversized batch: the in-flight cap splits it into a host-
            # stitched chunk loop — run synchronously at settle time
            # (admission keeps serving batches under the cap)
            payload = {
                "sources": sources, "name": name, "pol": pol, "ec": ec,
                "spec": spec, "g": g, "n_pad": n_pad, "morsels": morsels,
                "chunk": chunk, "state_layout": state_layout,
                "epoch": epoch,
            }
            return InflightBatch("chunked", name, n_real, buckets, payload)
        if self._hybrid_eligible(pol, state_layout):
            inf = self._begin_hybrid(
                pol, ec, g, n_pad, jnp.asarray(morsels), state_layout,
                extend=spec, n_real=n_real, buckets=buckets, epoch=epoch,
            )
            return InflightBatch("hybrid", name, n_real, buckets, inf)
        inf = self._begin_static(
            pol, ec, g, n_pad, jnp.asarray(morsels), state_layout,
            extend=spec, epoch=epoch,
        )
        return InflightBatch("static", name, n_real, buckets, inf)

    def settle_batch(self, inflight: InflightBatch) -> SettledBatch:
        """Drive one in-flight batch through its device sync points and
        post-batch learning. The result state may still be deferred —
        ``finalize_batch`` (or ``SettledBatch.finalize``) materializes it;
        the serving loop calls that *after* dispatching the next phase 1
        so the host stitch overlaps device compute."""
        if inflight.kind == "chunked":
            p = inflight.payload
            outcome = self._run_chunked(
                p["pol"], p["ec"], p["g"], p["n_pad"], p["morsels"],
                p["chunk"], p["state_layout"], p["spec"],
                inflight.n_real, inflight.buckets, p.get("epoch", 0),
            )
            settled = SettledBatch(outcome)
        elif inflight.kind == "hybrid":
            settled = self._settle_hybrid(inflight.payload)
        else:
            settled = self._settle_static(inflight.payload)
        settled.outcome.policy = inflight.name
        self._learn(settled.outcome, inflight.buckets, inflight.n_real)
        self.stats.record(settled.outcome)
        return settled

    def finalize_batch(self, settled: SettledBatch) -> QueryOutcome:
        """Run the deferred host materialization (idempotent)."""
        return settled.finalize()

    def _run_chunked(self, pol, ec, g, n_pad, morsels, chunk, state_layout,
                     spec, n_real, buckets, epoch=0) -> QueryOutcome:
        """The in-flight-cap chunk loop: fixed-size chunks, host-stitched
        into one outcome (learning/stats are applied once by the caller)."""
        run_fn = (
            self._run_hybrid
            if self._hybrid_eligible(pol, state_layout)
            else self._run_static
        )
        outcomes = []
        for i in range(0, morsels.shape[0], chunk):
            part = morsels[i : i + chunk]
            if part.shape[0] < chunk:  # keep one trace shape per chunk size
                pad = np.full(
                    (chunk - part.shape[0], part.shape[1]), n_pad, np.int32
                )
                part = np.concatenate([part, pad], axis=0)
            real_in = max(0, min(chunk, n_real - i))
            outcomes.append(
                run_fn(
                    pol, ec, g, n_pad, jnp.asarray(part), state_layout,
                    extend=spec, n_real=real_in,
                    buckets=buckets[i : i + real_in], epoch=epoch,
                )
            )
        result = IFEResult(
            state=jax.tree.map(
                lambda *xs: jnp.concatenate([jnp.asarray(x) for x in xs]),
                *[o.result.state for o in outcomes],
            ),
            iterations=jnp.concatenate(
                [jnp.asarray(o.result.iterations) for o in outcomes]
            ),
        )
        return QueryOutcome(
            result=result,
            policy=pol.name,
            hybrid=any(o.hybrid for o in outcomes),
            redispatched=sum(o.redispatched for o in outcomes),
            phase_ms={
                "phase1": sum(o.phase_ms["phase1"] for o in outcomes),
                "phase2": sum(o.phase_ms["phase2"] for o in outcomes),
            },
            phase1_budget=max(o.phase1_budget for o in outcomes),
            resumed_ganged=sum(o.resumed_ganged for o in outcomes),
            resumed_serial=sum(o.resumed_serial for o in outcomes),
            gang_width=max(o.gang_width for o in outcomes),
            budget_too_low=sum(o.budget_too_low for o in outcomes),
            budget_too_high=sum(o.budget_too_high for o in outcomes),
            budget_inert_slots=sum(o.budget_inert_slots for o in outcomes),
            budget_observed=sum(o.budget_observed for o in outcomes),
        )

    def query(
        self,
        sources,
        returns_paths: bool = False,
        policy: str | None = None,
        state_layout: str = "replicated",
        backend=None,
        query_kind: str = "reach",
    ) -> QueryOutcome:
        """Serve one request batch of source nodes, synchronously.

        Policy is chosen per batch via ``recommend_policy`` unless pinned;
        execution is two-phase hybrid whenever eligible (adaptive mode,
        replicated state, source-level morsels to re-dispatch). This is
        ``begin_batch`` + ``settle_batch`` + ``finalize_batch`` run
        back-to-back — bit-identical to the serving loop's overlapped
        pipeline on the same batch stream.

        ``backend`` selects the frontier-extension backend for this batch
        ("ell_push" | "ell_pull" | "block_mxu" | "dopt" | an ExtendSpec;
        "recommend" applies ``recommend_backend``); None uses the
        scheduler's default. All choices are bit-identical in result.

        ``query_kind`` selects the scenario family ("reach" | "topk_paths"
        | "ppr" | "pattern_counts"): everything downstream of the edge
        compute — engine cache, two-phase hybrid, gang resume, online
        learning — is shared across kinds unchanged.
        """
        inflight = self.begin_batch(
            sources, returns_paths=returns_paths, policy=policy,
            state_layout=state_layout, backend=backend,
            query_kind=query_kind,
        )
        return self.settle_batch(inflight).finalize()
