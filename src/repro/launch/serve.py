"""Recursive-query serving driver — the paper-kind end-to-end example.

A resident query service backed by the layered serving core (see
docs/serving.md): the graph is loaded and ELL-partitioned once, engines
are compiled per (kind × policy × edge-compute) into a shared cache and
reused across request batches, and each batch executes as the paper's
hybrid — phase 1 issues source-level morsels with per-shard convergence,
phase 2 re-dispatches stragglers at the frontier level — with policy and
scan layout picked per batch (``recommend_policy``/``recommend_backend``)
and the online learners (per-bucket phase-1 budgets, in-flight direction-
threshold refits) feeding on the served stream.

Two drivers share that core:

- **Open loop** (the default): an ``runtime.service.ServingLoop`` serves a
  seeded Poisson arrival stream — queries are admitted when they arrive
  whether or not the loop is keeping up, multi-tenant, optionally with
  per-query deadlines (``--deadline-ms``) and tenant quotas (``--quota``).
  Batch i's host-side result materialization overlaps batch i+1's device
  work (``--no-overlap`` pins the strictly serial baseline). Reported:
  per-tenant p50/p99, overlap occupancy, shed/deadline-miss counts.
- **Closed loop** (``--closed-loop``, or implied by ``--paths``): the
  legacy one-batch-at-a-time driver over ``AdaptiveScheduler.query``.

Both report *warm* latency percentiles — batches that compiled a new
engine (cache-miss batches) are excluded from p50/p99 and their wall is
reported separately as cold-start time, so the serving tail is never
conflated with compile time.

    PYTHONPATH=src python -m repro.launch.serve --dataset ldbc \
        --rate 20 --arrivals 60 --sources-per-batch 8
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from ..core import histogram_lengths, reconstruct_paths
from ..graph.delta import apply_delta_csr, random_delta
from ..graph.generators import (
    PAPER_DATASET_FAMILIES,
    PAPER_DATASETS,
    pick_sources,
)
from ..kernels.binned_pull.binned_pull import require_lowerable
from ..runtime.scheduler import AdaptiveScheduler
from ..runtime.service import ServingLoop
from .compile_cache import enable_compile_cache
from .mesh import make_mesh


class QueryService:
    """Compile-once, serve-many recursive query engine pool.

    Thin façade over AdaptiveScheduler kept for API stability: ``query``
    returns ``(IFEResult, policy_name)`` like the original static service,
    while the scheduler underneath decides static vs two-phase execution.
    Callers that count or inspect compiles use the scheduler's public
    ``EngineCache`` surface (``len(svc.scheduler.cache)``, ``.keys()``,
    ``.items()``) — there is no private reach-through here.
    """

    def __init__(self, mesh, csr, max_deg=None, max_iters=64, adaptive=True,
                 backend="recommend", direction_thresholds=None, family=None,
                 online_adapt=True, refit_every=16, cost="auto"):
        self.mesh = mesh
        self.csr = csr
        self.max_iters = max_iters
        self.max_deg = max_deg
        self.scheduler = AdaptiveScheduler(
            mesh, csr, max_deg=max_deg, max_iters=max_iters,
            adaptive=adaptive, backend=backend,
            direction_thresholds=direction_thresholds, family=family,
            online_adapt=online_adapt, refit_every=refit_every, cost=cost,
        )
        self.last_outcome = None  # per-phase latency of the last query

    def query(self, sources, returns_paths=False, policy=None,
              state_layout="replicated", backend=None, query_kind="reach"):
        """One request batch -> (result state, policy used)."""
        out = self.scheduler.query(
            sources, returns_paths=returns_paths, policy=policy,
            state_layout=state_layout, backend=backend,
            query_kind=query_kind,
        )
        self.last_outcome = out
        return out.result, out.policy


def _pct(values, p):
    return np.percentile(np.asarray(values), p) if len(values) else float("nan")


def poisson_arrivals(csr, rate_qps: float, n_arrivals: int,
                     sources_per_query: int, tenants: int = 1,
                     deadline_ms: float | None = None, seed: int = 0,
                     query_kind: str = "reach"):
    """Seeded open-loop Poisson schedule for ``ServingLoop.run_stream``:
    exponential inter-arrival gaps at ``rate_qps``, tenants round-robin,
    every query's sources drawn by the same ``pick_sources`` rule the
    closed-loop driver uses (so the two drivers serve the same work)."""
    rng = np.random.default_rng(seed)
    gaps_ms = rng.exponential(1e3 / rate_qps, size=n_arrivals)
    t_ms = np.cumsum(gaps_ms)
    return [
        {
            "t_ms": float(t_ms[i]),
            "sources": pick_sources(csr, sources_per_query, seed=100 + i),
            "tenant": f"t{i % tenants}",
            "deadline_ms": deadline_ms,
            "query_kind": query_kind,
        }
        for i in range(n_arrivals)
    ]


def _report_core(sched, used=None) -> None:
    cache, stats = sched.cache, sched.stats
    if used:
        print(f"policies used: {used}")
    print(
        f"engine cache {len(cache)} compiled, "
        f"{cache.hits} hits / {cache.misses} misses "
        f"({dict(cache.misses_by_kind)} compiles by kind)"
    )
    print(
        f"phase-2 resume: {stats.resumed_ganged} survivor(s) ganged across "
        f"{stats.gangs} gang dispatch(es) "
        f"(occupancy {stats.gang_occupancy:.2f}), "
        f"{stats.resumed_serial} resumed serially"
    )
    if sched.budget_model is not None:
        model = sched.budget_model
        budgets = {
            f"{fam}/2^{b}": v
            for (fam, b), v in model.budgets(sched.max_iters).items()
        }
        mp = model.mispredicts
        print(
            f"online adapt: {stats.refits} threshold refit(s) from "
            f"{sum(len(r) for r in sched._dir_samples.values())} live "
            f"samples; learned budgets {budgets}; "
            f"budget mispredicts {mp.too_low} too-low / {mp.too_high} "
            f"too-high over {mp.observed} morsels "
            f"(rate {stats.budget_mispredict_rate:.3f}, "
            f"{stats.budget_inert_slots} inert budget slots)"
        )


def run_open_loop(args, csr, mesh, family) -> int:
    loop = ServingLoop(
        mesh, csr, adaptive=not args.static, backend=args.backend,
        direction_thresholds=args.thresholds, family=family,
        online_adapt=args.online_adapt, refit_every=args.refit_every,
        overlap=args.overlap, tenant_quota=args.quota,
        max_batch_sources=args.max_batch_sources, cost=args.cost_mode,
    )
    arrivals = poisson_arrivals(
        csr, args.rate, args.arrivals, args.sources_per_batch,
        tenants=args.tenants, deadline_ms=args.deadline_ms, seed=1,
        query_kind=args.query_kind,
    )
    if args.mutate_stream:
        # interleave seeded edge-edit batches evenly through the arrival
        # schedule; run_stream applies each through the serving fence
        # (admitted-before sees the old graph, admitted-after the new)
        span = arrivals[-1]["t_ms"] if arrivals else 0.0
        cur = csr
        for i in range(args.mutate_stream):
            t_ms = span * (i + 1) / (args.mutate_stream + 1)
            d = random_delta(cur, args.delta_edges, args.delta_edges,
                             seed=500 + i)
            cur = apply_delta_csr(cur, d)  # deletes sample the live graph
            arrivals.append({"t_ms": float(t_ms), "delta": d})
        arrivals.sort(key=lambda a: a["t_ms"])
    print(
        f"open loop: {args.arrivals} Poisson arrivals at {args.rate:.1f} "
        f"q/s across {args.tenants} tenant(s)"
        + (f", deadline {args.deadline_ms:.0f} ms" if args.deadline_ms else "")
        + (f", {args.mutate_stream} interleaved graph delta(s) of "
           f"±{args.delta_edges} edges" if args.mutate_stream else "")
    )
    t0 = time.perf_counter()
    loop.run_stream(arrivals)
    wall_s = time.perf_counter() - t0
    st = loop.stats
    print(
        f"served {st.completed} queries in {wall_s:.2f} s over "
        f"{st.batches} batches ({st.cold_batches} cold); "
        f"warm p50 {st.p50():.1f} ms, p99 {st.p99():.1f} ms "
        f"(all-in p50 {st.p50(warm=False):.1f} ms, "
        f"p99 {st.p99(warm=False):.1f} ms); "
        f"cold-start {st.cold_ms:.0f} ms excluded from warm percentiles"
    )
    print(
        f"overlap occupancy {st.overlap_occupancy:.2f} "
        f"({st.overlapped_finalizes}/{st.finalizes} finalizes hidden "
        f"behind device work); shed {st.shed}, "
        f"deadline misses {st.deadline_misses}, "
        f"evictions {loop.admission.stats.evictions}"
    )
    for name in sorted(st.tenants):
        ts = st.tenants[name]
        print(
            f"  tenant {name}: {ts.completed}/{ts.submitted} served, "
            f"warm p50 {ts.p50():.1f} ms p99 {ts.p99():.1f} ms, "
            f"shed {ts.shed}, misses {ts.deadline_misses}"
        )
    if st.deltas_applied:
        same = sum(1 for r in loop.delta_reports if r.same_shape)
        inval = sum(r.engines_invalidated for r in loop.delta_reports)
        print(
            f"graph deltas: {st.deltas_applied} applied "
            f"(now version {loop.graph_version}); {same} kept every "
            f"operand shape (engines stayed warm), "
            f"{inval} engine(s) invalidated by reshapes; final graph "
            f"{loop.dispatcher.csr.n_edges} edges"
        )
    _report_core(loop.dispatcher)
    return 0


def run_closed_loop(args, csr, mesh, family) -> int:
    svc = QueryService(mesh, csr, adaptive=not args.static,
                       backend=args.backend,
                       direction_thresholds=args.thresholds, family=family,
                       online_adapt=args.online_adapt,
                       refit_every=args.refit_every, cost=args.cost_mode)
    rng = np.random.default_rng(0)
    lat, warm_lat, p1_ms, p2_ms, used = [], [], [], [], {}
    redispatched, cold_ms = 0, 0.0
    cache = svc.scheduler.cache
    for b in range(args.batches):
        sources = pick_sources(
            csr, args.sources_per_batch, seed=100 + b
        )
        compiles0 = cache.compile_events
        t0 = time.perf_counter()
        res, pol = svc.query(sources, returns_paths=args.paths,
                             policy=args.policy,
                             query_kind=args.query_kind)
        if args.query_kind != "reach":
            # non-reach kinds carry their own result leaves (dists /
            # mass / wedges+closed): sync the whole state for timing
            jax.block_until_ready(res.state)
        elif args.paths and not pol.startswith("ntkms"):
            dests = rng.integers(0, csr.n_nodes, 4).astype(np.int32)
            paths = reconstruct_paths(
                res.state.parents[0, : csr.n_nodes], dests, max_len=32
            )
            jax.block_until_ready(paths)
        else:
            hist = histogram_lengths(res.state.levels)
            jax.block_until_ready(hist)
        dt = (time.perf_counter() - t0) * 1e3
        lat.append(dt)
        if cache.compile_events > compiles0:  # this batch paid a compile
            cold_ms += dt
        else:
            warm_lat.append(dt)
        used[pol] = used.get(pol, 0) + 1
        out = svc.last_outcome
        p1_ms.append(out.phase_ms["phase1"])
        p2_ms.append(out.phase_ms["phase2"])
        redispatched += out.redispatched
        if b < 3 or b == args.batches - 1:
            phase = (
                f"p1 {out.phase_ms['phase1']:7.1f} ms"
                f" p2 {out.phase_ms['phase2']:7.1f} ms"
                if out.hybrid else "static"
            )
            print(f"batch {b:3d}: {len(sources)} sources -> {pol:6s} "
                  f"{dt:8.1f} ms  [{phase}]")
    p1_ms, p2_ms = map(np.asarray, (p1_ms, p2_ms))
    print(
        f"served {args.batches} batches ({args.batches - len(warm_lat)} "
        f"cold): warm p50 {_pct(warm_lat, 50):.1f} ms, "
        f"p99 {_pct(warm_lat, 99):.1f} ms "
        f"(all-in p50 {_pct(lat, 50):.1f} ms, p99 {_pct(lat, 99):.1f} ms); "
        f"cold-start {cold_ms:.0f} ms excluded from warm percentiles"
    )
    print(
        f"phase1 p50/p99 {np.percentile(p1_ms, 50):.1f}/"
        f"{np.percentile(p1_ms, 99):.1f} ms; "
        f"phase2 p50/p99 {np.percentile(p2_ms, 50):.1f}/"
        f"{np.percentile(p2_ms, 99):.1f} ms; "
        f"{redispatched} morsels re-dispatched"
    )
    _report_core(svc.scheduler, used)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="ldbc",
                    choices=sorted(PAPER_DATASETS))
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--closed-loop", action="store_true",
                    help="legacy one-batch-at-a-time driver (implied by "
                         "--paths); default is the open-loop ServingLoop")
    ap.add_argument("--batches", type=int, default=20,
                    help="closed-loop request batches")
    ap.add_argument("--arrivals", type=int, default=60,
                    help="open-loop arrival count")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="open-loop Poisson arrival rate (queries/sec)")
    ap.add_argument("--tenants", type=int, default=2,
                    help="open-loop tenant count (round-robin arrivals)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-query SLO deadline; enables deadline-aware "
                         "pack eviction and load shedding")
    ap.add_argument("--quota", type=int, default=None,
                    help="max concurrent queries per tenant (over-quota "
                         "submissions are shed)")
    ap.add_argument("--overlap", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="overlap batch i's host materialization with "
                         "batch i+1's device work (--no-overlap is the "
                         "strictly serial baseline)")
    ap.add_argument("--sources-per-batch", type=int, default=8)
    ap.add_argument("--max-batch-sources", type=int, default=None,
                    help="bound one batch's pooled sources (open loop): "
                         "under backlog the queue drains as capped "
                         "batches with re-admission between them, keeping "
                         "tail latency at O(batch) instead of O(backlog)")
    ap.add_argument("--mutate-stream", type=int, default=0, metavar="N",
                    help="open loop: interleave N seeded graph deltas "
                         "evenly through the arrival schedule; each is "
                         "applied through the serving fence (in-flight "
                         "batches finish on the old graph, later "
                         "admissions see the new one)")
    ap.add_argument("--delta-edges", type=int, default=64, metavar="M",
                    help="edges added and deleted per --mutate-stream "
                         "delta")
    ap.add_argument("--query-kind", default="reach",
                    choices=("reach", "topk_paths", "ppr", "pattern_counts"),
                    help="scenario family served by every arrival/batch: "
                         "'reach' = BFS levels (the historical surface), "
                         "'topk_paths' = weighted k-shortest distances "
                         "(synthesizes seeded edge weights when the "
                         "dataset has none), 'ppr' = personalized "
                         "PageRank mass, 'pattern_counts' = 2/3-hop "
                         "wedge+triangle walk counts; non-reach kinds "
                         "are never lane-packed")
    ap.add_argument("--paths", action="store_true",
                    help="return actual paths (parents), not lengths "
                         "(closed loop only)")
    ap.add_argument("--policy", default=None,
                    choices=(None, "1t1s", "nt1s", "ntks", "ntkms"))
    ap.add_argument("--backend", default="recommend",
                    choices=("ell_push", "ell_pull", "pull_binned",
                             "pull_binned_fused", "block_mxu", "dopt",
                             "dopt_ell", "dopt_binned", "dopt_fused",
                             "recommend"),
                    help="frontier-extension backend; the default "
                         "'recommend' picks the scan layout per batch via "
                         "recommend_backend (direction-optimized binned "
                         "pull for the BFS family) — all choices are "
                         "bit-identical in results")
    ap.add_argument("--thresholds", default=None, metavar="BENCH_JSON",
                    help="fit the direction switch's alpha/beta from this "
                         "BENCH_direction_opt.json trace file "
                         "(core.policies.fit_direction_thresholds) instead "
                         "of Beamer's constants; an explicit table is a "
                         "PIN — online refitting will not replace it")
    ap.add_argument("--static", action="store_true",
                    help="disable the adaptive hybrid (static dispatch)")
    ap.add_argument("--online-adapt", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="online policy learning: per-(family, "
                         "source-degree-bucket) phase-1 budget model + "
                         "in-flight direction-threshold refitting from the "
                         "live per-iteration sample tap "
                         "(--no-online-adapt pins the legacy global-p90 "
                         "budget and static thresholds)")
    ap.add_argument("--refit-every", type=int, default=16,
                    help="batches between in-flight threshold refits")
    ap.add_argument("--cost-mode", default="auto",
                    choices=("auto", "slots", "measured"),
                    help="direction-threshold fit cost model: 'slots' "
                         "scores by scan-slot counts (deterministic); "
                         "'measured' converts slots to wall-ms via the "
                         "lazily-probed per-backend rates "
                         "(core.extend.BackendCostProbe); 'auto' picks "
                         "measured on TPU, slots on CPU/interpret")
    args = ap.parse_args(argv)
    if args.backend in ("pull_binned_fused", "dopt_fused"):
        require_lowerable()
    enable_compile_cache()

    csr = PAPER_DATASETS[args.dataset](args.scale)
    if args.query_kind == "topk_paths" and csr.weights is None:
        # the k-shortest relax needs edge weights; paper proxy datasets
        # are unweighted, so synthesize a seeded uniform weighting (the
        # same convention as the weighted-graph test corpus)
        rng = np.random.default_rng(7)
        csr = dataclasses.replace(
            csr,
            weights=rng.uniform(0.1, 2.0, csr.n_edges).astype(np.float32),
        )
    mesh = make_mesh((1, jax.device_count()), ("data", "model"))
    # threshold-table family of the dataset (None => Beamer-default /
    # nearest-bucket fallback inside DirectionThresholds.lookup)
    family = PAPER_DATASET_FAMILIES.get(args.dataset)
    print(
        f"serving {args.dataset} proxy: {csr.n_nodes} nodes, "
        f"{csr.n_edges} edges, avg degree {csr.avg_degree:.0f}"
    )
    if args.closed_loop or args.paths:
        return run_closed_loop(args, csr, mesh, family)
    return run_open_loop(args, csr, mesh, family)


if __name__ == "__main__":
    raise SystemExit(main())
