"""Discrete-event simulator of the paper's morsel dispatching policies.

Reproduces the paper's thread-scaling experiments (Tables 1/3/4, Figs 9-12)
from per-frontier work traces measured on the engine (counts, not times).

Model (paper §3/§4):
- A *source morsel* is an IFE run: a list of per-level work amounts
  (edge-scan units, measured as sum of frontier out-degrees).
- A *frontier morsel* is a ≤ morsel_nodes slice of one level's frontier;
  the level's work divides evenly across its morsels (plus a fixed
  dispatch overhead EPS per morsel — the grabFrontierMorsel cost).
- checkIfFrontierFinished is a per-source barrier: level l+1 morsels
  become available when the last level-l morsel completes.
- 1T1S: a source is ONE indivisible unit of work (vanilla morsel scan).
- nT1S: k=1 — sources sequential, threads share each frontier.
- nTkS: up to k sources concurrently; idle threads grab frontier morsels
  from any active source ("sticky" preference for the last source).
- nTkMS: sources pack into 64-wide lane morsels whose per-level work is
  the measured UNION frontier scan (shared scans) × lane_cost_factor
  (the paper's §5.6 per-edge overhead of updating 64-bit lane state).

Cache-locality term (paper §5.5, Table 6 / Fig 13): running k concurrent
IFE states multiplies per-unit work by (1 + cache_alpha·min(1, (k·state -
llc)/llc · working-set pressure)); calibrated qualitatively — it reproduces
"denser graphs ⇒ lower optimal k", not absolute LLC counts.
"""
from __future__ import annotations

import dataclasses
import heapq

EPS = 0.02  # dispatch overhead per frontier morsel, in avg-morsel units


@dataclasses.dataclass
class SimResult:
    makespan: float
    busy_fraction: float  # 'CPU utilization' analogue

    def speedup_vs(self, t1: "SimResult") -> float:
        return t1.makespan / self.makespan if self.makespan > 0 else 1.0


def _morselize(level_work: float, level_nodes: int, morsel_nodes: int):
    n_morsels = max(-(-level_nodes // morsel_nodes), 1)
    return [level_work / n_morsels] * n_morsels


def simulate(
    traces: list,  # per source: list of (n_nodes, work) levels
    n_threads: int,
    policy: str,
    k: int = 32,
    morsel_nodes: int = 64,
    lanes: int = 1,
    cache_alpha: float = 0.0,
    state_per_source: float = 0.0,
    llc: float = 1.0,
) -> SimResult:
    """Schedules the traces under a policy; returns makespan in work units."""
    if policy == "1t1s":
        # LPT-free greedy: threads grab whole sources
        totals = [sum(w for _, w in t) for t in traces]
        heap = [0.0] * n_threads
        heapq.heapify(heap)
        for w in totals:  # arrival order, like scanning a source table
            t0 = heapq.heappop(heap)
            heapq.heappush(heap, t0 + w)
        makespan = max(heap)
        busy = sum(totals) / (n_threads * makespan) if makespan else 1.0
        return SimResult(makespan, busy)

    if policy == "nt1s":
        k = 1
    elif policy == "ntkms":
        pass  # traces are already lane-packed by the caller
    elif policy != "ntks":
        raise ValueError(policy)

    # cache-pressure factor: concurrent per-source state vs LLC
    def slowdown(active: int) -> float:
        if cache_alpha <= 0 or state_per_source <= 0:
            return 1.0
        pressure = active * state_per_source / llc
        return 1.0 + cache_alpha * max(0.0, pressure - 1.0)

    # per-source state: level index, morsels left to hand out, morsels in
    # flight, work queue for the level
    sources = [
        {"trace": t, "level": 0, "queue": [], "inflight": 0, "done": False}
        for t in traces
    ]
    for s in sources:
        if s["trace"]:
            n, w = s["trace"][0]
            s["queue"] = _morselize(w, n, morsel_nodes)
        else:
            s["done"] = True

    active: list = []
    waiting = [s for s in sources if not s["done"]]
    while len(active) < k and waiting:
        active.append(waiting.pop(0))

    threads = [(0.0, i) for i in range(n_threads)]
    heapq.heapify(threads)
    sticky = {i: None for i in range(n_threads)}
    # events: (time, seq, source) barrier completions (seq breaks ties)
    pending: list = []  # (finish_time, seq, source)
    seq = 0
    busy_time = 0.0
    now = 0.0

    def grab(tid):
        # sticky preference, then any active source with queued morsels
        cand = sticky[tid]
        if cand is not None and not cand["done"] and cand["queue"]:
            return cand
        for s in active:
            if s["queue"]:
                return s
        return None

    while True:
        # retire finished morsels up to the earliest free thread time
        if not threads:
            break
        t_free, tid = heapq.heappop(threads)
        now = max(now, t_free)
        # process barrier completions at or before `now`
        while pending and pending[0][0] <= now:
            _, _, s = heapq.heappop(pending)
            s["inflight"] -= 1
            if not s["queue"] and s["inflight"] == 0:
                s["level"] += 1
                if s["level"] >= len(s["trace"]):
                    s["done"] = True
                    if s in active:
                        active.remove(s)
                    if waiting and len(active) < k:
                        active.append(waiting.pop(0))
                else:
                    n, w = s["trace"][s["level"]]
                    s["queue"] = _morselize(w, n, morsel_nodes)
        src = grab(tid)
        if src is None:
            if not pending:
                if all(s["done"] for s in sources):
                    heapq.heappush(threads, (now, tid))
                    break
                # stall: no morsels and nothing in flight => advance time
                heapq.heappush(threads, (now + EPS, tid))
                continue
            # wait for the next completion
            heapq.heappush(threads, (max(pending[0][0], now), tid))
            continue
        w = src["queue"].pop(0)
        src["inflight"] += 1
        sticky[tid] = src
        dur = (w * lanes_factor(lanes) + EPS) * slowdown(len(active))
        busy_time += dur
        heapq.heappush(pending, (now + dur, seq, src))
        seq += 1
        heapq.heappush(threads, (now + dur, tid))

    makespan = max(t for t, _ in threads) if threads else now
    busy = busy_time / (n_threads * makespan) if makespan > 0 else 1.0
    return SimResult(makespan, min(busy, 1.0))


def lanes_factor(lanes: int) -> float:
    """Per-edge-scan cost multiplier of lane-packed state updates
    (paper §5.6: the extra loop over set bits; calibrated ~1.3 at 64)."""
    return 1.0 + 0.3 * (lanes > 1)
