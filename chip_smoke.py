#!/usr/bin/env python3
"""Smoke run of the served recursive-query path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the (1, 4) mesh: sharded graph/state

One process drives the chip from start to end; it starts no other. It
builds the LDBC proxy graph from a seed, serves a seeded Poisson stream
through ``ServingLoop.run_stream`` with ``serve``'s defaults (backend
"recommend", online adaptation, overlap, ``cost="auto"``, which is
"measured" on a TPU), and checks every delivered result against a plain
numpy BFS. ``--four-chips`` serves the same stream on a ``(1, 4)`` mesh and
adds one batch through ``QueryDispatcher`` with ``state_layout="sharded"``.

The last line of standard output is one JSON object naming the device.
The script exits non-zero and prints no such line when JAX finds no TPU or
too few chips, when any result differs from the oracle, or when any phase
raises.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LDBC100_NODES, LDBC100_EDGES = 448_626, 19_941_198  # configs/paper_bfs.py
GIB = 2**30
# Traffic: single-source queries, one per batch. Each source is one
# sequential morsel whose push steps scan the whole padded forward ELL, so
# a source sets a batch's time; 20 batches pass the dispatcher's first
# threshold refit (every 16), which runs the TPU-only measured-cost probe.
ARRIVALS, RATE_QPS, SOURCES_PER_QUERY = 20, 20.0, 1
MAX_BATCH_SOURCES, MIN_BATCHES = 1, 20


def numpy_bfs(indptr: np.ndarray, indices: np.ndarray, src: int) -> np.ndarray:
    """Level-synchronous BFS; -1 marks unreached nodes."""
    levels = np.full(len(indptr) - 1, -1, np.int32)
    levels[src] = 0
    frontier = np.asarray([src], np.int64)
    depth = 0
    while frontier.size:
        depth += 1
        starts = indptr[frontier]
        counts = indptr[frontier + 1] - starts
        offs = np.arange(int(counts.sum())) - np.repeat(
            np.cumsum(counts) - counts, counts
        )
        nbrs = indices[np.repeat(starts, counts) + offs]
        frontier = np.unique(nbrs[levels[nbrs] < 0])
        levels[frontier] = depth
    return levels


def check_rows(csr, sources, rows, oracle: dict, what: str) -> int:
    """Compare one query's per-source level rows with the oracle; returns
    the number of sources checked, raises on the first difference."""
    rows = np.asarray(rows)
    if rows.shape != (len(sources), csr.n_nodes):
        raise AssertionError(f"{what}: result shape {rows.shape}")
    for s, row in zip(sources, rows):
        s = int(s)
        if s not in oracle:
            oracle[s] = numpy_bfs(csr.indptr, csr.indices, s)
        bad = np.flatnonzero(row != oracle[s])
        if bad.size:
            v = int(bad[0])
            raise AssertionError(
                f"{what}: source {s}, node {v}: engine level {row[v]}, "
                f"oracle {oracle[s][v]} ({bad.size} nodes differ)"
            )
    return len(sources)


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, found {len(devices)}")
    return devices


def serve_stream(mesh, csr, oracle: dict):
    """The open-loop serving phase; returns the loop after checking every
    delivered result."""
    from repro.graph.generators import PAPER_DATASET_FAMILIES
    from repro.launch.serve import poisson_arrivals
    from repro.runtime.service import ServingLoop

    t0 = time.perf_counter()

    def progress(qid, _levels):
        print(f"  {qid} delivered at {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)

    loop = ServingLoop(
        mesh, csr, backend="recommend", family=PAPER_DATASET_FAMILIES["ldbc"],
        online_adapt=True, overlap=True, cost="auto",
        max_batch_sources=MAX_BATCH_SOURCES, on_result=progress,
    )
    arrivals = poisson_arrivals(
        csr, RATE_QPS, ARRIVALS, SOURCES_PER_QUERY, tenants=2, seed=1,
    )
    for i, a in enumerate(arrivals):
        a["qid"] = f"q{i}"
    loop.run_stream(arrivals)
    st, ds = loop.stats, loop.dispatcher.stats
    checked = sum(
        check_rows(csr, a["sources"], loop.results[a["qid"]], oracle, a["qid"])
        for a in arrivals
    )
    cache = loop.dispatcher.cache
    print(
        f"served {st.completed}/{len(arrivals)} queries in {st.batches} "
        f"batches ({st.cold_batches} cold); {checked} source rows equal to "
        f"the numpy BFS"
    )
    print(
        f"engine compile events {cache.compile_events} "
        f"({dict(cache.misses_by_kind)} engines by kind); refits {ds.refits} "
        f"under cost={loop.dispatcher.cost_mode!r}; hybrid batches "
        f"{ds.hybrid_runs}, survivors re-dispatched {ds.redispatched}"
    )
    report = loop.dispatcher.device_report()
    print(
        f"engine iterations sampled: {report['sampled']} over "
        f"{st.batches} batches"
    )
    for n_pad, rates in report["cost_rates"].items():
        for name, r in rates.items():
            print(
                f"cost probe (n_pad {n_pad}): {name} dense step "
                f"{r['probe_ms']:.1f} ms over {r['slots']} slots"
            )
    if st.completed != len(arrivals) or st.shed:
        raise AssertionError(f"{st.shed} shed, {st.completed} completed")
    if st.batches < MIN_BATCHES or ds.refits < 1:
        raise AssertionError(
            f"{st.batches} batches and {ds.refits} refits: the stream must "
            f"reach {MIN_BATCHES} batches and one threshold refit"
        )
    return loop


def sharded_batch(loop, csr, oracle: dict) -> None:
    """One 8-source nTkS batch through the dispatcher with per-shard state
    rows."""
    from repro.graph.generators import pick_sources

    sources = pick_sources(csr, 8, seed=7)
    out = loop.dispatcher.query(sources, policy="ntks", state_layout="sharded")
    levels = np.asarray(out.result.state.levels)[: len(sources), : csr.n_nodes]
    n = check_rows(csr, sources, levels, oracle, "sharded batch")
    print(
        f"sharded-state batch ({out.policy}, hybrid={out.hybrid}): {n} "
        f"source rows equal to the numpy BFS"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a (1, 4) mesh and add a sharded-state "
                         "batch; runs only that phase")
    ap.add_argument("--scale", type=float, default=8.0,
                    help="ldbc_proxy scale (1.0 = 4,486 nodes); 8 holds "
                         "2.6 GiB of operands per bundle on the device")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1

    devices = require_tpu(n_chips)
    from repro.graph.generators import ldbc_proxy
    from repro.launch.compile_cache import CacheEvents, enable_compile_cache
    from repro.launch.mesh import make_mesh

    cache_dir = enable_compile_cache()
    cache_events = CacheEvents()
    csr = ldbc_proxy(args.scale)
    print(
        f"graph: ldbc_proxy scale {args.scale:g}: {csr.n_nodes} nodes, "
        f"{csr.n_edges} edges, max out-degree {int(csr.degrees.max())}; "
        f"cut {csr.n_nodes / LDBC100_NODES:.4f} of LDBC100's nodes and "
        f"{csr.n_edges / LDBC100_EDGES:.4f} of its edges"
    )
    print(f"compile cache: {cache_dir}")
    mesh = make_mesh((1, n_chips), ("data", "model"))
    oracle: dict = {}
    loop = serve_stream(mesh, csr, oracle)
    if args.four_chips:
        sharded_batch(loop, csr, oracle)

    # one bundle per graph-axes split the served policies asked for; each
    # holds its own copy of the operands on the device
    report = loop.dispatcher.device_report()
    for axes, nbytes in report["operand_bytes"].items():
        print(f"graph operands split over {axes}: {nbytes / GIB:.3f} GiB")
    for d in devices[:n_chips]:
        ms = d.memory_stats() or {}
        print(
            f"{d}: bytes_in_use {ms.get('bytes_in_use', 0) / GIB:.3f} GiB, "
            f"peak_bytes_in_use {ms.get('peak_bytes_in_use', 0) / GIB:.3f} GiB"
        )
    print(
        f"persistent compile cache: {cache_events.hits} hits, "
        f"{cache_events.misses} entries written"
    )
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": n_chips,
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
