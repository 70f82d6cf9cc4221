"""Streamed vs wholesale operand build at scale-out size (ISSUE 10).

The scale-out claim: ``prepare_graph(stream=True)`` builds the operand
set one policy shard at a time (``operand_stream`` plans once, then
``build_shard(k)`` -> per-device placement -> global assembly via
``jax.make_array_from_single_device_arrays``), so host peak memory is
~one shard's operand bytes plus the resident CSR — instead of the whole
padded structure the wholesale path materializes before placing. On a
billion-edge graph the wholesale host peak is the thing that OOMs first;
this benchmark measures the two builds on a degree-matched proxy >=10x
the largest graph any other benchmark in this repo touches.

Measured here, each build mode in a **fresh subprocess** (``ru_maxrss``
is monotone per process, so wholesale-then-streamed in one process would
hide the streamed savings; ``multiprocessing`` spawn keeps the two
measurements independent), on 8 virtual CPU devices (2x4 mesh, nTkS
policy -> 4 graph shards), building the widest operand set
(``pull_binned_fused``: forward ELL + binned reverse slabs + kernel
pack):

- **wholesale**: ``prepare_graph(stream=False)`` — the seed path;
- **streamed**: ``prepare_graph(stream=True)`` — the scale-out path;
- per mode: build wall, ``tracemalloc`` peak (numpy allocations are
  traced, and the host-side operand build is pure numpy — this is the
  robust peak-host-memory signal at proxy scale), ``ru_maxrss``, and
  per-device live operand bytes (leaf shard ``nbytes``);
- **bitwise parity**: per-leaf sha256 digests of the device-assembled
  operands, compared across the two modes — the streamed build must be
  bit-identical, not just close;
- **chunked-hub oracle**: on a hub graph whose widest binned slab blows
  any reasonable gather budget, the binned pull's stepped scan
  (``BinnedPullBackend._reach_lanes`` / ``_min_parent_lanes``) under an
  artificially tiny ``_deg_chunk`` budget must match its one-step scan
  bit-for-bit.

Floors (asserted in-process and by ``scripts/ci.sh --bench-smoke``):
streamed tracemalloc peak strictly below wholesale, digests identical,
chunked oracle exact; the full run additionally requires the >=10x
workload size and the streamed ``ru_maxrss`` no worse than wholesale.

Writes machine-readable ``BENCH_scale_out.json`` (schema validated
in-process and re-validated by the CI lane).

    PYTHONPATH=src python benchmarks/scale_out.py [--smoke] \
        [--out BENCH_scale_out.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))
sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

SCHEMA = 1

# largest graph any other benchmark builds (direction_opt's powerlaw_d6)
LARGEST_OTHER_BENCH_NODES = 4096

REQUIRED = {
    "schema": int,
    "smoke": bool,
    "workload": dict,
    "modes": dict,
    "parity": dict,
    "chunked_oracle": dict,
    "summary": dict,
}
MODE_FIELDS = (
    "build_wall_ms", "tracemalloc_peak_bytes", "ru_maxrss_kb",
    "device_bytes", "max_device_bytes", "total_device_bytes", "n_pad",
    "n_leaves",
)


def validate(doc: dict) -> None:
    """Schema + acceptance guards for BENCH_scale_out.json: the streamed
    build's traced host peak strictly below wholesale, every operand leaf
    bit-identical across the two builds, the chunked hub gather exact
    against the unchunked oracle; full runs must also hit the >=10x
    workload floor and keep streamed ``ru_maxrss`` no worse than
    wholesale."""
    for key, ty in REQUIRED.items():
        assert key in doc, f"missing top-level field: {key}"
        assert isinstance(doc[key], ty), (key, type(doc[key]))
    assert doc["schema"] == SCHEMA, doc["schema"]
    for mode in ("wholesale", "streamed"):
        assert mode in doc["modes"], f"missing mode: {mode}"
        for f in MODE_FIELDS:
            assert f in doc["modes"][mode], (mode, f)
    w, s = doc["modes"]["wholesale"], doc["modes"]["streamed"]
    assert w["n_pad"] == s["n_pad"], (w["n_pad"], s["n_pad"])
    assert w["n_leaves"] == s["n_leaves"], (w["n_leaves"], s["n_leaves"])
    assert doc["parity"]["digests_match"] is True, (
        "streamed operands must be bitwise-identical to wholesale",
        doc["parity"],
    )
    assert doc["parity"]["n_leaves"] >= 5, doc["parity"]
    assert doc["chunked_oracle"]["reach_match"] is True, doc["chunked_oracle"]
    assert doc["chunked_oracle"]["parent_match"] is True, (
        doc["chunked_oracle"]
    )
    assert doc["chunked_oracle"]["hub_width"] > doc["chunked_oracle"][
        "forced_chunk"
    ], ("oracle must actually exercise chunking", doc["chunked_oracle"])
    su = doc["summary"]
    for f in ("wholesale_peak_bytes", "streamed_peak_bytes",
              "peak_reduction", "passes_memory_floor"):
        assert f in su, f"missing summary field: {f}"
    assert su["passes_memory_floor"] is True, su
    assert su["streamed_peak_bytes"] < su["wholesale_peak_bytes"], (
        "streamed host peak must be strictly below wholesale: "
        f"{su['streamed_peak_bytes']} vs {su['wholesale_peak_bytes']}"
    )
    if not doc["smoke"]:
        assert doc["workload"]["n_nodes"] >= 10 * LARGEST_OTHER_BENCH_NODES, (
            "full run must be >=10x the largest other bench graph",
            doc["workload"],
        )
        assert s["ru_maxrss_kb"] <= w["ru_maxrss_kb"], (
            "streamed process RSS regressed past wholesale", s, w
        )


def smoke_line(doc: dict) -> str:
    """One-line artifact summary for the CI bench-smoke lane."""
    su = doc["summary"]
    wl = doc["workload"]
    return (
        f"{wl['n_nodes']} nodes / {wl['n_edges']} edges ({wl['extend']}): "
        f"streamed host peak {su['streamed_peak_bytes'] / 2**20:.1f} MiB "
        f"vs wholesale {su['wholesale_peak_bytes'] / 2**20:.1f} MiB "
        f"({su['peak_reduction']:.2f}x lower), operands bit-identical "
        f"{doc['parity']['digests_match']}, chunked hub oracle exact "
        f"{doc['chunked_oracle']['reach_match']}"
    )


def _measure_build(mode: str, cfg: dict, out_path: str) -> None:
    """Subprocess worker: one build mode, fresh process, fresh rusage.

    Sets the virtual-device count *before* jax imports, regenerates the
    workload graph from (n, degree, seed), runs ``prepare_graph`` with
    the mode's ``stream`` flag, and writes wall/peak/RSS/per-device
    bytes plus per-leaf sha256 digests as JSON."""
    import os

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={cfg['devices']}"
    )
    import hashlib
    import resource
    import time
    import tracemalloc

    import numpy as np

    import jax

    from repro.core.dispatcher import prepare_graph
    from repro.core.policies import policy_ntks
    from repro.graph.generators import powerlaw
    from repro.launch.mesh import make_mesh

    csr = powerlaw(cfg["n_nodes"], cfg["avg_degree"], seed=cfg["seed"])
    mesh = make_mesh(
        (cfg["devices"] // cfg["model_axis"], cfg["model_axis"]),
        ("data", "model"),
    )
    policy = policy_ntks()

    # the CSR is resident in both modes; trace only the build itself
    tracemalloc.start()
    t0 = time.perf_counter()
    ops, n_pad = prepare_graph(
        csr, mesh, policy, pad_shards=mesh.size, extend=cfg["extend"],
        stream=(mode == "streamed"),
    )
    jax.block_until_ready(jax.tree_util.tree_leaves(ops))
    wall_ms = (time.perf_counter() - t0) * 1e3
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

    # digests + device accounting AFTER the measurement window (the
    # device_get copies below must not pollute the traced peak)
    device_bytes: dict = {}
    digests = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(ops)[0]:
        name = jax.tree_util.keystr(kp)
        for sh in leaf.addressable_shards:
            did = str(sh.device.id)
            device_bytes[did] = device_bytes.get(did, 0) + int(
                sh.data.nbytes
            )
        arr = np.asarray(jax.device_get(leaf))
        h = hashlib.sha256()
        h.update(str((name, arr.shape, str(arr.dtype))).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
        digests[name] = h.hexdigest()

    Path(out_path).write_text(json.dumps({
        "mode": mode,
        "build_wall_ms": float(wall_ms),
        "tracemalloc_peak_bytes": int(peak),
        "ru_maxrss_kb": int(rss_kb),
        "device_bytes": device_bytes,
        "max_device_bytes": max(device_bytes.values()),
        "total_device_bytes": sum(device_bytes.values()),
        "n_pad": int(n_pad),
        "n_leaves": len(digests),
        "digests": digests,
        "n_edges": int(csr.n_edges),
    }))


def chunked_hub_oracle(forced_budget: int = 4096) -> dict:
    """Bitwise parity of the degree-chunked binned slab gathers against
    the unchunked gather on a hub graph (one node whose in-degree dwarfs
    the rest, i.e. the widest slab far exceeds the forced chunk)."""
    import numpy as np

    import jax.numpy as jnp

    import repro.core.edge_compute as EC
    import repro.core.extend as E
    from repro.graph.csr import csr_from_edges

    rng = np.random.default_rng(7)
    n, hub_deg = 2048, 1200
    src = np.concatenate([
        rng.integers(0, n, 3 * n), np.arange(hub_deg) % (n - 1) + 1,
    ])
    dst = np.concatenate([rng.integers(0, n, 3 * n), np.zeros(hub_deg, np.int64)])
    csr = csr_from_edges(n, src, dst)
    ops, n_pad = E.build_operands(csr, extend="pull_binned")
    bn = ops.rev_binned
    widths = tuple(int(s.shape[-1]) for s in bn.slabs)
    L = 8
    gl = jnp.asarray(
        (rng.random((n_pad, L)) < 0.3).astype(np.uint8)
    )

    ctx = E.ExtendCtx(n_out=n_pad)

    def run():
        reach = E.BinnedPullBackend._reach_lanes(ops, gl, None, ctx)
        par = E.BinnedPullBackend._min_parent_lanes(ops, gl, None, ctx)
        return np.asarray(reach), np.asarray(par)

    ref_reach, ref_par = run()
    orig = EC._deg_chunk
    try:
        EC._deg_chunk = lambda rows, per_slot, budget=0: orig(
            rows, per_slot, forced_budget
        )
        forced_chunk = EC._deg_chunk(1, L)
        got_reach, got_par = run()
    finally:
        EC._deg_chunk = orig
    return {
        "hub_width": int(max(widths)),
        "forced_chunk": int(forced_chunk),
        "reach_match": bool((got_reach == ref_reach).all()),
        "parent_match": bool((got_par == ref_par).all()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small graph (CI bench-smoke lane)")
    ap.add_argument("--out", default=str(
        Path(__file__).resolve().parent.parent / "BENCH_scale_out.json"
    ))
    args = ap.parse_args(argv)

    import multiprocessing as mp

    if args.smoke:
        n_nodes, avg_degree = 8192, 6.0
    else:
        # >=10x the largest graph any other benchmark builds (4096 nodes)
        n_nodes, avg_degree = 65536, 8.0
    cfg = {
        "n_nodes": n_nodes,
        "avg_degree": avg_degree,
        "seed": 17,
        "devices": 8,
        "model_axis": 4,  # nTkS graph axis -> 4 policy shards
        "extend": "pull_binned_fused",  # widest operand set (fwd+binned+pack)
    }
    print(
        f"scale-out workload: {n_nodes} nodes x avg degree ~{avg_degree} "
        f"(symmetric), extend={cfg['extend']}, 2x4 mesh, one subprocess "
        f"per build mode"
    )

    ctx = mp.get_context("spawn")
    results = {}
    with tempfile.TemporaryDirectory() as td:
        for mode in ("wholesale", "streamed"):
            out = str(Path(td) / f"{mode}.json")
            p = ctx.Process(target=_measure_build, args=(mode, cfg, out))
            p.start()
            p.join()
            assert p.exitcode == 0, f"{mode} build subprocess failed"
            results[mode] = json.loads(Path(out).read_text())
            r = results[mode]
            print(
                f"{mode}: build {r['build_wall_ms']:.0f} ms, traced peak "
                f"{r['tracemalloc_peak_bytes'] / 2**20:.1f} MiB, maxrss "
                f"{r['ru_maxrss_kb'] / 2**10:.1f} MiB, device bytes "
                f"{r['total_device_bytes'] / 2**20:.1f} MiB total / "
                f"{r['max_device_bytes'] / 2**20:.2f} MiB max"
            )

    w, s = results["wholesale"], results["streamed"]
    digests_match = w.pop("digests") == s.pop("digests")
    print(f"parity: {w['n_leaves']} leaves, digests_match={digests_match}")

    oracle = chunked_hub_oracle()
    print(
        f"chunked hub oracle: widest slab {oracle['hub_width']} cols, "
        f"forced chunk {oracle['forced_chunk']}, reach_match="
        f"{oracle['reach_match']}, parent_match={oracle['parent_match']}"
    )

    wp, sp = w["tracemalloc_peak_bytes"], s["tracemalloc_peak_bytes"]
    doc = {
        "schema": SCHEMA,
        "smoke": bool(args.smoke),
        "workload": {
            "n_nodes": int(n_nodes),
            "n_edges": int(w["n_edges"]),
            "avg_degree": float(avg_degree),
            "extend": cfg["extend"],
            "devices": cfg["devices"],
            "graph_shards": cfg["model_axis"],
            "largest_other_bench_nodes": LARGEST_OTHER_BENCH_NODES,
        },
        "modes": results,
        "parity": {
            "digests_match": bool(digests_match),
            "n_leaves": int(w["n_leaves"]),
        },
        "chunked_oracle": oracle,
        "summary": {
            "wholesale_peak_bytes": int(wp),
            "streamed_peak_bytes": int(sp),
            "peak_reduction": float(wp / sp) if sp else 1.0,
            "wholesale_maxrss_kb": int(w["ru_maxrss_kb"]),
            "streamed_maxrss_kb": int(s["ru_maxrss_kb"]),
            "passes_memory_floor": bool(sp < wp),
        },
    }
    validate(doc)
    Path(args.out).write_text(json.dumps(doc, indent=1, sort_keys=True))
    print(
        f"summary: streamed peak {sp / 2**20:.1f} MiB vs wholesale "
        f"{wp / 2**20:.1f} MiB ({doc['summary']['peak_reduction']:.2f}x "
        f"lower)"
    )
    print(f"wrote {args.out} (schema v{SCHEMA} validated)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
