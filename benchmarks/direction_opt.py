"""Direction-optimizing extension benchmark: scans & wall-clock per backend.

Measures — on live frontier traces, not analytically — what each extension
backend (core.extend) must touch per IFE iteration across frontier-density
regimes:

- ``scanned_slots``: adjacency slots the backend's scan semantics require
  this iteration, computed from the *actual* frontier/visited tensors of the
  run. ell_push gathers the full forward-ELL tensor every iteration;
  ell_pull scans the still-unvisited rows of the single reverse slab padded
  to ``max_in_deg``; pull_binned scans each unvisited row at its own
  degree-bucket slab width (~its true in-degree — asserted ≤ 1.1× the
  ideal ``sum(deg)`` accounting on every workload, the binning acceptance
  floor); pull_binned_fused scans at the Pallas kernel's tile granularity
  (a compute tile is skipped only when every row it feeds is visited);
  dopt takes whichever side its alpha/beta predicate picks that
  iteration (pull side = binned). Every iteration record also carries the
  frontier/unexplored edge masses and all three hypothetical costs
  (``m_frontier`` / ``m_unexplored`` / ``push_slots`` /
  ``pull_slots_ell`` / ``pull_slots_binned``) — the samples
  ``core.policies.fit_direction_thresholds`` fits per-(family,
  degree-bucket) alpha/beta from. Schema v3 additionally joins each
  backend's measured per-iteration wall onto the canonical ell_push
  records (``push_wall_ms`` / ``pull_wall_ms_binned`` /
  ``pull_wall_ms_fused``) — the ``cost="measured"`` fit's inputs — and
  reports the fused-kernel wall floor in the summary.
- ``touched_blocks`` (block_mxu): materialized adjacency tiles whose source
  stripe is frontier-active — exactly the tiles the jnp path masks and the
  Pallas kernel DMAs (inactive tiles are skip-listed), via
  ``core.msbfs.active_block_count`` semantics.
- ``wall_ms``: median wall-clock of the jitted per-iteration step at that
  live state.

Workloads: ER density sweep (the paper Fig 13 family — dense frontiers after
one hop) + a power-law proxy (heavy-tail degrees, ragged frontier growth).
Every backend's final levels are asserted bit-identical before anything is
reported.

Writes machine-readable ``BENCH_direction_opt.json`` (schema validated
in-process; `scripts/ci.sh --bench-smoke` runs the --smoke lane per PR).

    PYTHONPATH=src python benchmarks/direction_opt.py [--smoke] \
        [--out BENCH_direction_opt.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).parent))
sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

from repro.core.edge_compute import EDGE_COMPUTES  # noqa: E402
from repro.core.extend import (  # noqa: E402
    BEAMER_ALPHA,
    BEAMER_BETA,
    ExtendCtx,
    ExtendSpec,
    GraphOperands,
    as_spec,
    build_operands,
    make_backend,
)
from repro.graph.generators import erdos_renyi, powerlaw  # noqa: E402
from repro.kernels.binned_pull.ops import pack_tile_map  # noqa: E402

BACKENDS = (
    "ell_push", "ell_pull", "pull_binned", "pull_binned_fused", "dopt",
    "block_mxu",
)
SCHEMA_VERSION = 3
#: binned-pull acceptance floor: scanned slots vs the ideal sum(deg) scan
BINNED_OVERHEAD_FLOOR = 1.1
#: fused-kernel wall floor tolerance under Pallas INTERPRET mode (this
#: container): interpret executes the kernel's grid as a python-level loop
#: with per-tile dispatch overhead, so the fused single-pass win is
#: invisible and the fused step measures a large constant factor SLOWER
#: than the jnp binned gather it fuses — on the smoke powerlaw workload
#: the observed ratio is ~4.5x, so the floor is checked at this
#: documented tolerance on CPU (empirical ~2x headroom for CI noise) and
#: at 1.0 (fused strictly <= jnp) when ``jax.default_backend() == "tpu"``
#: lowers the kernel for real.
FUSED_WALL_TOL_INTERPRET = 10.0


def _wall_ms(fn, *args, reps: int = 3) -> float:
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(ts))


def _use_pull_host(spec, fwd_deg, frontier, visited, n) -> bool:
    """Host replica of extend.AutoBackend's alpha/beta predicate (under
    Beamer's pair, which the engines run when no table is served)."""
    act = frontier != 0
    n_f = float(act.sum())
    m_f = float(fwd_deg[act].sum())
    m_u = float(fwd_deg[~(visited != 0)].sum())
    return bool((m_f * BEAMER_ALPHA > m_u) and (n_f * BEAMER_BETA > n))


#: one row-padding unit for every operand bundle the bench builds — the
#: block_mxu tile size, which every other backend's pad (32) divides — so
#: the shared counter bundle and each backend's scan operands agree on
#: n_pad for ANY node count, not just 128-multiples
BENCH_PAD_BLOCK = 128


def build_counter_operands(csr, block: int = BENCH_PAD_BLOCK):
    """The backend-independent scan-extent counters: one bundle carrying
    BOTH pull layouts (padded reverse ELL + binned slabs), built once per
    graph and shared by every backend's instrumentation."""
    ell_ops, n_pad = build_operands(
        csr, ExtendSpec(direction="auto", pull="ell"), shards=1, block=block
    )
    bin_ops, n_pad_b = build_operands(
        csr, as_spec("pull_binned"), shards=1, block=block
    )
    assert n_pad_b == n_pad, (n_pad_b, n_pad)
    return (
        GraphOperands(
            fwd=ell_ops.fwd, rev=ell_ops.rev, rev_binned=bin_ops.rev_binned
        ),
        n_pad,
    )


def run_backend(
    csr, source: int, backend: str, max_iters: int, full_ops, n_pad
) -> dict:
    """One full BFS under one backend, instrumented per iteration."""
    spec = as_spec(backend)
    # operands handed to the engine carry exactly what the spec says; the
    # shared ``full_ops`` bundle only feeds the scan counters
    ops, n_pad2 = build_operands(
        csr, spec, shards=1, block=BENCH_PAD_BLOCK
    )
    assert n_pad2 == n_pad, (n_pad2, n_pad)
    ec = EDGE_COMPUTES["sp_lengths"]
    be = make_backend(spec)
    ctx = ExtendCtx(n_out=n_pad)

    @jax.jit
    def step(state, it):
        contribution = ec.extend(be, ops, state, ctx)
        return ec.apply(state, contribution, it)

    fwd_slots = full_ops.fwd.slots
    rev_row_w = int(full_ops.rev.indices.shape[1])
    fwd_deg = np.asarray(full_ops.fwd.degrees).reshape(-1)
    # per-row binned slab widths + true in-degrees: the binned-pull scan
    # cost of one iteration is the widths of the still-unvisited rows
    # (the uncapped reverse ELL's degree vector IS the true in-degrees)
    bin_width = full_ops.rev_binned.row_widths()[0].astype(np.int64)
    rev_deg = np.asarray(full_ops.rev.degrees).astype(np.int64)

    # fused-kernel tile accounting: the Pallas kernel skips a compute tile
    # only when EVERY out-row it feeds is visited, so its scanned slots are
    # the tile_slots of tiles containing >=1 unvisited row (tile-granular,
    # vs the jnp path's row-granular widths)
    tile_of_row = tile_slots = None
    if spec.needs_binned_pack:
        tile_of_row, tile_slots = pack_tile_map(ops.rev_binned_pack)

    touched_fn = None
    if spec.needs_blocks:
        sb = ops.blocks
        bcols = np.asarray(sb.block_cols[0])
        brows = jnp.asarray(sb.block_rows[0])
        valid = jnp.asarray(bcols < (n_pad // sb.block_size))
        B = sb.block_size

        @jax.jit
        def touched_fn(frontier):
            stripe = (
                frontier.reshape(n_pad // B, B) != 0
            ).any(axis=1)
            return (stripe[brows] & valid).sum(dtype=jnp.int32)

    state = ec.init(n_pad, jnp.array([source], jnp.int32))
    iters = []
    ideal_pull_slots = 0  # sum over iterations of sum(deg of unvisited)
    for it in range(max_iters):
        f = np.asarray(state.frontier)
        v = np.asarray(state.visited)
        n_f = int((f != 0).sum())
        if n_f == 0:
            break
        unvis_mask = v == 0
        unvis = int(unvis_mask.sum())
        active = f != 0
        # the three hypothetical costs + the edge masses of the Beamer
        # predicate — identical across backends (bit-parity => identical
        # frontier trajectories), recorded for fit_direction_thresholds
        push_slots = fwd_slots
        pull_slots_ell = unvis * rev_row_w
        pull_slots_binned = int(bin_width[unvis_mask].sum())
        ideal_pull_slots += int(rev_deg[unvis_mask].sum())
        m_f = int(fwd_deg[active].sum())
        m_u = int(fwd_deg[unvis_mask].sum())
        direction = None
        if backend == "ell_push":
            scanned = push_slots
        elif backend == "ell_pull":
            scanned = pull_slots_ell
        elif backend == "pull_binned":
            scanned = pull_slots_binned
        elif backend == "pull_binned_fused":
            act_tiles = np.zeros(tile_slots.shape[0], bool)
            t = tile_of_row[unvis_mask]
            act_tiles[t[t >= 0]] = True
            scanned = int(tile_slots[act_tiles].sum())
        elif backend == "dopt":
            pull = _use_pull_host(spec, fwd_deg, f, v, n_pad)
            direction = "pull" if pull else "push"
            scanned = pull_slots_binned if pull else push_slots
        else:  # block_mxu: dense tiles, reported in tile cells
            tb = int(touched_fn(state.frontier))
            scanned = tb * spec.block * spec.block
        rec = {
            "it": it,
            "frontier": n_f,
            "unvisited": unvis,
            "scanned_slots": int(scanned),
            "push_slots": push_slots,
            "pull_slots_ell": pull_slots_ell,
            "pull_slots_binned": pull_slots_binned,
            "m_frontier": m_f,
            "m_unexplored": m_u,
            "touched_blocks": (
                int(touched_fn(state.frontier))
                if touched_fn is not None
                else None
            ),
            "direction": direction,
            "wall_ms": _wall_ms(step, state, jnp.int32(it)),
        }
        iters.append(rec)
        state = jax.block_until_ready(step(state, jnp.int32(it)))
    levels = np.asarray(state.levels)[: csr.n_nodes]
    bn = full_ops.rev_binned
    return {
        "iterations": iters,
        "total_slots": int(sum(r["scanned_slots"] for r in iters)),
        "total_wall_ms": float(sum(r["wall_ms"] for r in iters)),
        "ideal_pull_slots": int(ideal_pull_slots),
        "binned": {
            "n_slabs": int(bn.n_slabs),
            "widths": list(bn.widths),
            "capacity_slots": int(bn.capacity_slots),
            "rev_sum_deg": int(rev_deg.sum()),
            "overhead_vs_sum_deg": round(
                bn.capacity_slots / max(int(rev_deg.sum()), 1), 4
            ),
        },
        "levels": levels,  # stripped before serialization (parity check)
    }


def bench_graph(name, kind, csr, max_iters: int) -> dict:
    from repro.graph.generators import pick_sources

    source = int(pick_sources(csr, 1, seed=7)[0])
    full_ops, n_pad = build_counter_operands(csr)
    out = {
        "graph": name,
        "kind": kind,
        "n": int(csr.n_nodes),
        # the live Beamer predicate compares n_f*beta against the PADDED
        # row count (ExtendCtx.n_out); fit_direction_thresholds fits beta
        # against this field so served thresholds match the fit
        "n_pad": int(n_pad),
        "n_edges": int(csr.n_edges),
        "avg_degree": float(csr.avg_degree),
        "source": source,
        "backends": {},
    }
    ref = None
    for be in BACKENDS:
        r = run_backend(csr, source, be, max_iters, full_ops, n_pad)
        levels = r.pop("levels")
        if ref is None:
            ref = levels
        else:
            assert (levels == ref).all(), f"{name}:{be} parity violation"
        out.setdefault("binned", r.pop("binned"))
        out["backends"][be] = r
        print(
            f"  {name:12s} {be:11s} slots {r['total_slots']:>12,} "
            f"wall {r['total_wall_ms']:8.1f} ms "
            f"({len(r['iterations'])} iters)"
        )
    # binned-pull scanned-slot accounting floor (ISSUE 3 acceptance): the
    # degree-binned slabs must scan within BINNED_OVERHEAD_FLOOR of the
    # ideal sum(deg)-based scan — both as layout capacity and as actually
    # scanned slots over this live trace — on EVERY workload, and never
    # more than the single padded reverse slab.
    pb = out["backends"]["pull_binned"]
    ideal = max(pb["ideal_pull_slots"], 1)
    assert pb["total_slots"] <= BINNED_OVERHEAD_FLOOR * ideal, (
        name, pb["total_slots"], ideal,
    )
    assert pb["total_slots"] <= out["backends"]["ell_pull"]["total_slots"], name
    assert (
        out["binned"]["overhead_vs_sum_deg"] <= BINNED_OVERHEAD_FLOOR
    ), (name, out["binned"])
    # schema v3: join each backend's measured per-iteration wall onto the
    # canonical ell_push records (bit-parity => identical trajectories, so
    # iteration i is the same physical iteration under every backend) —
    # exactly the fields fit_direction_thresholds(cost="measured") reads
    binned_by_it = {r["it"]: r for r in pb["iterations"]}
    fused_by_it = {
        r["it"]: r
        for r in out["backends"]["pull_binned_fused"]["iterations"]
    }
    for r in out["backends"]["ell_push"]["iterations"]:
        r["push_wall_ms"] = r["wall_ms"]
        b, fz = binned_by_it.get(r["it"]), fused_by_it.get(r["it"])
        r["pull_wall_ms_binned"] = None if b is None else b["wall_ms"]
        r["pull_wall_ms_fused"] = None if fz is None else fz["wall_ms"]
    return out


def summarize(workloads: list[dict]) -> dict:
    """Acceptance metric: scanned-slot reduction at large-frontier
    iterations (frontier ≥ 10% of n) on the densest ER workload."""
    dense = [w for w in workloads if w["kind"] == "er"]
    dense.sort(key=lambda w: w["avg_degree"])
    w = dense[-1]
    push = w["backends"]["ell_push"]["iterations"]
    large = [r["it"] for r in push if r["frontier"] >= 0.1 * w["n"]]
    if not large:  # degenerate smoke graph: fall back to the peak iteration
        large = [max(push, key=lambda r: r["frontier"])["it"]]

    def slots_at(backend):
        recs = {
            r["it"]: r for r in w["backends"][backend]["iterations"]
        }
        return sum(recs[i]["scanned_slots"] for i in large if i in recs)

    push_slots = slots_at("ell_push")
    pull_slots = slots_at("ell_pull")
    dopt_slots = slots_at("dopt")
    reduction = push_slots / max(dopt_slots, 1)
    summary = {
        "dense_er": {
            "graph": w["graph"],
            "large_frontier_iterations": large,
            "push_slots": push_slots,
            "pull_slots": pull_slots,
            "dopt_slots": dopt_slots,
            "scan_reduction_dopt_vs_push": round(reduction, 2),
            "scan_reduction_pull_vs_push": round(
                push_slots / max(pull_slots, 1), 2
            ),
            "passes_2x": bool(reduction >= 2.0),
        }
    }
    # power-law acceptance: the heavy-tail graph where the padded reverse
    # slab pays n·max_in_deg and binning pays ~sum(deg)
    pls = [w for w in workloads if w["kind"] == "powerlaw"]
    if pls:
        w = max(pls, key=lambda w: w["n_edges"])
        pb = w["backends"]["pull_binned"]
        pe = w["backends"]["ell_pull"]
        ideal = max(pb["ideal_pull_slots"], 1)
        overhead = pb["total_slots"] / ideal
        summary["powerlaw_binned"] = {
            "graph": w["graph"],
            "ideal_pull_slots": ideal,
            "binned_pull_slots": pb["total_slots"],
            "ell_pull_slots": pe["total_slots"],
            "binned_overhead_vs_ideal": round(overhead, 4),
            "scan_reduction_binned_vs_ell_pull": round(
                pe["total_slots"] / max(pb["total_slots"], 1), 2
            ),
            "capacity_overhead_vs_sum_deg": w["binned"][
                "overhead_vs_sum_deg"
            ],
            "passes_overhead_floor": bool(
                overhead <= BINNED_OVERHEAD_FLOOR
                and w["binned"]["overhead_vs_sum_deg"]
                <= BINNED_OVERHEAD_FLOOR
            ),
        }
        # fused-kernel wall floor (schema v3): the Pallas slab-major kernel
        # vs the jnp binned gather it fuses, summed over the same live
        # trajectory. On real TPU lowering the fused single-VMEM-pass must
        # be no slower (tol 1.0); interpret mode pays python-loop grid
        # overhead instead, checked at the documented tolerance.
        pf = w["backends"]["pull_binned_fused"]
        interpret = jax.default_backend() != "tpu"
        tol = FUSED_WALL_TOL_INTERPRET if interpret else 1.0
        wall_f, wall_j = pf["total_wall_ms"], pb["total_wall_ms"]
        summary["fused_kernel"] = {
            "graph": w["graph"],
            "wall_ms_fused": round(wall_f, 3),
            "wall_ms_binned_jnp": round(wall_j, 3),
            "wall_ratio_fused_over_jnp": round(
                wall_f / max(wall_j, 1e-9), 3
            ),
            "interpret_mode": interpret,
            "wall_tolerance": tol,
            "scanned_slots_fused": pf["total_slots"],
            "scanned_slots_binned": pb["total_slots"],
            "passes_fused_wall_floor": bool(wall_f <= wall_j * tol),
        }
    return summary


def load(path) -> dict:
    """Versioned loader for ``BENCH_direction_opt.json`` artifacts.

    Accepts schema v2 (pre-fused, slots-only) and v3 documents; v2 docs
    are normalized in place to the v3 record surface — the wall-join
    fields read as ``None`` (so a measured-cost fit over an old trace
    degrades to the Beamer defaults instead of KeyError-ing) and the
    absent fused backend simply stays absent. Unknown versions raise."""
    doc = json.loads(Path(path).read_text())
    v = doc.get("meta", {}).get("schema_version")
    if v not in (2, SCHEMA_VERSION):
        raise ValueError(
            f"unsupported BENCH_direction_opt schema_version {v!r} "
            f"(supported: 2, {SCHEMA_VERSION})"
        )
    if v == 2:
        for w in doc.get("workloads", []):
            push = w.get("backends", {}).get("ell_push", {})
            for r in push.get("iterations", []):
                r.setdefault("push_wall_ms", None)
                r.setdefault("pull_wall_ms_binned", None)
                r.setdefault("pull_wall_ms_fused", None)
    return doc


def validate(doc: dict) -> None:
    """Schema check (run in-process and by scripts/ci.sh --bench-smoke)."""
    assert doc["meta"]["bench"] == "direction_opt"
    assert doc["meta"]["schema_version"] == SCHEMA_VERSION
    for k in ("alpha", "beta", "block"):
        assert isinstance(doc["meta"][k], (int, float)), k
    assert isinstance(doc["workloads"], list) and doc["workloads"]
    for w in doc["workloads"]:
        for k in ("graph", "kind", "n", "n_pad", "n_edges", "avg_degree",
                  "backends", "binned"):
            assert k in w, (w["graph"], k)
        assert set(w["backends"]) == set(BACKENDS), w["graph"]
        # per-bucket slab schema: widths ascending with a zero-width slab
        # first (the truncation-emptied / zero-in-degree rows), capacity
        # within the overhead floor of the true edge count
        b = w["binned"]
        for k in ("n_slabs", "widths", "capacity_slots", "rev_sum_deg",
                  "overhead_vs_sum_deg"):
            assert k in b, (w["graph"], k)
        assert b["n_slabs"] == len(b["widths"]) >= 1, b
        assert b["widths"][0] == 0, b["widths"]
        assert b["widths"] == sorted(b["widths"]), b["widths"]
        assert b["overhead_vs_sum_deg"] <= BINNED_OVERHEAD_FLOOR, b
        for be, r in w["backends"].items():
            assert r["iterations"], (w["graph"], be)
            for rec in r["iterations"]:
                for k in ("it", "frontier", "scanned_slots", "wall_ms",
                          "push_slots", "pull_slots_ell",
                          "pull_slots_binned", "m_frontier",
                          "m_unexplored"):
                    assert k in rec, (w["graph"], be, k)
            assert r["total_slots"] == sum(
                rec["scanned_slots"] for rec in r["iterations"]
            )
            assert "ideal_pull_slots" in r, (w["graph"], be)
        # v3: the canonical push records carry each backend's measured
        # per-iteration wall (the measured-cost fit's input fields)
        for rec in w["backends"]["ell_push"]["iterations"]:
            for k in ("push_wall_ms", "pull_wall_ms_binned",
                      "pull_wall_ms_fused"):
                assert k in rec and rec[k] is not None, (w["graph"], k)
    s = doc["summary"]["dense_er"]
    for k in (
        "push_slots", "dopt_slots", "scan_reduction_dopt_vs_push",
        "passes_2x",
    ):
        assert k in s, k
    pl = doc["summary"].get("powerlaw_binned")
    assert pl is not None, "powerlaw workload missing from bench"
    for k in ("ideal_pull_slots", "binned_pull_slots",
              "binned_overhead_vs_ideal",
              "scan_reduction_binned_vs_ell_pull",
              "passes_overhead_floor"):
        assert k in pl, k
    assert pl["passes_overhead_floor"], pl
    fk = doc["summary"].get("fused_kernel")
    assert fk is not None, "fused-kernel summary missing from bench"
    for k in ("wall_ms_fused", "wall_ms_binned_jnp",
              "wall_ratio_fused_over_jnp", "interpret_mode",
              "wall_tolerance", "passes_fused_wall_floor"):
        assert k in fk, k
    assert fk["passes_fused_wall_floor"], fk


def smoke_line(doc: dict) -> str:
    """One-line artifact summary for the CI bench-smoke lane."""
    pl = doc["summary"]["powerlaw_binned"]
    fk = doc["summary"]["fused_kernel"]
    return (
        f"dense-ER reduction "
        f"{doc['summary']['dense_er']['scan_reduction_dopt_vs_push']}x, "
        f"binned pull {pl['binned_overhead_vs_ideal']}x ideal / "
        f"{pl['scan_reduction_binned_vs_ell_pull']}x fewer slots than "
        f"padded pull, fused wall "
        f"{fk['wall_ratio_fused_over_jnp']}x jnp "
        f"(tol {fk['wall_tolerance']}"
        f"{' interpret' if fk['interpret_mode'] else ''}, "
        f"passes={fk['passes_fused_wall_floor']})"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph, schema-validation lane for CI")
    ap.add_argument("--out", default="BENCH_direction_opt.json")
    ap.add_argument("--max-iters", type=int, default=64)
    args = ap.parse_args(argv)

    spec = as_spec("dopt")
    if args.smoke:
        graphs = [
            ("er_smoke", "er", erdos_renyi(512, 8.0, seed=5)),
            ("pl_smoke", "powerlaw", powerlaw(512, 4.0, seed=7)),
        ]
    else:
        graphs = [
            ("er_d4", "er", erdos_renyi(2048, 4.0, seed=5)),
            ("er_d16", "er", erdos_renyi(2048, 16.0, seed=5)),
            ("er_d48", "er", erdos_renyi(2048, 48.0, seed=5)),
            ("powerlaw_d6", "powerlaw", powerlaw(4096, 6.0, seed=5)),
        ]
    workloads = [
        bench_graph(name, kind, csr, args.max_iters)
        for name, kind, csr in graphs
    ]
    doc = {
        "meta": {
            "bench": "direction_opt",
            "schema_version": SCHEMA_VERSION,
            "smoke": bool(args.smoke),
            "alpha": BEAMER_ALPHA,
            "beta": BEAMER_BETA,
            "block": spec.block,
            "backend_list": list(BACKENDS),
            "jax": jax.__version__,
            "device": jax.default_backend(),
        },
        "workloads": workloads,
        "summary": summarize(workloads),
    }
    validate(doc)
    Path(args.out).write_text(json.dumps(doc, indent=1))
    s = doc["summary"]["dense_er"]
    pl = doc["summary"]["powerlaw_binned"]
    print(
        f"summary [{s['graph']}] large-frontier scan reduction: "
        f"dopt {s['scan_reduction_dopt_vs_push']}x, "
        f"pull {s['scan_reduction_pull_vs_push']}x vs ell_push "
        f"(passes_2x={s['passes_2x']})"
    )
    print(
        f"summary [{pl['graph']}] binned pull: "
        f"{pl['binned_overhead_vs_ideal']}x the ideal sum(deg) scan "
        f"(floor {BINNED_OVERHEAD_FLOOR}), "
        f"{pl['scan_reduction_binned_vs_ell_pull']}x fewer slots than the "
        f"padded reverse slab "
        f"(passes_overhead_floor={pl['passes_overhead_floor']})"
    )
    fk = doc["summary"]["fused_kernel"]
    print(
        f"summary [{fk['graph']}] fused kernel: wall "
        f"{fk['wall_ms_fused']} ms vs {fk['wall_ms_binned_jnp']} ms jnp "
        f"({fk['wall_ratio_fused_over_jnp']}x, tol {fk['wall_tolerance']}"
        f"{' interpret' if fk['interpret_mode'] else ''}), "
        f"passes_fused_wall_floor={fk['passes_fused_wall_floor']}"
    )
    print(f"wrote {args.out} (schema v{SCHEMA_VERSION} validated)")
    return 0 if (
        s["passes_2x"]
        and pl["passes_overhead_floor"]
        and fk["passes_fused_wall_floor"]
    ) else 1


if __name__ == "__main__":
    raise SystemExit(main())
