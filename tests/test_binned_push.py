"""The push over degree-binned forward slabs (``GraphOperands.fwd``).

Every push entry point against the plain numpy push of ``oracle.py`` on
seeded power-law and Erdős–Rényi graphs, over K = 1, 2 and 4 graph shards
(each shard's scatter run on its own rows, then merged as the engines'
collectives merge them), with zero-degree rows, a degree cap and weights;
the streamed build and a delta fold against the wholesale build; the
served path's choice of the binned direction switch for 64 lanes; and a
threshold refit that moves alpha/beta without compiling anything.

The scans that read only the live 128-slot chunks (``binned_scan`` given
``live``) against the scan that reads every slot, push and pull, bit for
bit; and the dispatcher's ``scanned_slots`` / ``dense_slots`` counters
against chunks counted here from the slot arrays.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oracle import bfs_levels, push_step

from repro.core import as_spec, build_operands, operand_stream
from repro.core.edge_compute import (
    NO_PARENT,
    binned_min_dist,
    binned_min_parent,
    binned_min_parent_lanes,
    binned_push_sum,
    binned_reach_dense,
    binned_reach_lanes,
    SCAN_SLOTS,
    binned_scan,
    rows_off_fill,
    unvisited_rows,
)
from repro.core.extend import effective_csr
from repro.core.policies import DirectionThresholds
from repro.graph.csr import SCAN_CHUNK, CSRGraph, csr_from_edges
from repro.graph.delta import diff_effective, fold_operands, random_delta, \
    apply_delta_csr
from repro.graph.generators import erdos_renyi, powerlaw
from repro.launch.mesh import make_mesh
from repro.runtime.dispatch import QueryDispatcher


def mesh11():
    return make_mesh((1, 1), ("data", "model"))


def _graph(kind: str, seed: int, weighted: bool) -> CSRGraph:
    """A seeded graph whose last 9 nodes have no edges at all;
    ``pl_large`` holds more slots than one scan step."""
    n = 8000 if kind == "pl_large" else 150
    g = (powerlaw(n - 9, 5.0, seed=seed) if kind.startswith("pl")
         else erdos_renyi(n - 9, 4.0, seed=seed))
    src, dst = g.edge_list()
    w = None
    if weighted:
        w = np.random.default_rng(seed).uniform(
            0.1, 2.0, len(src)).astype(np.float32)
    return csr_from_edges(n, src, dst, weights=w)


def _shards(ops, K):
    """Shard k's slice of every stacked forward leaf."""
    return [jax.tree.map(lambda x, k=k: x[k:k + 1], ops.fwd)
            for k in range(K)]


@pytest.mark.parametrize("kind", ["pl", "er"])
@pytest.mark.parametrize("K", [1, 2, 4])
@pytest.mark.parametrize("cap", [None, 4])
def test_binned_push_entry_points_match_plain_push(kind, K, cap):
    seed = {"pl": 3, "er": 5}[kind] + K
    csr = _graph(kind, seed, weighted=True)
    eff = effective_csr(csr, cap)
    ops, n_pad = build_operands(csr, "ell_push", max_deg=cap, shards=K)
    assert ops.fwd.inv.shape == (K, n_pad // K)
    rl = n_pad // K
    n = csr.n_nodes
    rng = np.random.default_rng(seed)
    frontier = np.zeros(n_pad, bool)
    frontier[:n] = rng.random(n) < 0.3
    lanes = np.zeros((n_pad, 8), np.uint8)
    lanes[:n] = rng.random((n, 8)) < 0.2
    dist = np.full(n_pad, np.inf, np.float32)
    dist[:n] = rng.uniform(0, 5, n).astype(np.float32)
    counts = np.zeros(n_pad, np.int32)
    counts[:n] = rng.integers(0, 7, n)
    mass = np.zeros(n_pad, np.float32)
    mass[:n] = rng.random(n).astype(np.float32)

    parts = {name: [] for name in ("reach", "lanes", "parent",
                                   "parent_lanes", "dist", "count", "ppr")}
    for k, bn in enumerate(_shards(ops, K)):
        off = jnp.int32(k * rl)
        lo, hi = k * rl, (k + 1) * rl
        parts["reach"].append(binned_reach_dense(
            bn, jnp.asarray(frontier), off, n_pad))
        parts["lanes"].append(binned_reach_lanes(
            bn, jnp.asarray(lanes), off, n_pad))
        # the sharded state layout: local rows, global ids from row_base
        parts["parent"].append(binned_min_parent(
            bn, jnp.asarray(frontier[lo:hi]), None, n_pad, off))
        parts["parent_lanes"].append(binned_min_parent_lanes(
            bn, jnp.asarray(lanes), off, n_pad))
        parts["dist"].append(binned_min_dist(
            bn, jnp.asarray(dist), jnp.asarray(frontier), off, n_pad))
        parts["count"].append(binned_push_sum(
            bn, jnp.asarray(counts), off, n_pad))
        parts["ppr"].append(binned_push_sum(
            bn, jnp.asarray(mass), off, n_pad, normalize=True))
    got = {name: [np.asarray(x) for x in xs] for name, xs in parts.items()}

    def merged(name, how):
        return how.reduce(np.stack(got[name]), axis=0)[:n]

    np.testing.assert_array_equal(
        merged("reach", np.logical_or), push_step(eff, "or", frontier[:n]))
    np.testing.assert_array_equal(
        merged("lanes", np.maximum), push_step(eff, "or", lanes[:n]))
    np.testing.assert_array_equal(
        merged("parent", np.minimum),
        push_step(eff, "min_parent", frontier[:n]))
    np.testing.assert_array_equal(
        merged("parent_lanes", np.minimum),
        push_step(eff, "min_parent", lanes[:n]))
    du = np.where(frontier[:n], dist[:n], np.inf).astype(np.float32)
    np.testing.assert_array_equal(
        merged("dist", np.minimum), push_step(eff, "min_dist", du))
    np.testing.assert_array_equal(
        merged("count", np.add), push_step(eff, "sum", counts[:n]))
    np.testing.assert_allclose(
        merged("ppr", np.add), push_step(eff, "sum", mass[:n],
                                         normalize=True), rtol=1e-6)
    # rows past the graph and zero-degree rows receive nothing
    assert not np.logical_or.reduce(np.stack(got["reach"]))[n:].any()
    assert (np.minimum.reduce(np.stack(got["parent"]))[n:]
            == int(NO_PARENT)).all()
    # every row's slab width stays within 1.1 of its degree
    assert ops.fwd.row_widths().sum() <= 1.1 * eff.n_edges + 1e-9


@functools.partial(jax.jit,
                   static_argnames=("reducer", "fill", "pull", "value"))
def _both_scans(bn, src, out, live, *, reducer, fill, pull=False,
                value=None):
    """The scan of every slot and the scan of the live chunks, in one
    program (compiled once a shape)."""
    kw = dict(pull=pull, value=value)
    return (binned_scan(bn, src, out, reducer, fill, **kw),
            binned_scan(bn, src, out, reducer, fill, live=live, **kw))


def _parent_of(got, nbr):
    return jnp.where(got != 0, nbr, NO_PARENT)


def _parent_of_lanes(got, nbr):
    return jnp.where(got != 0, nbr[:, None], NO_PARENT)


def _case_rows(bn, case: str, lanes: int):
    """``[rows_local, lanes]`` bool: the lanes in which each local row of
    ``bn`` (shard 0) is live for one frontier ``case``."""
    rl = bn.rows_local
    rows = np.zeros(rl, bool)
    if case in ("full", "one_lane_left"):
        rows[:] = True
    elif case == "first_half":
        # the rows the first half of the chunks touch
        perm = np.asarray(bn.perm[0])
        upto = perm[:int(bn.chunk_lo[0, bn.chunk_lo.shape[1] // 2])]
        rows[upto[upto < rl]] = True
    elif case == "last_chunk":
        # the last real row of the last chunk that touches one (a shard's
        # last chunks may hold only the slab-padding rows of K > 1)
        perm = np.asarray(bn.perm[0])
        for lo, hi in zip(np.asarray(bn.chunk_lo[0])[::-1],
                          np.asarray(bn.chunk_hi[0])[::-1]):
            real = perm[lo:hi + 1][perm[lo:hi + 1] < rl]
            if real.size:
                rows[real[-1]] = True
                break
    mask = np.ones((rl, lanes), bool)
    if case == "one_lane_left":
        mask = np.arange(lanes)[None, :] == (np.arange(rl) % lanes)[:, None]
    return rows[:, None] & mask


@pytest.mark.parametrize("kind, K, case", [
    (kind, K, case)
    for kind in ("pl", "er") for K in (1, 2)
    for case in ("empty", "full", "first_half", "last_chunk", "one_lane_left")
] + [("pl_large", 1, case) for case in ("full", "first_half", "last_chunk")])
def test_live_chunk_scan_equals_the_dense_scan(kind, K, case):
    """Push and pull, max, min and integer add, lanes and dense: the scan
    that reads only the chunks holding a live row equals the scan of
    every slot bit for bit (the pull after its visited suppression).
    ``one_lane_left``: every row is live in one lane only (pull: visited
    in every lane but one); ``pl_large`` takes several scan steps."""
    L = 8
    csr = _graph(kind, {"pl": 3, "er": 5, "pl_large": 9}[kind] + K,
                 weighted=False)
    ops, n_pad = build_operands(csr, "dopt_binned", shards=K)
    # the slabs end mid-chunk: the sentinel padding is in play
    assert ops.fwd.capacity_slots % SCAN_CHUNK
    assert ops.fwd.scan_slots % SCAN_CHUNK == 0
    if kind == "pl_large":
        assert ops.fwd.scan_slots > SCAN_SLOTS  # several steps
    rng = np.random.default_rng(K)
    ids = lambda k, rl: jnp.arange(rl, dtype=jnp.int32)[:, None] + k * rl

    def both(bn, src, out, reducer, fill, live, **kw):
        return tuple(np.asarray(x) for x in _both_scans(
            bn, src, out, live, reducer=reducer, fill=fill, **kw))

    checked = 0
    for k in range(K):
        fwd = jax.tree.map(lambda x: x[k:k + 1], ops.fwd)
        rev = jax.tree.map(lambda x: x[k:k + 1], ops.rev_binned)
        rl = fwd.rows_local
        on = _case_rows(fwd, case, L)
        vals = np.where(on, rng.integers(1, 7, on.shape), 0)
        pushes = []
        for lanes_src in (vals, vals[:, :1]):  # lanes, then one lane
            src = jnp.asarray(lanes_src.astype(np.int32))
            n_l = src.shape[1]
            cand = jnp.where(src != 0, ids(k, rl), NO_PARENT)
            for s, red, fill, out in (
                (src, "max", 0, jnp.zeros((n_pad, n_l), jnp.int32)),
                (cand, "min", int(NO_PARENT),
                 jnp.full((n_pad, n_l), NO_PARENT, jnp.int32)),
                (src, "add", 0, jnp.zeros((n_pad, n_l), jnp.int32)),
            ):
                if n_l == 1:  # the dense state's [rows] vectors
                    s, out = s[:, 0], out[:, 0]
                pushes.append(both(fwd, s, out, red, fill,
                                   rows_off_fill(s, fill)))
        on = _case_rows(rev, case, L)
        gl = jnp.asarray(rng.integers(0, 2, (n_pad, L)).astype(np.uint8))
        counts = jnp.asarray(rng.integers(0, 7, (n_pad, L)).astype(np.int32))
        pulls = []
        for vis in (np.where(on, 0, 1).astype(np.uint8), ~on[:, 0]):
            lanes = vis.ndim == 2
            shape = (rl, L) if lanes else (rl,)
            act, cnt = (gl, counts) if lanes else (gl[:, 0], counts[:, 0])
            vloc = jnp.asarray(vis)
            parent = _parent_of_lanes if lanes else _parent_of
            for src, red, fill, out, kw in (
                (act, "max", 0, jnp.zeros(shape, jnp.uint8), {}),
                (act, "min", 0, jnp.full(shape, NO_PARENT, jnp.int32),
                 {"value": parent}),
                (cnt, "add", 0, jnp.zeros(shape, jnp.int32), {}),
            ):
                dense, sparse = both(rev, src, out, red, fill,
                                     unvisited_rows(vloc), pull=True, **kw)
                seen = np.asarray(vloc != 0)
                pulls.append((np.where(seen, 0, dense),
                              np.where(seen, 0, sparse)))
        for dense, sparse in pushes + pulls:
            np.testing.assert_array_equal(sparse, dense)
            checked += 1
        if case == "full":  # the scans did real work
            assert any((d != 0).any() for d, _ in pushes[:1] + pulls[:1])
    assert checked == 12 * K


@pytest.mark.parametrize("direction", ["push", "pull", "isolated"])
def test_scanned_and_dense_slot_counters(direction):
    """``device_report()``'s ``scanned_slots`` is SCAN_CHUNK times the
    chunks that hold a live row, counted here from the slot arrays over
    every iteration of one query, and ``dense_slots`` is the padded slot
    count times the iterations. The thresholds pin the direction: never
    pull, or pull whenever the frontier has out-edges. A source with no
    edges pushes an empty frontier once: 0 slots read."""
    csr = _graph("pl", 7, weighted=False)
    n = csr.n_nodes
    pair = {"push": (0.0, 0.0), "pull": (1e9, 1e9),
            "isolated": (0.0, 0.0)}[direction]
    d = QueryDispatcher(
        mesh11(), csr, backend="dopt_binned", family="powerlaw",
        direction_thresholds=DirectionThresholds({}, pair),
    )
    src = n - 1 if direction == "isolated" else 0
    assert (csr.degrees[src] == 0) == (direction == "isolated")
    out = d.query(np.asarray([src], np.int32))
    levels = np.asarray(out.result.state.levels)[0]
    ops = d._graphs[next(iter(d._graphs))].ops
    bn = ops.rev_binned if direction == "pull" else ops.fwd
    rl = bn.rows_local
    slot_row = np.asarray(bn.rows[0])
    depth = int(levels[levels >= 0].max()) + 1  # iterations the query ran
    want = 0
    for it in range(depth):
        if direction == "pull":
            live = ~((levels >= 0) & (levels <= it))  # not yet visited
        else:
            live = levels == it  # the frontier
        hot = np.zeros(slot_row.shape, bool)
        real = slot_row < rl
        hot[real] = live[slot_row[real]]
        want += SCAN_CHUNK * int(hot.reshape(-1, SCAN_CHUNK).any(1).sum())
    dirs = d.device_report()["directions"]
    assert dirs[f"{'pull' if direction == 'pull' else 'push'}_iterations"] \
        == depth
    assert dirs["scanned_slots"] == want
    assert dirs["dense_slots"] == depth * bn.scan_slots
    if direction == "isolated":
        assert want == 0 and depth == 1
    else:
        assert 0 < want < dirs["dense_slots"]


@pytest.mark.parametrize("K", [1, 2, 4])
def test_streamed_forward_slabs_equal_the_wholesale_build(K):
    csr = _graph("pl", 11, weighted=True)
    ref, n_pad = build_operands(csr, "dopt_binned", shards=K)
    st = operand_stream(csr, "dopt_binned", shards=K)
    pieces = [st.build_shard(k) for k in range(K)]
    got = st.assemble({name: np.concatenate([p[name] for p in pieces])
                       for name in pieces[0]})
    assert st.n_pad == n_pad
    for a, b in zip(jax.tree.leaves(ref.fwd), jax.tree.leaves(got.fwd)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (jax.tree.structure(ref.fwd) == jax.tree.structure(got.fwd))


def _rows(bn, n):
    """Each global row's (neighbours, weights) as the slabs hold them."""
    K, rl = bn.inv.shape
    starts = np.cumsum([0] + [s.shape[1] for s in bn.slabs])
    out = {}
    for g in range(n):
        k, r = divmod(g, rl)
        p = int(bn.inv[k, r])
        b = int(np.searchsorted(starts, p, side="right")) - 1
        row = np.asarray(bn.slabs[b][k, p - starts[b]])
        d = int(bn.degrees[k, r])
        w = (None if bn.slab_weights is None
             else np.asarray(bn.slab_weights[b][k, p - starts[b]])[:d])
        out[g] = (row[:d].tolist(), None if w is None else w.tolist())
        assert (row[d:] == K * rl).all()
    return out


@pytest.mark.parametrize("seed", [1, 2])
def test_forward_fold_equals_rebuild(seed):
    csr = _graph("pl", seed, weighted=False)
    n = csr.n_nodes
    ops, n_pad = build_operands(csr, "ell_push", shards=2)
    host = jax.tree.map(lambda x: np.array(x), ops)
    delta = random_delta(csr, n_adds=40, n_dels=30, seed=seed)
    new = apply_delta_csr(csr, delta)
    old_eff, new_eff = effective_csr(csr, None), effective_csr(new, None)
    structs, rep = fold_operands(host, old_eff, new_eff,
                                 diff_effective(old_eff, new_eff, delta))
    assert rep.changed["fwd"]
    rebuilt, _ = build_operands(new, "ell_push", shards=2)
    assert _rows(structs["fwd"], n) == _rows(
        jax.tree.map(np.asarray, rebuilt.fwd), n)
    # the folded slabs push exactly what the rebuilt ones do
    front = jnp.asarray(np.arange(n_pad) % 3 == 0)
    for k in range(2):
        a = binned_reach_dense(
            jax.tree.map(lambda x: jnp.asarray(x[k:k + 1]), structs["fwd"]),
            front, jnp.int32(k * n_pad // 2), n_pad)
        b = binned_reach_dense(
            jax.tree.map(lambda x: x[k:k + 1], rebuilt.fwd),
            front, jnp.int32(k * n_pad // 2), n_pad)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_64_lanes_serve_through_the_binned_direction_switch():
    """``backend="recommend"`` sends 64-lane morsels on a graph sparse at
    MXU-tile granularity (avg degree x 128² < n) to ``dopt_binned``, and
    the levels equal the plain BFS."""
    n = 60000
    csr = powerlaw(n, 1.4, seed=4)
    assert csr.avg_degree * 128 * 128 < n
    d = QueryDispatcher(mesh11(), csr, family="powerlaw", refit_every=2)
    srcs = np.random.default_rng(4).integers(0, n, 64).astype(np.int32)
    out = d.query(srcs)
    assert out.policy == "ntkms"
    assert {k.extend for k in d.cache.keys()} == {as_spec("dopt_binned")}
    assert all(k.kind != "static" for k in d.cache.keys())
    levels = np.asarray(out.result.state.levels)[0, :n, :].T
    want = np.stack([bfs_levels(csr, [s]) for s in srcs])
    got = np.where(levels == 255, -1, levels.astype(np.int64))
    np.testing.assert_array_equal(got, want)
    dirs = d.device_report()["directions"]
    assert dirs["push_iterations"] + dirs["pull_iterations"] > 0
    assert dirs["push_slots"] == dirs["push_iterations"] * d._graphs[
        next(iter(d._graphs))].ops.fwd.slots


def test_refit_moves_thresholds_without_compiling():
    """A refit that moves alpha/beta serves the next batch under the new
    pair as the engine's operand: no compile, the direction switch's
    choices change, the levels do not."""
    csr = powerlaw(600, 4.0, seed=8)
    d = QueryDispatcher(mesh11(), csr, family="powerlaw", phase1_iters=64,
                        refit_every=10**6)
    srcs = np.asarray([3, 77, 150, 420], np.int32)

    def served():
        p0 = d.stats.pull_iterations
        c0 = d.cache.compile_events
        out = d.query(srcs)
        return (np.asarray(out.result.state.levels)[:4, :csr.n_nodes],
                d.stats.pull_iterations - p0, d.cache.compile_events - c0)

    before, pulls_before, _ = served()
    assert d.direction_pair() == (14.0, 24.0)
    fitted = d.refit_thresholds()
    assert fitted is not None and d.direction_pair() != (14.0, 24.0)
    after, pulls_after, compiles = served()
    assert compiles == 0
    assert pulls_after != pulls_before
    np.testing.assert_array_equal(after, before)
    np.testing.assert_array_equal(
        before, np.stack([bfs_levels(csr, [s]) for s in srcs]))
    # the pair is no part of any engine's key
    assert not any(hasattr(k.extend, "alpha") for k in d.cache.keys())


SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, sys.argv[1])
from oracle import bfs_levels
from repro.core import run_recursive_query, policy_ntks, policy_ntkms
from repro.graph.csr import CSRGraph, csr_from_edges
from repro.graph.generators import powerlaw
from repro.launch.mesh import make_mesh

g = powerlaw(230, 5.0, seed=21)
src, dst = g.edge_list()
w = np.random.default_rng(21).uniform(0.1, 2.0, len(src)).astype(np.float32)
csr = csr_from_edges(240, src, dst)  # the last 10 nodes have no edges
csr_w = csr_from_edges(240, src, dst, weights=w)
sources = np.asarray([0, 7, 99, 180, 235], np.int32)
cases = [("sp_parents", csr, policy_ntks(), "replicated"),
         ("sp_parents", csr, policy_ntks(), "sharded"),
         ("bellman_ford", csr_w, policy_ntks(), "replicated"),
         ("msbfs_parents", csr, policy_ntkms(), "replicated"),
         ("ppr", csr, policy_ntks(), "replicated"),
         ("pattern_counts", csr, policy_ntks(), "replicated")]
for ec, graph, pol, layout in cases:
    outs = []
    for k in (1, 2, 4):
        mesh = make_mesh((1, k), ("data", "model"))
        res = run_recursive_query(mesh, graph, sources, pol, ec,
                                  max_iters=64, state_layout=layout)
        outs.append(jax.tree.map(np.asarray, res.state))
    for k, out in zip((2, 4), outs[1:]):
        for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(out)):
            if ec == "ppr":
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
            else:
                np.testing.assert_array_equal(a, b, err_msg=f"{ec} K={k}")
    if ec == "sp_parents":
        want = np.stack([bfs_levels(csr, [s]) for s in sources])
        np.testing.assert_array_equal(outs[0].levels[:5, :240], want)
    print(ec, layout, "OK", flush=True)
print("ALL_SHARDED_OK")
"""


def test_binned_push_on_fake_devices():
    """Every push entry point through the engines on 1, 2 and 4 graph
    shards (fake CPU devices): the same states as one shard, and the
    levels of the plain BFS."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", SHARDED, here], capture_output=True,
        text=True, timeout=900, env=env,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ALL_SHARDED_OK" in r.stdout
