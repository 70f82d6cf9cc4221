"""Multi-device (8 fake CPU devices) parity for collectives + policies.

Runs in a subprocess because XLA device count is locked at first jax init —
the main test process must keep seeing exactly 1 device.
"""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from functools import partial

import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P

shard_map = partial(jax.shard_map, check_vma=False)
from repro.core.collectives import or_allreduce, ring_or_u32
from repro.core import (run_recursive_query, policy_1t1s, policy_nt1s,
                        policy_ntks, policy_ntkms)
from repro.graph.generators import powerlaw
from repro.launch.mesh import make_mesh
import collections

def bfs_levels(csr, sources):
    levels = np.full(csr.n_nodes, -1, dtype=np.int32)
    q = collections.deque()
    for s in np.atleast_1d(sources):
        s = int(s)
        if levels[s] < 0:
            levels[s] = 0; q.append(s)
    while q:
        u = q.popleft()
        for v in csr.neighbors(u):
            if levels[int(v)] < 0:
                levels[int(v)] = levels[u] + 1; q.append(int(v))
    return levels

mesh = make_mesh((2, 4), ("data", "model"))

# --- collective parity: every or_allreduce impl must agree -----------------
rng = np.random.default_rng(0)
x = (rng.random((8, 1000)) < 0.2)
def run(impl):
    def f(xs):
        return or_allreduce(xs[0], ("data", "model"), impl)[None]
    sm = shard_map(f, mesh=mesh, in_specs=P(("data","model"), None),
                   out_specs=P(("data","model"), None))
    return np.asarray(jax.jit(sm)(jnp.asarray(x)))
ref = np.broadcast_to(x.any(axis=0), (8, 1000))
for impl in ("pmax", "allgather", "ring"):
    got = run(impl) != 0
    assert (got == ref).all(), f"or_allreduce[{impl}] mismatch"
print("collectives OK")

# --- ring on uint32 over one axis ------------------------------------------
xu = rng.integers(0, 2**32, size=(8, 37), dtype=np.uint32)
def fu(xs):
    return ring_or_u32(xs[0], "model")[None]
sm = shard_map(fu, mesh=mesh, in_specs=P(("data","model"), None),
               out_specs=P(("data","model"), None))
got = np.asarray(jax.jit(sm)(jnp.asarray(xu)))
expect = np.zeros_like(xu)
for d in range(2):
    grp = xu[d*4:(d+1)*4]
    r = np.bitwise_or.reduce(grp, axis=0)
    expect[d*4:(d+1)*4] = r
assert (got == expect).all(), "ring_or_u32 mismatch"
print("ring_or_u32 OK")

# --- policy parity on a real 2x4 mesh ---------------------------------------
csr = powerlaw(300, 5.0, seed=1)
sources = np.array([0, 3, 17, 44, 123, 200, 250, 280, 5, 9], dtype=np.int32)
expected = np.stack([bfs_levels(csr, [s]) for s in sources])
for pol in (policy_1t1s(), policy_nt1s(or_impl="ring"),
            policy_ntks(or_impl="allgather"), policy_ntks(or_impl="ring"),
            policy_ntks(or_impl="pmax")):
    res = run_recursive_query(mesh, csr, sources, pol, "sp_lengths")
    got = np.asarray(res.state.levels)[: len(sources), : csr.n_nodes]
    assert (got == expected).all(), f"policy {pol.name}/{pol.or_impl} mismatch"
print("policies OK")

# --- extension-backend parity on a real 2x4 mesh ----------------------------
# pull's inverse communication (global-frontier union) + the dopt lax.cond
# with psum'd predicate must agree with push under real collectives, in
# BOTH state layouts; pull_binned additionally exercises the multi-shard
# per-shard binning (4 graph shards here => stacked [K,...] slab operands)
for layout in ("replicated", "sharded"):
    for be in ("ell_pull", "pull_binned", "dopt", "dopt_ell", "block_mxu"):
        res = run_recursive_query(mesh, csr, sources, policy_ntks(),
                                  "sp_lengths", state_layout=layout,
                                  extend=be)
        got = np.asarray(res.state.levels)[: len(sources), : csr.n_nodes]
        assert (got == expected).all(), f"backend {be}/{layout} mismatch"
print("backends OK")

# nTkMS on multi-device with 70 sources -> 2 morsels over data axis
srcs70 = np.arange(70, dtype=np.int32) * 4 % csr.n_nodes
res = run_recursive_query(mesh, csr, srcs70, policy_ntkms(or_impl="ring"),
                          "msbfs_lengths")
lanes = np.asarray(res.state.levels)  # [2, n_pad, 64]
for i, s in enumerate(srcs70):
    m, l = divmod(i, 64)
    got = lanes[m, : csr.n_nodes, l].astype(np.int32)
    got[got == 255] = -1
    exp = bfs_levels(csr, [s])
    assert (got == exp).all(), f"ntkms lane {i} mismatch"
print("ntkms OK")

# Bellman-Ford merge=min across shards
res = run_recursive_query(mesh, csr, np.array([7], np.int32),
                          policy_ntks(), "bellman_ford")
dist = np.asarray(res.state.dist)[0, : csr.n_nodes]
lv = bfs_levels(csr, [7]).astype(np.float64)
lv[lv < 0] = np.inf
assert np.allclose(dist, lv), "bellman-ford (unit weights) != bfs levels"
print("bellman OK")

# --- gang-scheduled phase-2 resume on a real 2x4 mesh (ISSUE 4) -------------
# skewed workload: small-diameter powerlaw component + 3 long-path straggler
# components; with a tiny pinned phase-1 budget the path-head morsels survive
# on different source shards and must be ganged into ONE multi-frontier
# re-dispatch over all 8 devices — in BOTH state layouts the final state must
# bit-match the replicated reference and the oracle (the sharded phase 2
# exercises gang_handoff + the OR reduce-scatter merge across (data, model)).
from repro.graph.csr import csr_from_edges
from repro.runtime.scheduler import AdaptiveScheduler

pl = powerlaw(200, 5.0, seed=2)
src_pl, dst_pl = pl.edge_list()
srcs_e, dsts_e, base, heads = [src_pl], [dst_pl], 200, []
for L in (40, 28, 22):
    p = np.arange(L - 1, dtype=np.int64) + base
    srcs_e += [p, p + 1]; dsts_e += [p + 1, p]
    heads.append(base); base += L
skew = csr_from_edges(base, np.concatenate(srcs_e), np.concatenate(dsts_e))
gsrcs = np.array(heads + [3, 9, 17], dtype=np.int32)
expected_g = np.stack([bfs_levels(skew, [int(s)]) for s in gsrcs])

ref_levels = None
for layout in ("replicated", "sharded"):
    sched = AdaptiveScheduler(mesh, skew, max_iters=64, phase1_iters=2)
    out = sched.query(gsrcs, state_layout=layout)
    assert out.hybrid and out.resumed_ganged >= 3, (layout, out)
    assert out.gang_width >= out.resumed_ganged, (layout, out)
    assert out.resumed_serial == 0, (layout, out)
    got = np.asarray(out.result.state.levels)[: len(gsrcs), : skew.n_nodes]
    assert (got == expected_g).all(), f"gang {layout} != oracle"
    if ref_levels is None:
        ref_levels = np.asarray(out.result.state.levels)
    else:
        assert (np.asarray(out.result.state.levels) == ref_levels).all(), \
            "sharded gang != replicated gang"
    # serial per-morsel baseline must agree bit-for-bit (replicated only:
    # the sharded phase 2 IS the gang engine)
    if layout == "replicated":
        serial = AdaptiveScheduler(mesh, skew, max_iters=64, phase1_iters=2,
                                   gang_resume=False)
        sout = serial.query(gsrcs)
        assert sout.resumed_serial == out.resumed_ganged, (sout, out)
        assert (np.asarray(sout.result.state.levels) == ref_levels).all(), \
            "serial resume != gang resume"
print("gang OK")

# --- divergent-trip sharded phase 1 (ISSUE 9 deadlock regression) -----------
# sync="shard" lets source-shard groups exit the phase-1 while_loop at
# different trip counts. psum/pmin/all_gather rendezvous per replica group,
# so that divergence is safe — but the min/sum reduce-scatter merges of the
# sharded new-kind engines used ppermute rings, and a ring lowers to ONE
# CollectivePermute whose rendezvous spans every device: the group still
# iterating deadlocked forever once the other group exited. Budget 14 sits
# between this graph's group convergence depths (13 vs 15), so one group
# exits early while the other survives into the gang phase 2 — the exact
# pre-fix hang shape. A deadlock here trips the faulthandler exit below
# rather than the outer 900 s subprocess timeout.
import faulthandler
faulthandler.dump_traceback_later(300, exit=True)
from repro.runtime.dispatch import QueryDispatcher

rngq = np.random.default_rng(3)
nq, mq = 300, 1800
wq = rngq.uniform(0.1, 2.0, mq).astype(np.float32)
csrq = csr_from_edges(
    nq, rngq.integers(0, nq, mq), rngq.integers(0, nq, mq), weights=wq
)
srcsq = np.array([0, 3, 17, 44], dtype=np.int32)
# per-kind budgets straddle this graph's source-group convergence depths:
# topk converges at [12,13 | 15,12] trips per group, ppr at [46,41 | 42,50]
for kind, leaf, budget in (("topk_paths", "dists", 14), ("ppr", "mass", 48)):
    dq = QueryDispatcher(mesh, csrq, max_iters=512, phase1_iters=budget)
    refq = None
    for lay in ("replicated", "sharded"):
        out = dq.query(srcsq, query_kind=kind, state_layout=lay)
        assert out.hybrid and out.redispatched >= 1, (kind, lay, out)
        got = np.asarray(getattr(out.result.state, leaf))[:, :nq]
        its = np.asarray(out.result.iterations)
        if refq is None:
            refq, ref_its = got, its
        else:
            assert (its == ref_its).all(), (kind, its, ref_its)
            if kind == "ppr":
                np.testing.assert_allclose(got, refq, rtol=1e-6, atol=1e-9)
            else:
                assert (got == refq).all(), f"{kind} sharded != replicated"
faulthandler.cancel_dump_traceback_later()
print("divergent-shard OK")
print("ALL_MULTIDEV_OK")
"""


import pytest


@pytest.mark.slow
def test_multidev_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "ALL_MULTIDEV_OK" in r.stdout
