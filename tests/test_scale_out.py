"""Scale-out regression suite (ISSUE 10): degree-chunked gathers stay
bitwise-exact on non-pow2 widths, the int32 node-id range is guarded,
the streamed per-shard operand build matches the wholesale build
bit-for-bit, and multi-device / multi-process ``prepare_graph`` places
identical shards (subprocess tests, marked ``slow``)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import repro.core.edge_compute as EC
import repro.core.extend as E
from repro.core import NO_PARENT, build_operands, operand_stream
from repro.graph.csr import CSRGraph, csr_from_edges
from repro.graph.partition import slab_edges


# ---------------------------------------------------------------------------
# satellite: _deg_chunk pow2 fix


def test_deg_chunk_returns_pow2():
    """The chunk must be a power of two (so it divides pow2-padded slab
    widths and chunk_fold's remainder tail handles the rest) — the old
    round-to-multiple-of-8 result tripped ``D % chunk`` asserts on
    non-pow2 refined-bucket widths."""
    # regression: budget 72 bytes / 1 per-slot -> 72 slots -> largest
    # pow2 is 64... capped by rows*width arithmetic: old code gave 24
    assert EC._deg_chunk(3, 1, 72) == 16
    for rows, width, budget in [(3, 1, 72), (100, 8, 4096), (7, 4, 999),
                                (1, 1, 3), (1000, 64, 2 << 20)]:
        c = EC._deg_chunk(rows, width, budget)
        assert c >= 1
        assert c & (c - 1) == 0, f"not a pow2: {c}"
        assert rows * c * width <= max(budget, rows * width)


def test_chunk_fold_covers_remainder_tail():
    """chunk_fold(D, chunk) with chunk ∤ D must still visit every column
    exactly once (full chunks + one static remainder tail)."""
    for D, chunk in [(24, 16), (305, 64), (7, 8), (16, 16), (129, 32),
                     (1, 1)]:
        x = jnp.arange(D, dtype=jnp.int32)

        def step(start, width, acc):
            return acc + lax.dynamic_slice_in_dim(x, start, width).sum()

        total = E.chunk_fold(D, chunk, step, jnp.int32(0))
        assert int(total) == D * (D - 1) // 2, (D, chunk)


# ---------------------------------------------------------------------------
# satellite: chunked gathers bitwise-identical on a huge-hub fixture


def _hub_graph(n=512, hub_deg=300, seed=3):
    rng = np.random.default_rng(seed)
    src = np.concatenate([
        rng.integers(0, n, 3 * n),
        np.arange(hub_deg) % (n - 1) + 1,
    ])
    dst = np.concatenate([
        rng.integers(0, n, 3 * n),
        np.zeros(hub_deg, np.int64),
    ])
    return csr_from_edges(n, src, dst)


@pytest.fixture
def tiny_budget(monkeypatch):
    """Force every _deg_chunk call site (extend + edge_compute) down to a
    tiny byte budget so even the fixture's modest slabs get chunked."""
    orig = EC._deg_chunk

    def forced(rows, width, budget=0):
        # small enough that even the 1-row hub slab (per_slot = L) gets
        # a chunk narrower than its ~300-col width
        return orig(rows, width, 1024)

    monkeypatch.setattr(EC, "_deg_chunk", forced)
    monkeypatch.setattr(E, "_deg_chunk", forced)
    return forced


def test_binned_slab_gathers_chunk_parity(tiny_budget):
    """The binned pull's scan, cut into many small steps by the forced
    budget, equals plain unchunked gathers over the same slabs."""
    csr = _hub_graph()
    ops, n_pad = build_operands(csr, extend="pull_binned")
    bn = ops.rev_binned
    L = 8
    rng = np.random.default_rng(5)
    gl = jnp.asarray((rng.random((n_pad, L)) < 0.3).astype(np.uint8))

    # the scan really takes many steps, more than the hub slab's width
    step = EC._deg_chunk(1, L)
    assert max(bn.widths) > step and bn.capacity_slots > 4 * step

    ctx = E.ExtendCtx(n_out=n_pad)
    got_reach = np.asarray(E.BinnedPullBackend._reach_lanes(
        ops, gl, None, ctx))
    got_par = np.asarray(E.BinnedPullBackend._min_parent_lanes(
        ops, gl, None, ctx))

    # oracle: plain unchunked gathers over the same slabs, un-permuted
    reach_parts, par_parts = [], []
    for s in bn.slabs:
        s = s[0]
        act = gl.at[s].get(mode="fill", fill_value=0)
        if s.shape[1] == 0:
            reach_parts.append(jnp.zeros((s.shape[0], L), gl.dtype))
            par_parts.append(jnp.full((s.shape[0], L), NO_PARENT, jnp.int32))
            continue
        reach_parts.append(act.max(axis=1))
        par_parts.append(jnp.where(act != 0, s[:, :, None], NO_PARENT)
                         .min(axis=1))
    inv = bn.inv[0]
    ref_reach = np.asarray(jnp.concatenate(reach_parts)[inv])
    ref_par = np.asarray(jnp.concatenate(par_parts)[inv])
    np.testing.assert_array_equal(got_reach, ref_reach)
    np.testing.assert_array_equal(got_par, ref_par)


def test_pull_and_topk_chunk_parity(tiny_budget):
    """The ELL pull gathers and the k-best relax stay bitwise-identical
    under forced chunking (non-pow2 forward widths -> remainder tail)."""
    csr = _hub_graph(n=256, hub_deg=150)
    w = np.random.default_rng(9).random(csr.n_edges).astype(np.float32)
    csr = CSRGraph(csr.indptr, csr.indices, weights=w)
    ops, n_pad = build_operands(csr, extend="ell_pull")
    rev = ops.rev
    L = 8
    rng = np.random.default_rng(5)
    gl = jnp.asarray((rng.random((n_pad, L)) < 0.3).astype(np.uint8))

    got_r = np.asarray(E._pull_gather_lanes(rev, gl))
    got_p = np.asarray(E._pull_min_parent_lanes(rev, gl))
    ref_r = np.asarray(
        gl.at[rev.indices].get(mode="fill", fill_value=0).max(axis=1)
    )
    act = gl.at[rev.indices].get(mode="fill", fill_value=0)
    ref_p = np.asarray(jnp.where(
        act != 0, rev.indices[:, :, None].astype(jnp.int32), NO_PARENT
    ).min(axis=1))
    np.testing.assert_array_equal(got_r, ref_r)
    np.testing.assert_array_equal(got_p, ref_p)

    k = 4
    gd = jnp.sort(
        jnp.asarray(rng.random((n_pad, k)).astype(np.float32)), axis=1
    )
    seed_row = jnp.full((rev.indices.shape[0],), jnp.inf)
    got_tk = np.asarray(EC.ell_min_topk(rev, gd, seed_row))
    wmat = rev.weights if rev.weights is not None else jnp.ones(
        rev.indices.shape, jnp.float32)
    cand = gd.at[rev.indices].get(
        mode="fill", fill_value=jnp.inf) + wmat[:, :, None]
    allc = jnp.concatenate(
        [cand.reshape(cand.shape[0], -1), seed_row[:, None]], axis=1
    )
    ref_tk = np.asarray(jnp.sort(allc, axis=1)[:, :k])
    np.testing.assert_array_equal(got_tk, ref_tk)


# ---------------------------------------------------------------------------
# satellite: int32 node-id overflow guards


def test_csr_from_edges_rejects_int32_overflow():
    with pytest.raises(ValueError, match="2\\*\\*31"):
        csr_from_edges(2**31, np.zeros(0, np.int64), np.zeros(0, np.int64))
    # guard fires before any O(n) allocation: a huge-but-valid count is
    # the caller's problem, one past the line is ours
    with pytest.raises(ValueError):
        csr_from_edges(2**31 + 5, np.array([0]), np.array([1]))


def test_edge_keys_rejects_int32_overflow():
    from unittest import mock

    csr = csr_from_edges(4, np.array([0, 1]), np.array([1, 2]))
    with mock.patch.object(
        CSRGraph, "n_nodes", property(lambda self: 2**31)
    ):
        with pytest.raises(ValueError, match="2\\*\\*31"):
            csr.edge_keys()


# ---------------------------------------------------------------------------
# satellite: slab_edges vectorized fill + edge-count balancing


def test_slab_edges_vectorized_fill_matches_naive():
    rng = np.random.default_rng(11)
    n, m, K = 96, 600, 4
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    fsrc, fdst, bounds = slab_edges(src, dst, n, K)
    assert bounds[0] == 0 and bounds[-1] == n
    assert np.all(np.diff(bounds) >= 0)
    # every (src, dst) edge appears exactly once across the slabs; pad
    # entries carry dst == n_nodes (dropped by segment reduces)
    valid = fdst < n
    got = sorted(zip(fsrc[valid].tolist(), fdst[valid].tolist()))
    assert got == sorted(zip(src.tolist(), dst.tolist()))
    # each kept edge sits in its destination's slab (arrays are flat
    # [K * width] in slab-major order)
    width = fdst.size // K
    k_of = np.searchsorted(bounds, fdst[valid], side="right") - 1
    slab_of = np.repeat(np.arange(K), width)[valid]
    assert np.array_equal(k_of, slab_of)


def test_slab_edges_edge_balance_tightens_width():
    """On a graph whose edges concentrate in one node-balance slab,
    edge-count balancing must shrink the padded payload (uniform node
    ranges pad every slab to the hot slab's count)."""
    rng = np.random.default_rng(13)
    n, K, m = 256, 4, 1000
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n // K, m)  # all edges land in node-slab 0
    nsrc, _, nb = slab_edges(src, dst, n, K, balance="nodes")
    bsrc, _, bb = slab_edges(src, dst, n, K, balance="edges")
    assert bb[0] == 0 and bb[-1] == n
    assert np.all(np.diff(bb) >= 0)

    def max_edges(bounds):
        k_of = np.clip(
            np.searchsorted(bounds, dst, side="right") - 1, 0, K - 1
        )
        return int(np.bincount(k_of, minlength=K).max())

    assert max_edges(nb) == m  # node balance: the hot slab holds all m
    assert max_edges(bb) < m  # edge balance actually splits it
    assert bsrc.size < nsrc.size  # ... so the padded payload shrinks


# ---------------------------------------------------------------------------
# tentpole: streamed per-shard build == wholesale build, bit for bit


@pytest.mark.parametrize("extend", ["ell_push", "ell_pull",
                                    "pull_binned_fused", "block_mxu"])
@pytest.mark.parametrize("weighted", [False, True])
def test_streamed_build_matches_wholesale(extend, weighted):
    from repro.graph.generators import powerlaw

    csr = powerlaw(600, 5.0, seed=21)
    if weighted:
        w = np.random.default_rng(4).random(csr.n_edges).astype(np.float32)
        csr = CSRGraph(csr.indptr, csr.indices, weights=w)
    shards, binned = 8, 4
    ref, n_pad_ref = build_operands(
        csr, extend=extend, shards=shards, binned_shards=binned
    )
    if ref.blocks is not None:
        # the streamed build emits blocks already folded to the policy
        # shard count, exactly like prepare_graph's regrouping of the
        # wholesale fine-shard build
        import dataclasses

        from repro.core.dispatcher import _regroup_block_rows

        sb = ref.blocks
        B = sb.block_size
        ref = dataclasses.replace(ref, blocks=dataclasses.replace(
            sb,
            blocks=sb.blocks.reshape(binned, -1, B, B),
            block_rows=_regroup_block_rows(sb, binned, n_pad_ref),
            block_cols=sb.block_cols.reshape(binned, -1),
        ))
    st = operand_stream(
        csr, extend=extend, shards=shards, binned_shards=binned
    )
    assert st.n_pad == n_pad_ref
    pieces = [st.build_shard(k) for k in range(st.k_shards)]
    assembled = st.assemble({
        key: np.concatenate([p[key] for p in pieces], axis=0)
        for key in pieces[0]
    })

    import jax

    ref_leaves = jax.tree_util.tree_flatten_with_path(ref)[0]
    got_leaves = jax.tree_util.tree_flatten_with_path(assembled)[0]
    assert [k for k, _ in got_leaves] == [k for k, _ in ref_leaves]
    for (kp, got), (_, want) in zip(got_leaves, ref_leaves):
        name = jax.tree_util.keystr(kp)
        got, want = np.asarray(got), np.asarray(want)
        assert got.shape == want.shape, (name, got.shape, want.shape)
        assert got.dtype == want.dtype, (name, got.dtype, want.dtype)
        np.testing.assert_array_equal(got, want, err_msg=name)


def test_operand_stream_key_set_stable_across_shards():
    from repro.graph.generators import powerlaw

    csr = powerlaw(300, 4.0, seed=2)
    st = operand_stream(csr, extend="pull_binned_fused", shards=4)
    keys = {k: set(st.build_shard(k)) for k in range(st.k_shards)}
    first = keys[0]
    assert all(v == first for v in keys.values())


# ---------------------------------------------------------------------------
# tentpole: device-placed streamed prepare_graph (subprocess, 8 devices)

_PLACED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.core.dispatcher import prepare_graph
from repro.core.policies import policy_ntks, policy_nt1s
from repro.graph.generators import powerlaw
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
csr = powerlaw(500, 5.0, seed=3)
for pol in (policy_ntks(), policy_nt1s()):
    for extend in ("ell_push", "pull_binned_fused", "block_mxu"):
        ref, n_ref = prepare_graph(csr, mesh, pol, pad_shards=mesh.size,
                                   extend=extend, stream=False)
        got, n_got = prepare_graph(csr, mesh, pol, pad_shards=mesh.size,
                                   extend=extend, stream=True)
        assert n_got == n_ref
        rl = jax.tree_util.tree_flatten_with_path(ref)[0]
        gl = jax.tree_util.tree_flatten_with_path(got)[0]
        assert [k for k, _ in gl] == [k for k, _ in rl]
        for (kp, g), (_, r) in zip(gl, rl):
            name = jax.tree_util.keystr(kp)
            assert g.shape == r.shape, (name, g.shape, r.shape)
            assert g.dtype == r.dtype, (name, g.dtype, r.dtype)
            assert g.sharding.is_equivalent_to(r.sharding, g.ndim), name
            assert (np.asarray(g) == np.asarray(r)).all(), name
print("placed-parity OK")
"""


@pytest.mark.slow
def test_prepare_graph_streamed_placement():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _PLACED],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "placed-parity OK" in out.stdout


# ---------------------------------------------------------------------------
# tentpole: multi-process placement — each process builds ONLY the
# shards its addressable devices own, and those shards match wholesale

_DIST = r"""
import os, sys
pid = int(sys.argv[1])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
try:
    jax.distributed.initialize(coordinator_address="127.0.0.1:%d",
                               num_processes=2, process_id=pid)
    assert jax.process_count() == 2
except Exception as e:  # container without distributed CPU support
    print("DIST-UNAVAILABLE", repr(e))
    sys.exit(0)

import repro.core.extend as E
from repro.core import operand_stream
from repro.core.dispatcher import prepare_graph
from repro.core.policies import policy_ntks
from repro.graph.generators import powerlaw
from repro.launch.mesh import make_mesh

mesh = make_mesh((1, 8), ("data", "model"))  # 8 shards, 4 per process
csr = powerlaw(400, 5.0, seed=6)

built = []
orig = E.OperandStream.build_shard
E.OperandStream.build_shard = (
    lambda self, k: (built.append(k), orig(self, k))[1]
)
ops, n_pad = prepare_graph(csr, mesh, policy_ntks(), pad_shards=mesh.size,
                           extend="pull_binned_fused")  # stream=None -> auto
E.OperandStream.build_shard = orig

# shard k lives on mesh column k; this process must have built exactly
# the shards whose column device is locally addressable — half of them
local_ids = {d.id for d in jax.local_devices()}
expected = sorted(
    k for k in range(8) if mesh.devices[0, k].id in local_ids
)
assert len(expected) == 4, expected
assert sorted(set(built)) == expected, (sorted(set(built)), expected)

# every addressable shard's bytes match the host-side reference build
st = operand_stream(csr, extend="pull_binned_fused", shards=mesh.size,
                    binned_shards=8)
refs = {k: st.build_shard(k) for k in set(built)}
flat = {}
for kp, leaf in jax.tree_util.tree_flatten_with_path(ops)[0]:
    flat[jax.tree_util.keystr(kp)] = leaf
names = {
    ".fwd.nbrs": "fwd.nbrs", ".fwd.rows": "fwd.rows",
    ".fwd.perm": "fwd.perm", ".fwd.inv": "fwd.inv",
    ".fwd.degrees": "fwd.degrees",
    ".rev_binned.perm": "bn.perm", ".rev_binned.inv": "bn.inv",
    ".rev_binned_pack.inv_pad": "pack.inv_pad",
}
checked = 0
for gname, sname in names.items():
    leaf = flat[gname]
    rl = leaf.shape[0] // 8
    for sh in leaf.addressable_shards:
        k = sh.index[0].start // rl if sh.index[0].start else 0
        assert (np.asarray(sh.data) == refs[k][sname]).all(), (gname, k)
        checked += 1
assert checked > 0
print(f"proc {pid}: local-shards-only OK ({sorted(set(built))})")
"""


@pytest.mark.slow
def test_prepare_graph_multiprocess_local_shards_only():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = _DIST % port
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=cwd,
        )
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            o, e = p.communicate(timeout=900)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process prepare_graph timed out")
        outs.append((p.returncode, o, e))
    for rc, o, e in outs:
        assert rc == 0, e[-4000:]
        if "DIST-UNAVAILABLE" in o:
            pytest.skip(f"jax.distributed unavailable: {o.strip()}")
    for pid, (rc, o, e) in enumerate(outs):
        assert f"proc {pid}: local-shards-only OK" in o, (o, e[-2000:])
