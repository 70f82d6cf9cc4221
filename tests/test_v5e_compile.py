"""Compile rehearsals for a TPU v5e chip that is described, not attached.

The TPU compiler compiles for a topology described by
``topologies.get_topology_desc``; nothing runs, so these tests say nothing
about results or time. They catch what interpret mode and the CPU backend
cannot: a program that the chip's compiler refuses, or that does not fit
one chip's memory. The topology is described only inside a module fixture
(never at import), and every compile happens in the test's own process.

Shapes come from ``ldbc_proxy(1)`` (4,486 nodes, 134,935 edges) in the
operand layout the served path builds for the default ``recommend`` →
``dopt_binned`` backend.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import build_operands, policy_ntks
from repro.core.dispatcher import build_engine, strip_operands
from repro.core.extend import (
    BinnedPullBackend,
    ExtendCtx,
    PushBackend,
    as_spec,
)
from repro.graph.generators import ldbc_proxy
from repro.kernels.binned_pull.ops import binned_pull

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def mesh11(topo):
    return Mesh(
        np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )


@pytest.fixture(scope="module")
def served_operands(mesh11):
    """ShapeDtypeStructs of the dopt_binned operands, rows over "model"."""
    spec = as_spec("dopt_binned")
    ops, n_pad = build_operands(ldbc_proxy(1.0), spec)
    ops = strip_operands(spec, ops)
    sds = lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype,
        sharding=NamedSharding(mesh11, P("model", *(None,) * (x.ndim - 1))),
    )
    return spec, jax.tree.map(sds, ops), n_pad


def _fits_one_chip(compiled, arg_bytes):
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes >= arg_bytes
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM_BYTES


def _nbytes(tree):
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def test_served_engine_compiles_for_v5e(mesh11, served_operands):
    """The phase-1 nTkS ``sp_lengths`` engine the serving loop runs by
    default (sync="shard" with the online-adapt stats tap)."""
    spec, ops, n_pad = served_operands
    eng = build_engine(
        mesh11, policy_ntks(), "sp_lengths", n_pad, 8, sync="shard",
        extend=spec, operands=ops, collect_stats=True,
    )
    morsels = jax.ShapeDtypeStruct(
        (8, 1), jnp.int32, sharding=NamedSharding(mesh11, P("data", None))
    )
    compiled = eng.fn.lower(ops, morsels).compile()
    _fits_one_chip(compiled, _nbytes(ops))
    assert "while" in compiled.as_text()


@pytest.mark.parametrize(
    "backend, scanned",
    [(PushBackend, "fwd"), (BinnedPullBackend, "rev_binned")],
    ids=["push", "pull_binned"],
)
def test_extension_step_compiles_for_v5e(mesh11, served_operands, backend,
                                         scanned):
    """One jitted dense reach step of each direction the dopt switch picks
    between, on the served operands (jit drops the structure a direction
    does not scan from its arguments)."""
    _, ops, n_pad = served_operands
    vec = jax.ShapeDtypeStruct(
        (n_pad,), jnp.bool_, sharding=NamedSharding(mesh11, P())
    )
    ctx = ExtendCtx(n_out=n_pad, row_offset=0)
    step = jax.jit(lambda o, f, v: backend.reach_dense(o, f, v, ctx))
    compiled = step.lower(ops, vec, vec).compile()
    _fits_one_chip(compiled, _nbytes(getattr(ops, scanned)))


@pytest.mark.xfail(
    strict=True,
    raises=ValueError,
    reason="Mosaic refuses the fused binned-pull kernel: rank-1 row tiles "
    "off the 128/512-element tiling and a general VMEM gather",
)
def test_fused_binned_pull_lowers_for_v5e(mesh11):
    ops, n_pad = build_operands(ldbc_proxy(1.0), "pull_binned_fused")
    one = NamedSharding(mesh11, P())
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
    pack = jax.tree.map(sds, ops.rev_binned_pack)
    gsrc = jax.ShapeDtypeStruct((n_pad,), jnp.uint8, sharding=one)
    vloc = jax.ShapeDtypeStruct((pack.rows_local,), jnp.uint8, sharding=one)
    try:
        jax.jit(
            lambda p, g, v: binned_pull(p, g, v, op="reach", interpret=False)
        ).lower(pack, gsrc, vloc).compile()
    except ValueError as e:
        # the first refusal Mosaic meets; any other error fails the test
        assert "rank 1 block shapes" in str(e), e
        raise
