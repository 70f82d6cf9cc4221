"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships as <name>/{<name>.py, ops.py, ref.py}: the pallas_call with
explicit BlockSpec VMEM tiling, the jit'd wrapper, and the pure-jnp oracle.
Tests run them in interpret mode on the CPU; on a TPU backend
``interpret=None`` compiles them with Mosaic. All wrappers share that
auto-detect convention via ``common.default_interpret``. ``binned_pull``
does not lower for TPU yet and raises ``FusedPullNotLowerable`` there.

- msbfs_extend   : MS-BFS frontier extension (paper hot loop, MXU int8)
- block_spmm     : block-sparse SpMM (GNN message passing)
- flash_attention: causal online-softmax attention (LM prefill/train)
- binned_pull    : fused slab-major degree-binned pull extension
                   (bottom-up hot loop behind ``pull_binned_fused``)
"""
from .common import default_interpret
from .binned_pull.ops import (
    BinnedPullPack,
    binned_pull,
    build_pack,
    pack_plan,
    pack_tile_map,
)

__all__ = [
    "default_interpret",
    "BinnedPullPack",
    "binned_pull",
    "build_pack",
    "pack_plan",
    "pack_tile_map",
]
