"""Production mesh construction and the repo's one ``make_mesh``.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state. The dry-run forces 512 host devices before first jax init;
real deployments get the same mesh over ICI-connected TPU chips.

Single-pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the pod axis
extends data parallelism across pods (DCN-crossing collectives are gradient
all-reduces only; frontier/TP collectives stay inside a pod).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto`` (the shard_map engines mix
    manual per-shard code with auto-sharded jit around it)."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names, axis_types=(AxisType.Auto,) * len(names)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes(multi_pod: bool = False):
    return ("pod", "data") if multi_pod else ("data",)


def all_axes(multi_pod: bool = False):
    return ("pod", "data", "model") if multi_pod else ("data", "model")
