"""Production mesh construction.

Single pod: (16, 16) over ("data", "model") — 256 chips (TPU v5e pod).
Multi-pod: (2, 16, 16) over ("pod", "data", "model") — 512 chips; the `pod`
axis is pure data parallelism (serving replicas / gradient all-reduce), so
elastic scaling adds or removes pods without resharding the model axes.

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialisation).
Scope a mesh for jit'd computations with ``jax.set_mesh(mesh)``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU multi-device tests (8 host devices)."""
    return _make_mesh((data, model), ("data", "model"))
