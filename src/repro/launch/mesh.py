"""Production mesh definition.

Single pod: (data=16, model=16) — 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across a DCI.

A function, not a module constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS first; smoke tests
see 1 device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """Arbitrary mesh with Auto axis types (tests, examples)."""
    return _mesh(shape, axes)
