"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256 style).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is an
outer data-parallel axis (gradients sync over DCN) and an extra shard axis
for the pooled Engram table.

Functions, not module constants: importing this module never touches jax
device state.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests / examples)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def single_device_mesh() -> jax.sharding.Mesh:
    return make_mesh((1, 1), ("data", "model"))
