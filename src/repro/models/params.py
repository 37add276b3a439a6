"""Parameter definition trees.

A model is described by a pytree of ``ParamDef`` leaves (shape, dtype,
logical axes, init scale). From one def-tree we derive:
  * abstract params (ShapeDtypeStruct) — for dry-run lowering,
  * shardings (via sharding/rules.py mapping logical axes -> mesh axes),
  * materialized params (deterministic per-path seeded init).
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    dtype: str = "float32"
    # logical axis names, len == ndim; None entries are unsharded
    axes: tuple[Optional[str], ...] = ()
    init: str = "normal"        # normal | zeros | ones | eye_like
    scale: float = -1.0         # -1 => 1/sqrt(fan_in)

    def __post_init__(self):
        if self.axes == ():
            object.__setattr__(self, "axes", (None,) * len(self.shape))
        assert len(self.axes) == len(self.shape), (self.shape, self.axes)

    @property
    def fan_in(self) -> int:
        return self.shape[0] if len(self.shape) >= 1 else 1


def pd(*shape, axes=(), dtype="float32", init="normal", scale=-1.0) -> ParamDef:
    return ParamDef(tuple(shape), dtype, tuple(axes) if axes else (), init, scale)


def is_def(x: Any) -> bool:
    return isinstance(x, ParamDef)


def tree_abstract(defs) -> Any:
    """Def tree -> ShapeDtypeStruct tree (no allocation)."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype)),
        defs, is_leaf=is_def)


def tree_axes(defs) -> Any:
    return jax.tree.map(lambda d: d.axes, defs, is_leaf=is_def)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


@functools.partial(jax.jit, static_argnums=0)
def _init_leaf(d: ParamDef, key) -> jax.Array:
    """One fused program per leaf: eager ops would hold the f32 draw and
    its scaled copy at once, twice the leaf in f32 on the device."""
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    scale = d.scale if d.scale >= 0 else 1.0 / np.sqrt(max(d.fan_in, 1))
    if d.init == "normal":
        return (jax.random.normal(key, d.shape, jnp.float32) * scale).astype(d.dtype)
    raise ValueError(d.init)


def tree_init(defs, seed: int = 0) -> Any:
    """Materialize params; per-leaf key derived from tree path (stable).

    The path hash must be stable *across processes* (Python's ``hash`` on
    strings is salted per interpreter): serving replicas built in separate
    processes, CI smoke runs, and cached-vs-fresh comparisons all assume
    ``tree_init(defs, seed)`` is one function of its arguments."""
    base = jax.random.PRNGKey(seed)

    def init_one(path, d):
        h = np.uint32(zlib.crc32(_path_str(path).encode()) % (2**31))
        return _init_leaf(d, jax.random.fold_in(base, h))

    return jax.tree_util.tree_map_with_path(init_one, defs, is_leaf=is_def)


def tree_stack_defs(defs_list) -> Any:
    """Stack N structurally-identical def trees along a new leading axis
    (logical axis name 'layers')."""
    n = len(defs_list)

    def stack(*ds):
        d0 = ds[0]
        assert all(d.shape == d0.shape and d.dtype == d0.dtype for d in ds)
        return ParamDef((n,) + d0.shape, d0.dtype, ("layers",) + d0.axes,
                        d0.init, d0.scale)

    return jax.tree.map(stack, *defs_list, is_leaf=is_def)


def count_params(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_def)
    return int(sum(np.prod(d.shape) for d in leaves))
