"""Logical-axis sharding rules with divisibility-aware fallback.

Model code tags tensors with *logical* axes ("batch", "heads", "ffn",
"eng_vocab", ...). A ``ShardCtx`` resolves them onto mesh axes. Axes that
don't exist in the mesh or don't divide the dimension are dropped
(replicated) — e.g. gemma3-1b's 4 heads over model=16 fall back gracefully.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# Default logical -> mesh axis mapping. Tuples shard over multiple axes.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch":     ("pod", "data"),
    "seq":       (),                 # sequence usually unsharded (SP variants override)
    "kv_seq":    (),                 # decode KV-sequence sharding (flash-decode) override
    "vocab":     ("model",),
    "embed":     (),
    "heads":     ("model",),
    "kv_heads":  ("model",),
    "ffn":       ("model",),
    "experts":   ("model",),
    "eng_vocab": ("pod", "data", "model"),   # the pooled Engram table: over everything
    "eng_emb":   ("model",),                 # fused-embedding dim (tp retrieval)
    "layers":    (),
    "lora":      (),
    "conv":      (),
    "state":     (),
    "opt":       ("data",),          # ZeRO-1 optimizer-state extra axis
}


@dataclasses.dataclass
class ShardCtx:
    mesh: jax.sharding.Mesh
    rules: dict[str, tuple[str, ...]]

    def resolve(self, logical: Optional[str]) -> tuple[str, ...]:
        if logical is None:
            return ()
        axes = self.rules.get(logical, ())
        if isinstance(axes, str):
            axes = (axes,)
        return tuple(a for a in axes if a in self.mesh.axis_names)

    def axis_prod(self, axes: tuple[str, ...]) -> int:
        return int(np.prod([self.mesh.shape[a] for a in axes], initial=1))

    def spec_for(self, shape: tuple[int, ...],
                 logical_axes: tuple[Optional[str], ...]) -> P:
        """PartitionSpec with divisibility fallback (drop axes until ok)."""
        entries, used = [], set()
        for dim, name in zip(shape, logical_axes):
            axes = tuple(a for a in self.resolve(name) if a not in used)
            while axes and dim % self.axis_prod(axes) != 0:
                axes = axes[:-1]          # drop innermost axis, retry
            if axes:
                used.update(axes)
                entries.append(axes if len(axes) > 1 else axes[0])
            else:
                entries.append(None)
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    def sharding_for(self, shape, logical_axes, memory_kind: Optional[str] = None):
        kw = {"memory_kind": memory_kind} if memory_kind else {}
        return NamedSharding(self.mesh, self.spec_for(shape, logical_axes), **kw)


_TLS = threading.local()


def current_ctx() -> Optional[ShardCtx]:
    return getattr(_TLS, "ctx", None)


@contextlib.contextmanager
def sharding_ctx(mesh: Optional[jax.sharding.Mesh],
                 rules: Optional[dict] = None):
    """Install a sharding context; model code then emits constraints."""
    prev = current_ctx()
    if mesh is None:
        _TLS.ctx = None
    else:
        merged = dict(DEFAULT_RULES)
        if rules:
            merged.update(rules)
        _TLS.ctx = ShardCtx(mesh, merged)
    try:
        yield _TLS.ctx
    finally:
        _TLS.ctx = prev


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Apply a sharding constraint by logical axes; no-op without a ctx."""
    ctx = current_ctx()
    if ctx is None:
        return x
    assert len(logical_axes) == x.ndim, (x.shape, logical_axes)
    spec = ctx.spec_for(x.shape, tuple(logical_axes))
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def axis_size(logical: str) -> int:
    """Product of mesh-axis sizes behind a logical axis (1 w/o ctx)."""
    ctx = current_ctx()
    if ctx is None:
        return 1
    return ctx.axis_prod(ctx.resolve(logical))


def mesh_axes(logical: str) -> tuple[str, ...]:
    ctx = current_ctx()
    if ctx is None:
        return ()
    return ctx.resolve(logical)


def params_shardings(defs_axes, abstract, memory_kinds=None):
    """Build a NamedSharding tree for a param tree.

    defs_axes: pytree of logical-axis tuples (from params.tree_axes)
    abstract:  matching ShapeDtypeStruct tree
    memory_kinds: optional pytree of memory-kind strings (or None)
    """
    ctx = current_ctx()
    assert ctx is not None

    def one(ax, ab, mk=None):
        return ctx.sharding_for(ab.shape, ax, memory_kind=mk)

    if memory_kinds is None:
        return jax.tree.map(one, defs_axes, abstract,
                            is_leaf=lambda x: isinstance(x, tuple) and all(
                                a is None or isinstance(a, str) for a in x))
    return jax.tree.map(one, defs_axes, abstract, memory_kinds,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            a is None or isinstance(a, str) for a in x))
