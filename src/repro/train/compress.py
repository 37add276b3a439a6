"""Int8-compressed gradient all-reduce (distributed-optimization trick).

At 1000+ nodes the gradient sync over the DP/pod axis (DCN) dominates the
step budget; compressing the wire payload f32 -> int8 cuts it 4x. The
JAX-native construction is a shard_map ring:

    quantize(g/n) -> all_to_all (int8 wire) -> widen+sum locally
    -> requantize chunk -> all_gather (int8 wire) -> dequantize

i.e. a reduce-scatter + all-gather decomposition of the all-reduce where
both wire passes carry int8. Per-tensor symmetric scales ride along as
tiny f32 side channels. Quantization error is bounded by max|g|/127 per
element and validated against the exact psum in tests.

Used by ``train/ddp.py`` (pure-DP outer loop) and available standalone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8. Returns (q int8, scale f32)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _compressed_allreduce_local(x: jax.Array, axis: str) -> jax.Array:
    """Inside shard_map: all-reduce ``x`` over ``axis`` with int8 wire."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    shape, dt = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    # mean contribution (divide before quant: keeps int8 range tight)
    q, scale = quantize(flat / n)
    chunks = q.reshape(n, -1)                                  # (n, m)
    # reduce-scatter pass: int8 wire
    recv = jax.lax.all_to_all(chunks, axis, split_axis=0, concat_axis=0,
                              tiled=False)                     # (n, m)
    scales = jax.lax.all_gather(scale, axis)                   # (n,) f32
    part = jnp.sum(recv.astype(jnp.float32) * scales[:, None], axis=0)  # (m,)
    # all-gather pass: requantize the reduced chunk, int8 wire
    q2, s2 = quantize(part)
    full_q = jax.lax.all_gather(q2, axis)                      # (n, m) int8
    full_s = jax.lax.all_gather(s2, axis)                      # (n,) f32
    out = (full_q.astype(jnp.float32) * full_s[:, None]).reshape(-1)
    if pad:
        out = out[:-pad]
    return (out * n).reshape(shape).astype(dt)                 # undo /n => sum


def compressed_psum(x: jax.Array, axis: str) -> jax.Array:
    """shard_map-internal API: int8-wire psum over ``axis``."""
    return _compressed_allreduce_local(x, axis)


def compressed_psum_tree(tree, axis: str):
    return jax.tree.map(lambda x: compressed_psum(x, axis), tree)


def compressed_pmean_tree(tree, axis: str):
    def one(x):
        n = jax.lax.axis_size(axis)
        return compressed_psum(x, axis) / n
    return jax.tree.map(one, tree)
