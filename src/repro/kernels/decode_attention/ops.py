"""Public wrapper for the decode_attention kernel: the block size, and the
per-slot walk that tells the kernel's index maps which block each grid
step reads. ``interpret`` is an explicit argument: the interpreter is a CPU
correctness harness, and the wrapper never falls back to it on its own."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .decode_attention import bounded_attention
from .ref import decode_attention_ref

# a K block in VMEM is at most this many bytes (K and V, double-buffered:
# four of them), and at most MAX_BLOCK positions
BLOCK_BYTES = 2 << 20
MAX_BLOCK = 256


def kv_block(S: int, row_bytes: int) -> int:
    """Positions per block for a cache of ``S`` positions whose K row (all
    KV heads of one position) is ``row_bytes``: the largest power of two
    up to ``MAX_BLOCK`` that divides ``S`` and keeps a block within
    ``BLOCK_BYTES``, or ``S`` when no power of two from 8 up divides it."""
    b = MAX_BLOCK
    while b > 8 and (S % b or b * row_bytes > BLOCK_BYTES):
        b //= 2
    return b if S % b == 0 else S


def rows_read(lens, block: int):
    """Cache positions the kernel reads for slot lengths ``lens``: each
    slot's length rounded up to whole blocks, none for a slot of length 0."""
    return -(-lens // block) * block


def block_walk(lens, block: int):
    """(slot, lo, hi) (B,) int32 for ``bounded_attention``'s index maps.

    A live slot reads its own blocks 0..last and then holds its last one.
    A slot of length 0 holds the block the step before it read (the last
    block of the live slot before it, or block 0 of the first live slot
    when none comes before), so the pipeline fetches nothing for it."""
    B = lens.shape[0]
    live = lens > 0
    last = jnp.maximum(-(-lens // block) - 1, 0)
    prev = jax.lax.cummax(jnp.where(live, jnp.arange(B), -1), axis=0)
    slot = jnp.where(prev >= 0, prev, jnp.argmax(live)).astype(jnp.int32)
    held = jnp.where(prev >= 0, last[jnp.maximum(prev, 0)], 0)
    lo = jnp.where(live, 0, held).astype(jnp.int32)
    hi = jnp.where(live, last, held).astype(jnp.int32)
    return slot, lo, hi


def bounded_decode_attention(q, k, v, layer, lens, *,
                             interpret: bool = False):
    """q (B, H_q, D); k, v the stacked cache (n_layers, B, S, H_kv, D);
    ``layer`` an int scalar; lens (B,) int -> (B, H_q, D).

    Slot b attends positions [0, min(lens[b], S)) of layer ``layer``; a
    slot of length 0 reads nothing and returns zeros."""
    S, H, D = k.shape[2:]
    block = kv_block(S, H * D * k.dtype.itemsize)
    lens = jnp.clip(lens, 0, S).astype(jnp.int32)
    slot, lo, hi = block_walk(lens, block)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    return bounded_attention(q, k, v, layer, lens, slot, lo, hi,
                             block=block, interpret=interpret)


__all__ = ["bounded_attention", "bounded_decode_attention", "block_walk",
           "decode_attention_ref", "kv_block", "rows_read"]
