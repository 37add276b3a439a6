"""Pallas TPU kernel: length-bounded single-query decode attention.

Each slot's query attends only its own K/V rows ``[0, lens[b])`` of one
layer of the stacked decode cache ``(n_layers, B, S, H_kv, D)``, read in
place (the layer index is scalar-prefetched). The grid walks (slot, block
of ``block`` positions). The K/V index map clamps a slot's block index at
its last needed block, so the pipeline issues no DMA for the steps past
it, and ``pl.when`` skips their compute. A slot with ``lens == 0`` keeps
the block index of the step before it: nothing is read for it, and it
returns zeros.

A block of positions is ``(block, H_kv, D)`` in VMEM, viewed as
``(block * H_kv, D)`` rows. Every query head scores every row in one MXU
product and keeps the rows of its own KV head (a column mask), so any
group size ``H_q / H_kv`` needs no per-head slicing of the cache layout.
Scores, the running max and the softmax sum are float32; P is cast to V's
dtype for P·V, as the masked XLA form does.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _kernel(layer_ref, lens_ref, slot_ref, lo_ref, hi_ref,
            cols_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
            *, block: int, group: int, scale: float):
    del layer_ref, slot_ref, lo_ref, hi_ref      # read by the index maps
    b, j = pl.program_id(0), pl.program_id(1)
    n = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(j * block < n)
    def _step():
        P, H, D = k_ref.shape
        k = k_ref[...].reshape(P * H, D)
        v = v_ref[...].reshape(P * H, D)
        s = jnp.einsum("qd,nd->qn", q_ref[...], k,
                       preferred_element_type=jnp.float32) * scale
        # column c is row c // H of the block, KV head c % H: cols holds
        # (c % H) * group and c // H, so a query head r keeps the columns
        # whose KV head is r // group and whose position is below n
        head = cols_ref[0:1, :]
        pos = cols_ref[1:2, :] + j * block
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        keep = (row >= head) & (row < head + group) & (pos < n)
        s = jnp.where(keep, s, NEG_INF)

        m_cur = s.max(axis=-1)
        p = jnp.exp(s - m_cur[..., None])
        m_prev, l_prev = m_sc[...], l_sc[...]
        m_cur = jax.lax.broadcast_in_dim(m_cur, m_prev.shape, (0,))
        l_cur = jax.lax.broadcast_in_dim(p.sum(axis=-1), l_prev.shape, (0,))
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        beta = jnp.exp(m_cur - m_next)
        pv = jnp.einsum("qn,nd->qd", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        m_sc[...] = m_next
        l_sc[...] = alpha * l_prev + beta * l_cur
        acc_sc[...] = alpha * acc_sc[...] + beta * pv

    @pl.when(j == pl.num_programs(1) - 1)
    def _out():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def bounded_attention(q, k, v, layer, lens, slot, lo, hi, *, block: int,
                      interpret: bool = False):
    """q (B, H_q, D); k, v (n_layers, B, S, H_kv, D); layer (1,) int32;
    lens, slot, lo, hi (B,) int32 -> (B, H_q, D) in q's dtype.

    Grid step (b, j) reads block ``clip(j, lo[b], hi[b])`` of slot
    ``slot[b]``'s cache at ``layer`` and computes only while
    ``j * block < lens[b]`` (``ops.block_walk`` makes the vectors).
    ``interpret=True`` runs the body through the Pallas interpreter: a
    correctness harness for CPU tests, never a data path."""
    B, Hq, D = q.shape
    S, H = k.shape[2], k.shape[3]
    assert S % block == 0 and Hq % H == 0, (S, block, Hq, H)
    group = Hq // H
    c = np.arange(block * H)
    cols = jnp.asarray(np.stack([(c % H) * group, c // H]), jnp.int32)

    def kv_map(b, j, layer, lens, slot, lo, hi):
        return (layer[0], slot[b], jnp.minimum(jnp.maximum(j, lo[b]), hi[b]),
                0, 0)

    def row_map(b, j, *_):
        return (b, 0, 0)

    kv_spec = pl.BlockSpec((None, None, block, H, D), kv_map)
    return pl.pallas_call(
        functools.partial(_kernel, block=block, group=group,
                          scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, S // block),
            in_specs=[pl.BlockSpec((2, block * H), lambda b, j, *_: (0, 0)),
                      pl.BlockSpec((None, Hq, D), row_map),
                      kv_spec, kv_spec],
            out_specs=pl.BlockSpec((None, Hq, D), row_map),
            scratch_shapes=[pltpu.VMEM((Hq, D), jnp.float32)] * 3),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="bounded_decode_attention",
    )(layer, lens, slot, lo, hi, cols, q, k, v)
