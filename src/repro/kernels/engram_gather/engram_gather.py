"""Pallas TPU kernel: high-concurrency Engram row gather.

TPU-native adaptation of the paper's wide-grid CUDA ``cxl2vram_copy``
(Listing 2): there, thousands of thread blocks each copy one embedding
segment so the GPU scheduler saturates PCIe. Here each grid step issues
``block_rows`` concurrent HBM->VMEM DMAs (row addresses come from the
scalar-prefetched index vector in SMEM), waits on all of them, and writes
one ``(block_rows, hd)`` output tile; the Pallas pipeline overlaps that
tile's write-back with the next step's DMAs.

Mosaic only slices a tiled HBM array on tile boundaries: the lane dim must
be a multiple of 128 and the row dim a multiple of the 8-row tile. So a
DMA moves the aligned ``(8, hd)`` tile that holds the wanted row (8x read
amplification: 4 KiB for a 320 B Engram segment lane-padded to 512 B),
and the row is picked out of VMEM through a float32 staging buffer —
dynamic sublane indexing is only supported on 32-bit data. bf16 -> f32
-> bf16 is exact, so the gather stays bit-identical to ``jnp.take``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 8        # rows per HBM tile: the DMA granularity
LANES = 128         # lane width: the table's minor dim must be a multiple


def _gather_kernel(idx_ref, table_hbm, out_ref, tiles, row_f32, stage, sem,
                   *, block_rows: int):
    base = pl.program_id(0) * block_rows

    def dma(r):
        g = idx_ref[base + r]
        start = pl.multiple_of((g // ROW_TILE) * ROW_TILE, ROW_TILE)
        return pltpu.make_async_copy(table_hbm.at[pl.ds(start, ROW_TILE)],
                                     tiles.at[r], sem)

    def issue(r, c):
        dma(r).start()
        return c

    def wait(r, c):
        dma(r).wait()
        return c

    def pick(r, c):
        row_f32[...] = tiles[r].astype(jnp.float32)
        sub = idx_ref[base + r] % ROW_TILE
        stage[pl.ds(r, 1), :] = row_f32[pl.ds(sub, 1), :]
        return c

    jax.lax.fori_loop(0, block_rows, issue, 0)
    jax.lax.fori_loop(0, block_rows, wait, 0)
    jax.lax.fori_loop(0, block_rows, pick, 0)
    out_ref[...] = stage[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def gather_rows(table: jax.Array, idx: jax.Array, *,
                interpret: bool = False, block_rows: int = 128) -> jax.Array:
    """out[i] = table[idx[i]].  table (..., R, hd) with hd % 128 == 0 and
    R % 8 == 0, its leading dims flattened into the row space (a free
    reshape inside this jit); idx (N,) int32 with N % block_rows == 0;
    out (N, hd).

    ``interpret=True`` runs the kernel body through the Pallas
    interpreter: a correctness harness for CPU tests, never a data path.
    """
    N = idx.shape[0]
    R, hd = table.shape[-2:]
    table = table.reshape(-1, hd)
    assert hd % LANES == 0 and R % ROW_TILE == 0, (table.shape,)
    assert N % block_rows == 0, (N, block_rows)
    return pl.pallas_call(
        functools.partial(_gather_kernel, block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // block_rows,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block_rows, hd),
                                   lambda i, idx_ref: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_rows, ROW_TILE, hd), table.dtype),
                pltpu.VMEM((ROW_TILE, hd), jnp.float32),
                pltpu.VMEM((block_rows, hd), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=jax.ShapeDtypeStruct((N, hd), table.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), table)
