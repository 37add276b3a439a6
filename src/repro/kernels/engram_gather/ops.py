"""Jit'd public wrappers for the engram_gather kernel.

Handle lane padding (hd -> multiple of 128) for unpadded test tables,
row-count padding, and multi-table flattening. ``interpret`` is an explicit
argument everywhere: the interpreter is a CPU correctness harness, and no
wrapper falls back to it on its own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .engram_gather import LANES, gather_rows
from .ref import engram_gather_ref


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def engram_gather(tables: jax.Array, idx: jax.Array, *,
                  interpret: bool = False,
                  block_rows: int = 128) -> jax.Array:
    """tables (T, V, hd); idx (..., T) int32 -> rows (..., T, hd).

    Flattens the T sub-tables into one (T*V, hd) row space so a single
    kernel launch covers every hash head (maximum in-flight concurrency,
    mirroring the paper's single fused wide-grid launch). An unpadded
    ``hd`` is lane-padded here, which copies the tables on every call:
    served tables are stored lane-padded (``EngramConfig.table_lanes``).
    """
    T, V, hd = tables.shape
    batch_shape = idx.shape[:-1]
    flat = tables.reshape(T * V, hd)
    hd_p = _pad_to(hd, LANES)
    if hd_p != hd:
        flat = jnp.pad(flat, ((0, 0), (0, hd_p - hd)))
    # global row ids: table t row r -> t*V + r
    gid = (idx + (jnp.arange(T, dtype=idx.dtype) * V)).reshape(-1)
    N = gid.shape[0]
    gid = jnp.pad(gid, (0, _pad_to(N, block_rows) - N))
    rows = gather_rows(flat, gid, interpret=interpret, block_rows=block_rows)
    return rows[:N, :hd].reshape(*batch_shape, T, hd)


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


def gather_rows_padded(tables: jax.Array, gid, *, width: int | None = None,
                       interpret: bool = False,
                       block_rows: int = 128) -> jax.Array:
    """Variable-count row gather through the Pallas kernel.

    Cache-miss gathers (pool/store.py) produce an *arbitrary* number of
    rows per wave. This wrapper pads the host index vector to the next
    power-of-two bucket (bounding compiles to O(log N) shapes as the miss
    count wanders), runs the kernel, and slices the real rows and the
    first ``width`` lanes back out.

    tables (..., R, hd) lane-aligned, flattened over the leading dims
    inside the kernel's jit (free: no copy of the table); gid (N,) host
    ints, N >= 0 -> (N, width or hd).
    """
    gid = np.asarray(gid, np.int32).reshape(-1)
    N = gid.shape[0]
    width = tables.shape[-1] if width is None else width
    if N == 0:
        return jnp.zeros((0, width), tables.dtype)
    n_p = _pad_to(_next_pow2(N), block_rows)
    gid = np.pad(gid, (0, n_p - N))           # pad rows re-read row 0: cheap
    rows = gather_rows(tables, jnp.asarray(gid), interpret=interpret,
                       block_rows=block_rows)
    return rows[:N, :width]


__all__ = ["engram_gather", "engram_gather_ref", "gather_rows",
           "gather_rows_padded"]
