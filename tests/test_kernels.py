"""Per-kernel allclose vs the pure-jnp oracle (ref.py), interpret=True,
with shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import (block_walk,
                                               bounded_decode_attention,
                                               kv_block)
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.engram_gather.ops import engram_gather
from repro.kernels.engram_gather.ref import engram_gather_ref
from repro.kernels.engram_gather.engram_gather import gather_rows
from repro.kernels.gated_fuse.ops import engram_gated_fuse
from repro.kernels.gated_fuse.ref import gated_fuse_ref


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("T,V,hd,B,S", [
    (2, 64, 16, 2, 4),        # tiny, unaligned hd
    (4, 128, 128, 1, 8),      # lane-aligned hd
    (16, 512, 160, 2, 3),     # Engram-27B head shape (160 dims)
    (1, 32, 8, 1, 1),         # single row
])
def test_engram_gather_matches_ref(T, V, hd, B, S, dtype):
    rng = np.random.RandomState(hash((T, V, hd)) % 2**31)
    tables = jnp.asarray(rng.randn(T, V, hd), jnp.dtype(dtype))
    idx = jnp.asarray(rng.randint(0, V, (B, S, T)), jnp.int32)
    out = engram_gather(tables, idx, interpret=True)
    ref = engram_gather_ref(tables, idx)
    assert out.shape == ref.shape == (B, S, T, hd)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=0, atol=0)


@pytest.mark.parametrize("block_rows", [1, 4, 8])
def test_gather_rows_block_sweep(block_rows):
    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.randn(256, 128), jnp.float32)
    N = 32
    idx = jnp.asarray(rng.randint(0, 256, (N,)), jnp.int32)
    out = gather_rows(table, idx, interpret=True, block_rows=block_rows)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(table)[np.asarray(idx)])


def test_engram_gather_extreme_indices():
    """First/last rows and repeated indices."""
    table = jnp.arange(64 * 128, dtype=jnp.float32).reshape(1, 64, 128)
    idx = jnp.asarray([[[0], [63], [0], [63]]], jnp.int32).reshape(1, 4, 1)
    out = np.asarray(engram_gather(table, idx, interpret=True))
    np.testing.assert_array_equal(out[0, 0, 0], np.asarray(table)[0, 0])
    np.testing.assert_array_equal(out[0, 1, 0], np.asarray(table)[0, 63])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("B,S,d,de", [
    (2, 4, 32, 64),
    (1, 8, 128, 256),
    (2, 3, 96, 160),          # unaligned dims
])
def test_gated_fuse_matches_ref(B, S, d, de, dtype):
    rng = np.random.RandomState(hash((B, S, d, de)) % 2**31)
    dt = jnp.dtype(dtype)
    h = jnp.asarray(rng.randn(B, S, d), dt)
    rows = jnp.asarray(rng.randn(B, S, de), dt)
    w_gate = jnp.asarray(rng.randn(d, d) / np.sqrt(d), dt)
    w_proj = jnp.asarray(rng.randn(de, d) / np.sqrt(de), dt)
    out = engram_gated_fuse(h, rows, w_gate, w_proj, interpret=True)
    ref = gated_fuse_ref(h, rows, w_gate, w_proj)
    tol = 1e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_gated_fuse_zero_update_identity():
    rng = np.random.RandomState(1)
    h = jnp.asarray(rng.randn(2, 4, 64), jnp.float32)
    rows = jnp.zeros((2, 4, 96), jnp.float32)
    w_gate = jnp.asarray(rng.randn(64, 64), jnp.float32)
    w_proj = jnp.asarray(rng.randn(96, 64), jnp.float32)
    out = engram_gated_fuse(h, rows, w_gate, w_proj, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(h), atol=1e-6)


# decode attention: 48 positions make 16-position blocks (48 is no multiple
# of 32), so the lengths below are 0, 1, one block, one past a block, the
# whole cache, and two more, live and dead slots side by side
DECODE_S = 48
DECODE_LENS = [0, 1, 16, 17, DECODE_S, 0, 33, 5]


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (14, 2)])   # groups 1 and 7
def test_decode_attention_matches_masked_form(hq, hkv, layer):
    """The length-bounded kernel against the masked XLA form on a stacked
    bf16 cache: live slots agree within bf16 rounding of P, and a slot of
    length 0 returns finite zeros."""
    n, B, D = 3, len(DECODE_LENS), 128
    rng = np.random.RandomState(hq * 10 + layer)
    q = jnp.asarray(rng.randn(B, hq, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(n, B, DECODE_S, hkv, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(n, B, DECODE_S, hkv, D), jnp.bfloat16)
    lens = jnp.asarray(DECODE_LENS, jnp.int32)
    assert kv_block(DECODE_S, hkv * D * 2) == 16
    out = np.asarray(bounded_decode_attention(q, k, v, layer, lens,
                                              interpret=True), np.float32)
    ref = np.asarray(decode_attention_ref(q, k, v, layer, lens), np.float32)
    live = np.asarray(DECODE_LENS) > 0
    np.testing.assert_allclose(out[live], ref[live], rtol=0, atol=2e-2)
    assert np.isfinite(out).all()
    assert (out[~live] == 0).all()


@pytest.mark.parametrize("lens", [
    DECODE_LENS,
    [0, 0, 20, 0, 48, 1],         # dead slots before the first live one
    [30, 0, 0, 0],                # dead slots after the last live one
    [0, 0, 0],                    # none live
])
def test_decode_attention_walk_fetches_only_live_blocks(lens):
    """The grid's K/V block indices, step by step: a new block is fetched
    only where the index changes, so the fetches are exactly the live
    slots' blocks up to each length (none at all when no slot is live,
    beyond the one block the pipeline reads before its first step)."""
    block, nb = 16, DECODE_S // 16
    slot, lo, hi = (np.asarray(a) for a in
                    block_walk(jnp.asarray(lens, jnp.int32), block))
    walk = [(int(slot[b]), int(min(max(j, lo[b]), hi[b])))
            for b in range(len(lens)) for j in range(nb)]
    fetched = [walk[0]] + [w for p, w in zip(walk, walk[1:]) if w != p]
    want = [(b, j) for b, n in enumerate(lens) for j in range(-(-n // block))]
    assert fetched == (want or [(0, 0)])
