"""Engram retrieval + fusion unit tests (single device; strategies fall
back to local without a mesh — multi-device equivalence runs in
tests/test_multidev.py subprocesses)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import EngramConfig, ModelConfig
from repro.core.engram import (engram_defs, engram_fuse, engram_lookup,
                               padded_vocab, retrieve, retrieve_local,
                               retrieve_local_kernel)
from repro.core.hashing import engram_indices
from repro.models.params import tree_init

ECFG = EngramConfig(orders=(2, 3), n_heads=4, emb_dim=64, table_vocab=1024,
                    layers=(1, 2), strategy="local")
CFG = ModelConfig(name="t", family="dense", n_layers=4, d_model=32,
                  vocab_size=211, n_heads=2, n_kv_heads=2, head_dim=16,
                  d_ff=64, engram=ECFG, dtype="float32")


@pytest.fixture(scope="module")
def eng_params():
    return tree_init(engram_defs(CFG, "float32"), 0)


def test_padded_vocab_divisible():
    assert padded_vocab(ECFG) % 4096 == 0
    assert padded_vocab(ECFG) >= ECFG.table_vocab


def test_retrieve_local_shapes(eng_params):
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 211, (2, 8)))
    idx = engram_indices(ECFG, toks)
    rows = retrieve_local(ECFG, eng_params["layers"][0]["tables"], idx)
    assert rows.shape == (2, 8, len(ECFG.orders) * ECFG.emb_dim)
    assert np.isfinite(np.asarray(rows)).all()


def test_retrieve_strategies_fall_back_consistently(eng_params):
    """Without a mesh ctx every strategy must equal the local gather."""
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 211, (2, 8)))
    idx = engram_indices(ECFG, toks)
    tab = eng_params["layers"][0]["tables"]
    ref = np.asarray(retrieve_local(ECFG, tab, idx))
    for strat in ("local", "tp", "pooled"):
        out = np.asarray(retrieve(ECFG, tab, idx, strat))
        np.testing.assert_allclose(out, ref, rtol=1e-6, err_msg=strat)


def test_retrieve_kernel_matches_local(eng_params):
    toks = jnp.asarray(np.random.RandomState(2).randint(0, 211, (2, 8)))
    idx = engram_indices(ECFG, toks)
    tab = eng_params["layers"][0]["tables"]
    ref = np.asarray(retrieve_local(ECFG, tab, idx))
    out = np.asarray(retrieve_local_kernel(ECFG, tab, idx, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-6)


def test_fuse_gating_bounds(eng_params):
    """Fusion adds sigmoid-gated update: output within h ± |update|."""
    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(2, 8, CFG.d_model).astype(np.float32))
    rows = jnp.asarray(
        rng.randn(2, 8, len(ECFG.orders) * ECFG.emb_dim).astype(np.float32))
    fuse = eng_params["layers"][0]
    out = engram_fuse(CFG, fuse, h, rows)
    assert out.shape == h.shape
    assert np.isfinite(np.asarray(out)).all()
    # zero rows (after norm they stay zero only if rows==0) => out == h
    out0 = engram_fuse(CFG, fuse, h, jnp.zeros_like(rows))
    np.testing.assert_allclose(np.asarray(out0), np.asarray(h), atol=1e-5)


def test_fuse_kernel_matches_ref(eng_params):
    rng = np.random.RandomState(4)
    h = jnp.asarray(rng.randn(2, 8, CFG.d_model).astype(np.float32))
    rows = jnp.asarray(
        rng.randn(2, 8, len(ECFG.orders) * ECFG.emb_dim).astype(np.float32))
    fuse = eng_params["layers"][0]
    ref = np.asarray(engram_fuse(CFG, fuse, h, rows, use_kernel=False))
    out = np.asarray(engram_fuse(CFG, fuse, h, rows, use_kernel=True))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_engram_lookup_end_to_end(eng_params):
    toks = jnp.asarray(np.random.RandomState(5).randint(0, 211, (3, 6)))
    rows = engram_lookup(CFG, eng_params, toks, layer_slot=1)
    assert rows.shape == (3, 6, len(ECFG.orders) * ECFG.emb_dim)


def test_same_context_same_rows(eng_params):
    """Two sequences sharing an n-gram context retrieve identical rows at
    that position (the 'static knowledge' property)."""
    a = jnp.asarray([[11, 22, 33, 44]], jnp.int32)
    b = jnp.asarray([[99, 22, 33, 44]], jnp.int32)   # same final trigram
    ra = np.asarray(engram_lookup(CFG, eng_params, a))
    rb = np.asarray(engram_lookup(CFG, eng_params, b))
    np.testing.assert_allclose(ra[0, -1], rb[0, -1])
