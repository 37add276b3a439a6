"""Prefill + decode must agree with the full-sequence forward — across
attention (GQA + MLA), SSM, and hybrid cache types, and with Engram on
(the incremental last_tokens path vs full recompute)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced

from repro.models.model import (build_decode_step, build_prefill_step,
                                build_loss_fn, forward, init_params)
from repro.models.layers import head_logits
from repro.models.transformer import RunFlags

ARCHS = ["deepseek-7b", "deepseek-v2-236b", "gemma2-27b", "xlstm-125m",
         "jamba-1.5-large-398b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode_matches_full_forward(arch):
    cfg = reduced(arch)
    flags = RunFlags()
    params = init_params(cfg, 0)
    rng = np.random.RandomState(0)
    S_total, S_prompt = 12, 8
    toks = rng.randint(1, cfg.vocab_size, (2, S_total)).astype(np.int32)

    # full forward logits at every position
    h, _, _ = forward(cfg, flags, params, {"tokens": jnp.asarray(toks)},
                      "train")
    from repro.models.layers import rmsnorm  # final norm applied in forward
    hp = params["embed"] if cfg.tie_embeddings else params["head"]
    full_logits = np.asarray(head_logits(hp, h, cfg.final_logit_softcap,
                                         cfg.tie_embeddings))

    # prefill on the prompt, then decode the remaining tokens one by one
    prefill = build_prefill_step(cfg, flags, max_len=S_total + 4)
    decode = build_decode_step(cfg, flags)
    logits_p, state = prefill(params, {"tokens": jnp.asarray(toks[:, :S_prompt])})
    np.testing.assert_allclose(np.asarray(logits_p),
                               full_logits[:, S_prompt - 1], rtol=2e-3,
                               atol=2e-3)
    for t in range(S_prompt, S_total):
        logits_d, state = decode(params, state, jnp.asarray(toks[:, t]))
        np.testing.assert_allclose(
            np.asarray(logits_d), full_logits[:, t], rtol=2e-3, atol=2e-3,
            err_msg=f"{arch} decode step {t}")


def test_decode_respects_prompt_lengths():
    """Ragged prompts: per-row lengths select the right last logits."""
    cfg = reduced("deepseek-7b")
    flags = RunFlags()
    params = init_params(cfg, 0)
    rng = np.random.RandomState(1)
    toks = rng.randint(1, cfg.vocab_size, (2, 10)).astype(np.int32)
    lengths = jnp.asarray([6, 10], jnp.int32)
    prefill = build_prefill_step(cfg, flags, max_len=16)
    logits, state = prefill(params, {"tokens": jnp.asarray(toks),
                                     "lengths": lengths})
    # row 0: must equal prefill of the 6-token prefix alone
    l0, _ = prefill(params, {"tokens": jnp.asarray(toks[:1, :6])})
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(l0[0]),
                               rtol=2e-3, atol=2e-3)
    assert int(state["positions"][0]) == 6
    assert int(state["positions"][1]) == 10


def _scanned_attention(arch: str):
    """The reduced config with attention inside a scanned layer stack: the
    reduced gemma2 and jamba put every attention layer in an unrolled
    prefix, so they get the depth for two periods behind one Engram layer
    (widths unchanged)."""
    import dataclasses
    cfg = reduced(arch)
    if arch == "gemma2-27b":
        n = 6
        return dataclasses.replace(
            cfg, n_layers=n, layer_types=("attn",) * n,
            ffn_types=("dense",) * n,
            attn_kinds=tuple("local" if i % 2 == 0 else "global"
                             for i in range(n)),
            engram=dataclasses.replace(cfg.engram, layers=(1,)))
    if arch == "jamba-1.5-large-398b":
        n = 17
        types = tuple("attn" if i % 8 == 3 else "mamba" for i in range(n))
        return dataclasses.replace(
            cfg, n_layers=n, layer_types=types,
            attn_kinds=tuple("global" if t == "attn" else "-" for t in types),
            ffn_types=tuple("moe" if i % 2 == 1 else "dense"
                            for i in range(n)),
            engram=dataclasses.replace(cfg.engram, layers=(1,)))
    return cfg


def _assert_trees_equal(a, b, what):
    la, ta = jax.tree_util.tree_flatten_with_path(a)
    lb, tb = jax.tree_util.tree_flatten_with_path(b)
    assert ta == tb, what
    for (path, x), (_, y) in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{what} {jax.tree_util.keystr(path)}")


@pytest.mark.parametrize("arch,window_slice", [
    ("deepseek-7b", False), ("gemma2-27b", False), ("gemma2-27b", True),
    ("deepseek-v2-236b", False), ("jamba-1.5-large-398b", False)])
def test_scanned_decode_matches_unrolled(arch, window_slice):
    """The layer scan updates its stacked cache in place (one KV row a
    slot for attention, the whole state for recurrent mixers); the
    unrolled path updates per-layer buffers. Several decode steps from one
    prefilled state give equal logits and equal cache leaves."""
    from repro.models.transformer import segment_plan
    cfg = _scanned_attention(arch)
    assert any(seg.period and any(
        cfg.layer_types[i] == "attn"
        for i in seg.layers[seg.prefix_len:])
        for seg in segment_plan(cfg)), "no attention in a scanned stack"
    params = init_params(cfg, 0)
    rng = np.random.RandomState(2)
    S_prompt, steps, max_len = 14, 6, 24   # past gemma2's window of 16
    toks = rng.randint(1, cfg.vocab_size,
                       (2, S_prompt + steps)).astype(np.int32)
    lengths = jnp.asarray([S_prompt, S_prompt - 5], jnp.int32)
    scan = RunFlags(scan_layers=True, decode_window_slice=window_slice)
    unrolled = RunFlags(scan_layers=False, decode_window_slice=window_slice)
    _, state0 = jax.jit(build_prefill_step(cfg, scan, max_len=max_len))(
        params, {"tokens": jnp.asarray(toks[:, :S_prompt]),
                 "lengths": lengths})
    states = {}
    for name, flags in (("scan", scan), ("unrolled", unrolled)):
        step = jax.jit(build_decode_step(cfg, flags))
        st, logits = state0, []
        for t in range(steps):
            lg, st = step(params, st, jnp.asarray(toks[:, S_prompt + t]))
            logits.append(lg)
        states[name] = (jnp.stack(logits), st)
    _assert_trees_equal(states["scan"][0], states["unrolled"][0],
                        f"{arch} logits")
    _assert_trees_equal(states["scan"][1], states["unrolled"][1],
                        f"{arch} state")


def test_multitoken_decode_matches_single_steps():
    """The speculative verify step (m unrolled single-token steps in one
    program) gives the logits and final state of m separate decode steps
    through the same in-place layer-scan cache update."""
    from repro.models.model import build_multitoken_decode
    cfg = reduced("deepseek-7b")
    flags = RunFlags(scan_layers=True)
    params = init_params(cfg, 0)
    rng = np.random.RandomState(3)
    S_prompt, m = 8, 3
    toks = rng.randint(1, cfg.vocab_size, (2, S_prompt + m)).astype(np.int32)
    _, state0 = jax.jit(build_prefill_step(cfg, flags, max_len=16))(
        params, {"tokens": jnp.asarray(toks[:, :S_prompt])})
    multi = jax.jit(build_multitoken_decode(cfg, flags))
    logits_m, state_m, _ = multi(params, state0,
                                 jnp.asarray(toks[:, S_prompt:]))
    step = jax.jit(build_decode_step(cfg, flags))
    st, logits = state0, []
    for s in range(m):
        lg, st = step(params, st, jnp.asarray(toks[:, S_prompt + s]))
        logits.append(lg)
    _assert_trees_equal(logits_m, jnp.stack(logits, axis=1), "logits")
    _assert_trees_equal(state_m, st, "state")
