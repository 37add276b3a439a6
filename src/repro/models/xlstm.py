"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory).

Both use the stabilized exponential-gating recurrence of the xLSTM paper
(log-domain max-stabilizer m). Implemented as lax.scan over time — correct
for train/prefill, and the same step function drives one-token decode.
(Chunkwise-parallel mLSTM is a recorded hillclimb opportunity.)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding.rules import shard
from .params import pd


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def mlstm_defs(cfg: ModelConfig, dtype: str):
    d = cfg.d_model
    di = int(cfg.xlstm.proj_factor_mlstm * d)
    K = cfg.xlstm.conv1d_kernel
    return {
        "up": pd(d, 2 * di, axes=(None, "ffn"), dtype=dtype),
        "conv_w": pd(K, di, axes=("conv", "ffn"), dtype=dtype),
        "conv_b": pd(di, axes=("ffn",), dtype=dtype, init="zeros"),
        "wq": pd(di, di, axes=("ffn", None), dtype=dtype),
        "wk": pd(di, di, axes=("ffn", None), dtype=dtype),
        "wv": pd(di, di, axes=("ffn", None), dtype=dtype),
        "w_i": pd(di, cfg.n_heads, axes=("ffn", None), dtype="float32"),
        "w_f": pd(di, cfg.n_heads, axes=("ffn", None), dtype="float32"),
        "b_i": pd(cfg.n_heads, dtype="float32", init="zeros"),
        "b_f": pd(cfg.n_heads, dtype="float32", init="ones"),
        "out_norm": {"scale": pd(di, init="ones")},
        "down": pd(di, d, axes=("ffn", None), dtype=dtype),
    }


def _causal_conv(w, b, x, state):
    K = w.shape[0]
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, k:k + x.shape[1]] * w[k][None, None] for k in range(K))
    return out + b[None, None], xp[:, -(K - 1):]


def _mlstm_step(h_c, q, k, v, i_raw, f_raw, dh):
    """Stabilized mLSTM recurrence. h_c = (C (B,H,dh,dh), n (B,H,dh), m (B,H)).
    q/k/v (B,H,dh); i_raw/f_raw (B,H)."""
    C, n, m = h_c
    f_log = jax.nn.log_sigmoid(f_raw)
    m_new = jnp.maximum(f_log + m, i_raw)
    i_p = jnp.exp(i_raw - m_new)
    f_p = jnp.exp(f_log + m - m_new)
    k_s = k / math.sqrt(dh)
    C = f_p[..., None, None] * C + i_p[..., None, None] * (
        v[..., :, None] * k_s[..., None, :])
    n = f_p[..., None] * n + i_p[..., None] * k_s
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, q)),
                        jnp.exp(-m_new))
    h = jnp.einsum("bhij,bhj->bhi", C, q) / denom[..., None]
    return (C, n, m_new), h


def mlstm_forward(cfg: ModelConfig, params, x, cache=None):
    B, S, d = x.shape
    H = cfg.n_heads
    di = int(cfg.xlstm.proj_factor_mlstm * d)
    dh = di // H
    K = cfg.xlstm.conv1d_kernel
    xz = x @ params["up"]
    xm, z = xz[..., :di], xz[..., di:]
    conv_state = (cache["conv"] if cache is not None else
                  jnp.zeros((B, K - 1, di), x.dtype))
    xc, conv_state = _causal_conv(params["conv_w"], params["conv_b"],
                                  xm, conv_state)
    xc = jax.nn.silu(xc)
    q = (xc @ params["wq"]).reshape(B, S, H, dh)
    k = (xc @ params["wk"]).reshape(B, S, H, dh)
    v = (xm @ params["wv"]).reshape(B, S, H, dh)
    i_raw = xc.astype(jnp.float32) @ params["w_i"] + params["b_i"]
    f_raw = xc.astype(jnp.float32) @ params["w_f"] + params["b_f"]

    if cache is not None:
        st = (cache["C"], cache["n"], cache["m"])
    else:
        st = (jnp.zeros((B, H, dh, dh), jnp.float32),
              jnp.zeros((B, H, dh), jnp.float32),
              jnp.zeros((B, H), jnp.float32))

    def body(carry, xs):
        qt, kt, vt, it, ft = xs
        carry, h = _mlstm_step(carry, qt.astype(jnp.float32),
                               kt.astype(jnp.float32),
                               vt.astype(jnp.float32), it, ft, dh)
        return carry, h

    xs = tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v, i_raw, f_raw))
    st, hs = jax.lax.scan(body, st, xs)
    h = jnp.swapaxes(hs, 0, 1).reshape(B, S, di).astype(x.dtype)
    # per-feature group norm (out_norm) then z-gate
    hf = h.astype(jnp.float32)
    hf = hf * jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True) + 1e-6)
    h = (hf * params["out_norm"]["scale"]).astype(x.dtype)
    out = (h * jax.nn.silu(z)) @ params["down"]
    new_cache = {"conv": conv_state, "C": st[0], "n": st[1], "m": st[2]}
    return shard(out, "batch", None, None), new_cache


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def slstm_defs(cfg: ModelConfig, dtype: str):
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    f = int(cfg.xlstm.proj_factor_slstm * d)
    return {
        "conv_w": pd(cfg.xlstm.conv1d_kernel, d, axes=("conv", None), dtype=dtype),
        "conv_b": pd(d, dtype=dtype, init="zeros"),
        "w": pd(d, 4 * d, axes=(None, "ffn"), dtype=dtype),      # i,f,z,o
        "r": pd(H, dh, 4 * dh, axes=(None, None, None), dtype=dtype),
        "b": pd(4 * d, dtype="float32", init="zeros"),
        "norm": {"scale": pd(d, init="ones")},
        "ff_up": pd(d, 2 * f, axes=(None, "ffn"), dtype=dtype),
        "ff_down": pd(f, d, axes=("ffn", None), dtype=dtype),
    }


def _slstm_step(params, carry, x_t, H, dh):
    """carry = (c, n, h, m): c/n/h (B,H,dh), m (B,H). x_t (B,4d) pre-proj."""
    c, n, h, m = carry
    B = x_t.shape[0]
    rec = jnp.einsum("bhd,hdk->bhk", h.astype(x_t.dtype),
                     params["r"])                      # (B,H,4dh)
    gates = x_t.reshape(B, H, 4 * dh) + rec + \
        params["b"].reshape(H, 4 * dh).astype(x_t.dtype)
    gates = gates.astype(jnp.float32)
    i_raw, f_raw, z_raw, o_raw = jnp.split(gates, 4, axis=-1)
    i_raw, f_raw = i_raw.mean(-1), f_raw.mean(-1)      # scalar gates per head
    f_log = jax.nn.log_sigmoid(f_raw)
    m_new = jnp.maximum(f_log + m, i_raw)
    i_p = jnp.exp(i_raw - m_new)[..., None]
    f_p = jnp.exp(f_log + m - m_new)[..., None]
    z = jnp.tanh(z_raw)
    o = jax.nn.sigmoid(o_raw)
    c = f_p * c + i_p * z
    n = f_p * n + i_p
    h_new = o * (c / jnp.maximum(n, 1.0))
    return (c, n, h_new, m_new), h_new


def slstm_forward(cfg: ModelConfig, params, x, cache=None):
    B, S, d = x.shape
    H = cfg.n_heads
    dh = d // H
    K = cfg.xlstm.conv1d_kernel
    conv_state = (cache["conv"] if cache is not None else
                  jnp.zeros((B, K - 1, d), x.dtype))
    xc, conv_state = _causal_conv(params["conv_w"], params["conv_b"],
                                  x, conv_state)
    xc = jax.nn.silu(xc)
    xg = xc @ params["w"]                              # (B,S,4d)

    if cache is not None:
        st = (cache["c"], cache["n"], cache["h"], cache["m"])
    else:
        z = jnp.zeros((B, H, dh), jnp.float32)
        st = (z, z, z, jnp.zeros((B, H), jnp.float32))

    def body(carry, x_t):
        return _slstm_step(params, carry, x_t, H, dh)

    st, hs = jax.lax.scan(body, st, jnp.swapaxes(xg, 0, 1))
    h = jnp.swapaxes(hs.reshape(S, B, d), 0, 1).astype(x.dtype)
    hf = h.astype(jnp.float32)
    hf = hf * jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True) + 1e-6)
    h = (hf * params["norm"]["scale"]).astype(x.dtype)
    # post up/down GeGLU feed-forward (proj_factor 4/3)
    f = params["ff_down"].shape[0]
    gu = h @ params["ff_up"]
    g, u = gu[..., :f], gu[..., f:]
    out = (jax.nn.gelu(g, approximate=True) * u) @ params["ff_down"]
    new_cache = {"conv": conv_state, "c": st[0], "n": st[1], "h": st[2],
                 "m": st[3]}
    return shard(out, "batch", None, None), new_cache


def init_xlstm_cache(cfg: ModelConfig, kind: str, batch: int, dtype):
    H = cfg.n_heads
    d = cfg.d_model
    K = cfg.xlstm.conv1d_kernel
    if kind == "mlstm":
        di = int(cfg.xlstm.proj_factor_mlstm * d)
        dh = di // H
        return {
            "conv": jnp.zeros((batch, K - 1, di), dtype),
            "C": jnp.zeros((batch, H, dh, dh), jnp.float32),
            "n": jnp.zeros((batch, H, dh), jnp.float32),
            "m": jnp.zeros((batch, H), jnp.float32),
        }
    dh = d // H
    # a buffer per leaf: the serving engine donates its decode state
    cell = {k: jnp.zeros((batch, H, dh), jnp.float32) for k in ("c", "n", "h")}
    return {"conv": jnp.zeros((batch, K - 1, d), dtype), **cell,
            "m": jnp.zeros((batch, H), jnp.float32)}
