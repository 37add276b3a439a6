"""DeepSeek Multi-head Latent Attention (v2/v3).

Train/prefill: decompress per-head K/V from the latent and run standard
attention (chunked for long sequences). Decode: the *absorbed* path — the
KV cache stores only (c_kv, k_rope) = (kv_lora + rope_dim) per token
(576 dims for v2/v3 vs 128·128·2 = 32768 for naive MHA), and W_uk / W_uv
are absorbed into the query / output projections.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding.rules import shard
from .attention import _chunk_attn, _mask, _sdpa, layer_view, write_rows
from .layers import apply_rope, rmsnorm
from .params import pd


def mla_defs(cfg: ModelConfig, dtype: str):
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wdq":  pd(d, m.q_lora_rank, axes=(None, "lora"), dtype=dtype),
        "q_ln": {"scale": pd(m.q_lora_rank, init="ones")},
        "wuq":  pd(m.q_lora_rank, H * qk_head, axes=(None, "heads"), dtype=dtype),
        "wdkv": pd(d, m.kv_lora_rank + m.qk_rope_head_dim, axes=(None, "lora"),
                   dtype=dtype),
        "kv_ln": {"scale": pd(m.kv_lora_rank, init="ones")},
        "wuk":  pd(m.kv_lora_rank, H * m.qk_nope_head_dim,
                   axes=(None, "heads"), dtype=dtype),
        "wuv":  pd(m.kv_lora_rank, H * m.v_head_dim,
                   axes=(None, "heads"), dtype=dtype),
        "wo":   pd(H * m.v_head_dim, d, axes=("heads", None), dtype=dtype),
    }


def _latents(cfg: ModelConfig, params, h, positions):
    """Shared by prefill/decode: q heads + compressed kv latents."""
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = h.shape
    nope, rope = m.qk_nope_head_dim, m.qk_rope_head_dim
    cq = rmsnorm(params["q_ln"], h @ params["wdq"], cfg.norm_eps)
    q = (cq @ params["wuq"]).reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv_full = h @ params["wdkv"]
    c_kv = rmsnorm(params["kv_ln"], ckv_full[..., :m.kv_lora_rank], cfg.norm_eps)
    k_rope = ckv_full[..., m.kv_lora_rank:][:, :, None, :]     # (B,S,1,rope)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


def mla_attention(cfg: ModelConfig, params, h, positions, kind: str = "global",
                  *, q_chunk: int = 1024, kv_chunk: int = 1024,
                  chunk_threshold: int = 2048, bf16_scores: bool = False):
    """Train/prefill path. Returns (out, cache={c_kv, k_rope})."""
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = h.shape
    nope, rope = m.qk_nope_head_dim, m.qk_rope_head_dim
    q_nope, q_rope, c_kv, k_rope = _latents(cfg, params, h, positions)

    k_nope = (c_kv @ params["wuk"]).reshape(B, S, H, nope)
    v = (c_kv @ params["wuv"]).reshape(B, S, H, m.v_head_dim)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "heads", None)
    v = shard(v, "batch", None, "heads", None)

    if S <= chunk_threshold:
        mask = _mask(positions, positions, causal=True, window=0)[None]
        out = _sdpa(cfg, q, k, v, mask, bf16_scores)
    else:
        out = _chunk_attn(cfg, q, k, v, positions, positions, causal=True,
                          window=0, q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = out.reshape(B, S, H * m.v_head_dim)
    out = shard(out @ params["wo"], "batch", None, None)
    return out, {"c_kv": c_kv, "k_rope": k_rope.squeeze(2)}


def mla_decode(cfg: ModelConfig, params, h, cache, positions,
               *, layer=None, bf16_scores: bool = False):
    """Absorbed decode on compressed cache.

    cache: c_kv (B,Smax,kv_lora), k_rope (B,Smax,rope). positions (B,).
    ``layer``: the leaves are a layer stack and this is layer ``layer``
    of it, as in ``attention.decode_attention``.
    ``bf16_scores``: f32 accumulation without materializing f32 cache
    copies (§Perf iteration 1)."""
    m, H = cfg.mla, cfg.n_heads
    B = h.shape[0]
    nope, rope = m.qk_nope_head_dim, m.qk_rope_head_dim
    q_nope, q_rope, c_kv_new, k_rope_new = _latents(cfg, params, h,
                                                    positions[:, None])
    # absorb W_uk into the query: q_lat[h] = q_nope[h] @ W_uk[h].T
    wuk = params["wuk"].reshape(m.kv_lora_rank, H, nope)
    q_lat = jnp.einsum("bshn,lhn->bshl", q_nope, wuk)          # (B,1,H,kv_lora)

    new_cache = {
        "c_kv": write_rows(cache["c_kv"], c_kv_new, positions, layer),
        "k_rope": write_rows(cache["k_rope"], k_rope_new.squeeze(2),
                             positions, layer)}
    ckv = shard(layer_view(new_cache["c_kv"], layer), "batch", "kv_seq", None)
    krp = shard(layer_view(new_cache["k_rope"], layer),
                "batch", "kv_seq", None)
    if layer is None:
        new_cache = {"c_kv": ckv, "k_rope": krp}

    S = ckv.shape[1]
    scale = 1.0 / math.sqrt(nope + rope)
    if bf16_scores:
        s_lat = jnp.einsum("bshl,bSl->bhsS", q_lat, ckv,
                           preferred_element_type=jnp.float32)   # (B,H,1,S)
        s_rope = jnp.einsum("bshr,bSr->bhsS", q_rope, krp,
                            preferred_element_type=jnp.float32)
    else:
        s_lat = jnp.einsum("bshl,bSl->bhsS", q_lat.astype(jnp.float32),
                           ckv.astype(jnp.float32))              # (B,H,1,S)
        s_rope = jnp.einsum("bshr,bSr->bhsS", q_rope.astype(jnp.float32),
                            krp.astype(jnp.float32))
    scores = (s_lat + s_rope) * scale
    valid = (jnp.arange(S)[None] <= positions[:, None])[:, None, None, :]
    scores = jnp.where(valid, scores, -2.0 ** 30)
    p = jax.nn.softmax(scores, axis=-1)
    if bf16_scores:
        out_lat = jnp.einsum("bhsS,bSl->bshl", p.astype(ckv.dtype), ckv,
                             preferred_element_type=jnp.float32)
    else:
        out_lat = jnp.einsum("bhsS,bSl->bshl", p, ckv.astype(jnp.float32))
    wuv = params["wuv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bshl,lhv->bshv", out_lat.astype(h.dtype), wuv)
    out = out.reshape(B, 1, H * m.v_head_dim) @ params["wo"]
    return out, new_cache


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    ckv = jnp.zeros((batch, max_len, m.kv_lora_rank), dtype)
    krp = jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)
    return {"c_kv": shard(ckv, "batch", "kv_seq", None),
            "k_rope": shard(krp, "batch", "kv_seq", None)}
