"""GQA/MQA attention: chunked causal-efficient train/prefill path, decode path.

The chunked path loops Python-side over query chunks and scans KV chunks only
up to the causal/window frontier — fully-masked KV blocks are never computed
(sub-quadratic for sliding-window layers). Decode supports per-sequence
positions (continuous batching) and arbitrary KV-cache sharding, including
KV-sequence sharding over the data axis (flash-decode style: GSPMD inserts
the logsumexp-combine collectives for the reductions over the sharded dim).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.decode_attention.ops import bounded_decode_attention
from ..sharding.rules import current_ctx, shard
from .layers import apply_rope, rmsnorm, softcap
from .params import pd

NEG_INF = -2.0 ** 30


def attn_defs(cfg: ModelConfig, dtype: str):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    defs = {
        "wq": pd(d, hq * hd, axes=(None, "heads"), dtype=dtype),
        "wk": pd(d, hkv * hd, axes=(None, "kv_heads"), dtype=dtype),
        "wv": pd(d, hkv * hd, axes=(None, "kv_heads"), dtype=dtype),
        "wo": pd(hq * hd, d, axes=("heads", None), dtype=dtype),
    }
    if cfg.qk_norm:
        defs["q_norm"] = {"scale": pd(hd, init="ones")}
        defs["k_norm"] = {"scale": pd(hd, init="ones")}
    return defs


def _rope_theta(cfg: ModelConfig, kind: str) -> float:
    if kind == "local" and cfg.rope_local_theta > 0:
        return cfg.rope_local_theta
    return cfg.rope_theta


def _qkv(cfg: ModelConfig, params, h, positions, kind):
    B, S, _ = h.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ params["wq"]).reshape(B, S, hq, hd)
    k = (h @ params["wk"]).reshape(B, S, hkv, hd)
    v = (h @ params["wv"]).reshape(B, S, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    theta = _rope_theta(cfg, kind)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def _mask(qpos, kpos, *, causal: bool, window: int):
    """(..., Q, K) boolean validity mask from position vectors."""
    m = jnp.ones(qpos.shape[:-1] + (qpos.shape[-1], kpos.shape[-1]), bool)
    if causal:
        m &= kpos[..., None, :] <= qpos[..., :, None]
    if window > 0:
        m &= kpos[..., None, :] > qpos[..., :, None] - window
    return m


def _sdpa(cfg: ModelConfig, q, k, v, mask, bf16_scores: bool = False):
    """Dense grouped attention. q: (B,Q,Hq,Dk) k/v: (B,K,Hkv,Dk/Dv), mask (B?,Q,K).
    Dv may differ from Dk (MLA).

    ``bf16_scores``: keep q/k in their native dtype and accumulate in f32
    via preferred_element_type — avoids materializing an f32 copy of the
    whole KV cache (the dominant decode memory term; §Perf iteration 1).
    """
    B, Q, hq, hd = q.shape
    hkv, hd_v = k.shape[2], v.shape[-1]
    g = hq // hkv
    qg = q.reshape(B, Q, hkv, g, hd)
    if bf16_scores:
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                            preferred_element_type=jnp.float32) / math.sqrt(hd)
    else:
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) / math.sqrt(hd)
    scores = softcap(scores, cfg.attn_logit_softcap)
    while mask.ndim < scores.ndim:
        mask = mask[:, None]
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(B, Q, hq, hd_v)


def _chunk_attn(cfg: ModelConfig, q, k, v, qpos, kpos, *, causal, window,
                q_chunk=1024, kv_chunk=1024):
    """Flash-style two-level chunking with causal/window block skipping."""
    B, S, hq, hd = q.shape
    hkv, hd_v = k.shape[2], v.shape[-1]
    g = hq // hkv
    nq = (S + q_chunk - 1) // q_chunk
    nk = (S + kv_chunk - 1) // kv_chunk
    pad_q = nq * q_chunk - S
    pad_k = nk * kv_chunk - S
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, pad_q),), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, pad_k),), constant_values=2**30)
    kc = k.reshape(B, nk, kv_chunk, hkv, hd)
    vc = v.reshape(B, nk, kv_chunk, hkv, hd_v)
    kposc = kpos.reshape(nk, kv_chunk)
    outs = []
    scale = 1.0 / math.sqrt(hd)
    for i in range(nq):
        qi = q[:, i * q_chunk:(i + 1) * q_chunk].reshape(B, q_chunk, hkv, g, hd)
        qpi = qpos[i * q_chunk:(i + 1) * q_chunk]
        # static KV frontier for this q chunk: blocks past the causal
        # diagonal are never computed
        hi = nk if not causal else min(nk, -(-((i + 1) * q_chunk) // kv_chunk))
        lo = 0
        if window > 0:
            lo = max(0, (i * q_chunk - window) // kv_chunk)
        xs = (kc[:, lo:hi], vc[:, lo:hi], kposc[lo:hi])

        def body(carry, x):
            m_run, l_run, acc = carry
            kj, vj, kpj = x
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi.astype(jnp.float32),
                           kj.astype(jnp.float32)) * scale
            s = softcap(s, cfg.attn_logit_softcap)
            valid = _mask(qpi, kpj, causal=causal, window=window)
            s = jnp.where(valid[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_run * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vj.astype(jnp.float32))
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, hkv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, hkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, hkv, g, q_chunk, hd_v), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                          jax.tree.map(lambda t: jnp.swapaxes(t, 0, 1) if t.ndim > 2 else t, xs))
        out_i = acc / jnp.maximum(l_f[..., None], 1e-20)
        outs.append(jnp.transpose(out_i, (0, 3, 1, 2, 4)).reshape(B, q_chunk, hq, hd_v))
    out = jnp.concatenate(outs, axis=1)[:, :S]
    return out.astype(q.dtype)


def attention(cfg: ModelConfig, params, h, positions, kind: str,
              *, q_chunk: int = 1024, kv_chunk: int = 1024,
              chunk_threshold: int = 2048, bf16_scores: bool = False):
    """Train/prefill attention. h (B,S,d), positions (S,). Returns (out, kv)."""
    B, S, _ = h.shape
    q, k, v = _qkv(cfg, params, h, positions, kind)
    causal = not cfg.is_encoder
    window = cfg.window_size if kind == "local" else 0
    if S <= chunk_threshold:
        mask = _mask(positions, positions, causal=causal, window=window)[None]
        out = _sdpa(cfg, q, k, v, mask, bf16_scores)
    else:
        out = _chunk_attn(cfg, q, k, v, positions, positions,
                          causal=causal, window=window,
                          q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    out = shard(out @ params["wo"], "batch", None, None)
    return out, {"k": k, "v": v}


def write_rows(buf, new, positions, layer=None):
    """Write each slot's new row into its cache at the slot's position.

    buf (B, S, ...) and new (B, 1, ...); positions (B,) are clamped into
    [0, S) as ``dynamic_update_slice`` clamps them. With ``layer``, buf is
    a layer stack (n, B, S, ...) and only layer ``layer``'s B rows are
    written: a scatter of B rows that updates a donated stack in place,
    where slicing the layer out and writing it back would copy the whole
    layer's cache twice. Returns the updated buf."""
    new = new.astype(buf.dtype)
    if layer is None:
        return jax.vmap(
            lambda b, n, p: jax.lax.dynamic_update_slice_in_dim(b, n, p, axis=0)
        )(buf, new, positions)

    pos = jnp.clip(positions, 0, buf.shape[2] - 1)
    return buf.at[layer, jnp.arange(buf.shape[1]), pos].set(new[:, 0])


def layer_view(buf, layer=None):
    """Layer ``layer`` of a stacked cache leaf (the leaf itself without
    one). A dynamic index the compiler fuses into its consumers."""
    if layer is None:
        return buf
    return jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)


def bounded_read(cfg: ModelConfig, kind: str) -> bool:
    """Whether a decode attention layer of ``kind`` reads each slot's
    cache only up to its length on the TPU: a global GQA/MHA layer with
    no logit softcap (``decode_attention``)."""
    return (cfg.attn_impl != "mla" and kind != "local"
            and not cfg.attn_logit_softcap)


def decode_attention(cfg: ModelConfig, params, h, cache, positions, kind: str,
                     *, layer=None, bf16_scores: bool = False,
                     window_slice: bool = False, lens=None):
    """Single-token decode. h (B,1,d); cache {k,v}: (B,Smax,Hkv,D);
    positions (B,) current index per sequence. Returns (out, new_cache).

    ``layer``: the cache leaves are a layer stack (n,B,Smax,Hkv,D) and
    this is layer ``layer`` of it; the new rows are written into the
    stack (``write_rows``) and the whole stack is returned.

    ``window_slice``: sliding-window layers attend to a gathered
    window-sized cache slice instead of masking the full context — cuts
    the per-step cache read from O(S) to O(window) (§Perf iteration 2).

    ``lens`` (B,): positions each slot attends, 0 for a slot that is not
    live (default ``positions + 1``). A global layer without a logit
    softcap on one device lowers on the TPU to the length-bounded kernel
    (``kernels/decode_attention``), which reads each slot's cache only up
    to its length; everywhere else, and on every other backend, the layer
    reads the whole cache and masks it (there ``lens`` is not read)."""
    B = h.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ params["wq"]).reshape(B, 1, hq, hd)
    k = (h @ params["wk"]).reshape(B, 1, hkv, hd)
    v = (h @ params["wv"]).reshape(B, 1, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    theta = _rope_theta(cfg, kind)
    q = apply_rope(q, positions[:, None], theta)
    k = apply_rope(k, positions[:, None], theta)

    # scatter new k/v at per-sequence positions
    new_cache = {"k": write_rows(cache["k"], k, positions, layer),
                 "v": write_rows(cache["v"], v, positions, layer)}
    kc = shard(layer_view(new_cache["k"], layer),
               "batch", "kv_seq", "kv_heads", None)
    vc = shard(layer_view(new_cache["v"], layer),
               "batch", "kv_seq", "kv_heads", None)
    if layer is None:
        new_cache = {"k": kc, "v": vc}

    S = kc.shape[1]
    window = cfg.window_size if kind == "local" else 0
    if window_slice and 0 < window < S:
        w = min(window, S)
        start = jnp.clip(positions - (w - 1), 0, S - w)
        k_att = jax.vmap(lambda b, s: jax.lax.dynamic_slice_in_dim(
            b, s, w, axis=0))(kc, start)
        v_att = jax.vmap(lambda b, s: jax.lax.dynamic_slice_in_dim(
            b, s, w, axis=0))(vc, start)
        kpos = start[:, None] + jnp.arange(w)[None]    # (B, w)
        valid = kpos <= positions[:, None]             # window via the slice
    else:
        k_att, v_att = kc, vc
        kpos = jnp.arange(S)[None]                     # (1, S)
        valid = kpos <= positions[:, None]
        if window > 0:
            valid &= kpos > positions[:, None] - window

    def masked(q):
        return _sdpa(cfg, q, k_att, v_att, valid[:, None, :], bf16_scores)

    ctx = current_ctx()
    if bounded_read(cfg, kind) and (ctx is None or ctx.mesh.size == 1):
        if lens is None:
            lens = positions + 1
        stack = (new_cache["k"], new_cache["v"]) if layer is not None \
            else (kc[None], vc[None])

        def bounded(q):
            return bounded_decode_attention(
                q[:, 0], *stack, 0 if layer is None else layer,
                lens)[:, None]
        out = jax.lax.platform_dependent(q, tpu=bounded, default=masked)
    else:
        out = masked(q)
    out = out.reshape(B, 1, hq * hd)
    out = out @ params["wo"]
    return out, new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    # k and v get buffers of their own: the serving engine donates the
    # cache to each step, and one buffer cannot be donated twice
    return {name: shard(jnp.zeros((batch, max_len, hkv, hd), dtype),
                        "batch", "kv_seq", "kv_heads", None)
            for name in ("k", "v")}
