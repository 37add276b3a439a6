"""Common layers: RMSNorm, RoPE, MLP, embeddings, softcap, chunked xent."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..sharding.rules import shard
from .params import pd


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_defs(d: int):
    return {"scale": pd(d, init="ones")}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


def softcap(x, cap: float):
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)            # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) or (..., H, D) w/ positions (..., S) or (...,)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    # broadcast over heads: x (..., S, H, D) -> split halves interleaved-free
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[..., None, :]                      # (..., S, 1, D/2)
    sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def mlp_defs(d: int, f: int, dtype: str):
    return {
        "gate": pd(d, f, axes=(None, "ffn"), dtype=dtype),
        "up":   pd(d, f, axes=(None, "ffn"), dtype=dtype),
        "down": pd(f, d, axes=("ffn", None), dtype=dtype),
    }


def mlp(params, x, act: str = "silu"):
    g = x @ params["gate"]
    u = x @ params["up"]
    g = shard(g, "batch", None, "ffn") if g.ndim == 3 else g
    a = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g, approximate=True)
    out = (a * u) @ params["down"]
    return out


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_defs(vocab: int, d: int, dtype: str):
    return {"w": pd(vocab, d, axes=("vocab", None), dtype=dtype, scale=1.0)}


def embed_lookup(params, tokens):
    return jnp.take(params["w"], tokens, axis=0)


def embed_lookup_local(params, tokens):
    """Vocab-sharded embedding gather as masked-local take + psum.

    XLA lowers a plain take on a vocab-sharded table to an all-gather of
    the whole table (hundreds of MB per step for 256k vocabs); the
    shard_map form moves only the (tokens x d_model) result
    (§Perf iteration: embed_local_gather)."""
    import jax as _jax
    from jax.sharding import PartitionSpec as P
    from ..sharding.rules import current_ctx

    ctx = current_ctx()
    w = params["w"]
    V, D = w.shape
    axes = tuple(ctx.rules.get("vocab", ())) if ctx else ()
    axes = tuple(a for a in axes if ctx and a in ctx.mesh.axis_names)
    if ctx is None or not axes:
        return embed_lookup(params, tokens)
    n = ctx.axis_prod(axes)
    if n == 1 or V % n != 0:
        return embed_lookup(params, tokens)
    v_loc = V // n
    ax = axes[0] if len(axes) == 1 else axes

    def local_fn(wl, tok):
        base = _jax.lax.axis_index(ax) * v_loc
        rel = tok - base
        ok = (rel >= 0) & (rel < v_loc)
        rows = jnp.take(wl, jnp.clip(rel, 0, v_loc - 1), axis=0)
        rows = rows * ok[..., None].astype(rows.dtype)
        return _jax.lax.psum(rows, ax)

    spec_t = ctx.spec_for(tokens.shape, ("batch",) + (None,) * (tokens.ndim - 1))
    b_entry = spec_t[0] if len(spec_t) > 0 else None
    fn = _jax.shard_map(local_fn, mesh=ctx.mesh,
                        in_specs=(P(ax, None), spec_t),
                        out_specs=P(b_entry, *([None] * tokens.ndim)),
                        check_vma=False)
    return fn(w, tokens)


def head_defs(vocab: int, d: int, dtype: str):
    return {"w": pd(d, vocab, axes=(None, "vocab"), dtype=dtype)}


def head_logits(params, h, final_cap: float = 0.0, tied: bool = False):
    w = params["w"].T if tied else params["w"]
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    return softcap(logits, final_cap)


# ---------------------------------------------------------------------------
# Chunked softmax cross-entropy (vocab-sharded, bounded logits memory)
# ---------------------------------------------------------------------------

def chunked_xent(head_params, h, labels, mask=None, *, final_cap: float = 0.0,
                 tied: bool = False, chunk: int = 2048,
                 remat_body: bool = False):
    """h: (B,S,d); labels (B,S) int32; returns mean xent over mask.

    Computes logits for ``chunk`` positions at a time via lax.scan so the
    (tokens, vocab) logits tensor never fully materializes.

    ``remat_body``: checkpoint each chunk so the backward pass recomputes
    its logits instead of storing every (chunk, vocab) f32 block as a scan
    residual — the dominant train-mode activation term (§Perf iteration).
    """
    B, S, D = h.shape
    T = B * S
    hf = h.reshape(T, D)
    lf = labels.reshape(T)
    mf = jnp.ones((T,), jnp.float32) if mask is None else mask.reshape(T).astype(jnp.float32)
    pad = (-T) % chunk
    if pad:
        hf = jnp.pad(hf, ((0, pad), (0, 0)))
        lf = jnp.pad(lf, (0, pad))
        mf = jnp.pad(mf, (0, pad))
    n = hf.shape[0] // chunk
    hc = hf.reshape(n, chunk, D)
    lc = lf.reshape(n, chunk)
    mc = mf.reshape(n, chunk)

    def body(carry, xs):
        hx, lx, mx = xs
        logits = head_logits(head_params, hx, final_cap, tied)   # (chunk, V) f32
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lx[:, None], axis=-1)[:, 0]
        loss = (logz - gold) * mx
        return (carry[0] + loss.sum(), carry[1] + mx.sum()), None

    if remat_body:
        body = jax.checkpoint(body)
    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),) * 2,
                                 (hc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)
