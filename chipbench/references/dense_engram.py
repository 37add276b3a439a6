"""Plain float32 reference of a dense Llama-style decoder with Engram layers.

The published math, written out in ``jax.numpy`` at
``Precision.HIGHEST``: token embedding; per layer a pre-norm block of
RoPE attention (grouped KV heads, causal) and a SwiGLU MLP; before each
Engram layer the gated fusion of its n-gram rows (rows from the multi-head
n-gram hash, RMS-normed, projected, gated by ``sigmoid(h @ gate)``); a
final RMSNorm and the output head. No cache, no batching of requests, no
kernels: each sequence is one full causal pass.

It imports nothing of the program under test. Weights come from
``chipbench.weights`` (made again from the seed, one layer at a time) and
the n-gram hash is a copy of the published hash in numpy. Departures from
the published models are listed in each configuration file.

``precision`` other than ``"float32"`` is the control: the same pass with
every matmul's operands rounded to the next precision below the one the
configuration states: ``"fp8"`` (float8 e4m3 under absmax scaling) below
bfloat16, ``"bfloat16"`` below float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


# ------------------------------------------------------------ n-gram hash

_M1, _M2 = np.uint32(0x7FEB352D), np.uint32(0x846CA68B)


def _mix(x):
    x = x ^ (x >> np.uint32(16))
    x = x * _M1
    x = x ^ (x >> np.uint32(15))
    x = x * _M2
    return x ^ (x >> np.uint32(16))


def ngram_indices(eng: dict, tokens: np.ndarray) -> np.ndarray:
    """tokens (S,) -> row index (S, n_tables) of each (order, head) table
    for the n-gram ending at each position (left edge padded)."""
    orders, heads = list(eng["orders"]), int(eng["n_heads"])
    n_tables = len(orders) * heads
    rng = np.random.RandomState(int(eng["seed"]) & 0x7FFFFFFF)
    consts = (rng.randint(1, 2 ** 31, size=(n_tables, max(orders)),
                          dtype=np.int64) * 2 + 1).astype(np.uint32)
    tokens = np.asarray(tokens, np.int64)
    out = []
    with np.errstate(over="ignore"):
        for oi, order in enumerate(orders):
            padded = np.concatenate([np.full(order - 1, eng["pad_token"]),
                                     tokens]).astype(np.uint32)
            win = np.stack([padded[j:j + len(tokens)] for j in range(order)],
                           axis=-1)                       # oldest .. newest
            for h in range(heads):
                t = oi * heads + h
                acc = np.full(len(tokens),
                              (0x9E3779B9 * (t + 1)) & 0xFFFFFFFF, np.uint32)
                for j in range(order):
                    acc = _mix(acc ^ (win[:, j] * consts[t, j]))
                out.append(acc % np.uint32(eng["table_vocab"]))
    return np.stack(out, axis=-1).astype(np.int64)


# ------------------------------------------------------------------ math

def _q8(x, axis=None):
    """Round to float8 e4m3 under absmax scaling (over ``axis``)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, prec: str):
    if prec == "fp8":
        x, w = _q8(x, axis=-1), _q8(w)
    elif prec == "bfloat16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """Rotate-half RoPE. x (S, H, D), pos (S,)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Model:
    """One configuration's reference for one seed. Jitted pieces take the
    seed words as an argument, so one compile serves every seed."""

    def __init__(self, config: dict, seed: int, precision: str = "float32"):
        if precision not in ("float32", "bfloat16", "fp8"):
            raise ValueError(precision)
        self.c = config
        self.words = jnp.asarray(W.seed_words(seed))
        self.prec = precision
        self.dtype = config["torch_dtype"]     # the weights as served
        d, H = config["hidden_size"], config["num_attention_heads"]
        self.d, self.H = d, H
        self.KV = config["num_key_value_heads"]
        self.hd = d // H
        self.F = config["intermediate_size"]
        self.V = config["vocab_size"]
        self.eps = float(config["rms_norm_eps"])
        self.theta = float(config["rope_theta"])
        self.eng = config.get("engram")
        self.eng_layers = [l for l in self.eng["layers"]
                           if 0 < l < config["num_hidden_layers"]] \
            if self.eng else []
        if self.eng:
            self.T = len(self.eng["orders"]) * self.eng["n_heads"]
            self.ehd = self.eng["emb_dim"] // self.eng["n_heads"]
            self.fuse = len(self.eng["orders"]) * self.eng["emb_dim"]

    def __hash__(self):
        return hash((self.prec, self.d, self.H, self.KV, self.F, self.V,
                     self.eps, self.theta, repr(self.eng)))

    def __eq__(self, other):
        return isinstance(other, Model) and hash(self) == hash(other)

    # ---------------------------------------------------------- weights
    @functools.partial(jax.jit, static_argnums=0)
    def _layer_weights(self, words, nids):
        d, F, hd = self.d, self.F, self.hd
        shapes = [(d, self.H * hd), (d, self.KV * hd), (d, self.KV * hd),
                  (self.H * hd, d), (d, F), (d, F), (F, d)]
        # the weights in the configuration's dtype, computed in float32
        return [W.by_id(words, nids[k], s, "layer0.mixer.wq",
                        self.dtype).astype(jnp.float32)
                for k, s in enumerate(shapes)]

    def layer_weights(self, i):
        names = [f"layer{i}.mixer.{k}" for k in ("wq", "wk", "wv", "wo")] \
            + [f"layer{i}.ffn.{k}" for k in ("gate", "up", "down")]
        return self._layer_weights(self.words, jnp.asarray(
            np.array([W.name_id(n) for n in names], np.uint32)))

    # ------------------------------------------------------------ pass
    @functools.partial(jax.jit, static_argnums=0)
    def _embed(self, words, tokens):
        return W.rows_of(words, "embed.w", tokens, (self.V, self.d),
                         self.dtype).astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def _fuse(self, j, words, h, rows):
        S = h.shape[0]
        tid = jnp.broadcast_to(jnp.arange(self.T), (S, self.T))
        r = W.rows_of(words, f"engram{j}.tables",
                      tid * self.eng["table_vocab"] + rows,
                      (self.T, self.eng["table_vocab"], self.ehd),
                      self.dtype)
        r = _rms(r.astype(jnp.float32).reshape(S, -1), self.eps)
        proj = W.logical(words, f"engram{j}.proj", (self.fuse, self.d),
                         self.dtype).astype(jnp.float32)
        gate = W.logical(words, f"engram{j}.gate", (self.d, self.d),
                         self.dtype).astype(jnp.float32)
        g = jax.nn.sigmoid(_mm(h, gate, self.prec))
        return h + g * _mm(r, proj, self.prec)

    @functools.partial(jax.jit, static_argnums=0)
    def _block(self, h, ws):
        wq, wk, wv, wo, wg, wu, wd = ws
        S = h.shape[0]
        pos = jnp.arange(S)
        x = _rms(h, self.eps)
        q = _rope(_mm(x, wq, self.prec).reshape(S, self.H, self.hd), pos,
                  self.theta)
        k = _rope(_mm(x, wk, self.prec).reshape(S, self.KV, self.hd), pos,
                  self.theta)
        v = _mm(x, wv, self.prec).reshape(S, self.KV, self.hd)
        g = self.H // self.KV
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
            / math.sqrt(self.hd)
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        h = h + _mm(o.reshape(S, -1), wo, self.prec)
        x = _rms(h, self.eps)
        a = jax.nn.silu(_mm(x, wg, self.prec)) * _mm(x, wu, self.prec)
        return h + _mm(a, wd, self.prec)

    @functools.partial(jax.jit, static_argnums=0)
    def _head(self, words, h, targets):
        w = W.logical(words, "head.w", (self.d, self.V),
                      self.dtype).astype(jnp.float32)
        logits = _mm(_rms(h, self.eps), w, self.prec)
        at = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return logits.max(-1), at, jnp.argmax(logits, -1).astype(jnp.int32)

    def final_hidden(self, seqs: list, length: int) -> list:
        """Final hidden states, one (len(s), d) array per token sequence
        ``s`` of ``seqs`` (each one full causal pass). Sequences are
        padded at the end to ``length`` so that each piece compiles once;
        causal attention keeps the padding out of the real positions."""
        if max(len(s) for s in seqs) > length:
            raise ValueError(f"a sequence is longer than {length}")
        toks = [np.pad(np.asarray(s, np.int64), (0, length - len(s)))
                for s in seqs]
        hs = [self._embed(self.words, jnp.asarray(t, jnp.int32))
              for t in toks]
        rows = [jnp.asarray(ngram_indices(self.eng, t), jnp.int32)
                if self.eng else None for t in toks]
        for i in range(self.c["num_hidden_layers"]):
            if i in self.eng_layers:
                j = self.eng_layers.index(i)
                hs = [self._fuse(j, self.words, h, r)
                      for h, r in zip(hs, rows)]
            ws = self.layer_weights(i)
            hs = [self._block(h, ws) for h in hs]
            del ws
        return [h[:len(s)] for h, s in zip(hs, seqs)]

    def head(self, h, targets, length: int):
        """(max logit, logit of ``targets``, argmax) at each row of ``h``,
        computed ``length`` rows at a time."""
        out = [], [], []
        for a in range(0, h.shape[0], length):
            blk = h[a:a + length]
            n = blk.shape[0]
            hp = jnp.pad(blk, ((0, length - n), (0, 0)))
            tp = jnp.pad(jnp.asarray(targets[a:a + length], jnp.int32),
                         (0, length - n))
            for acc, x in zip(out, self._head(self.words, hp, tp)):
                acc.append(np.asarray(x)[:n])
        return tuple(np.concatenate(x) for x in out)
