"""Plain float32 reference of a DeepSeek-V2/V3-style decoder with Engram
layers: latent attention (MLA) in every layer, dense SwiGLU layers, then
expert layers.

The published math, non-absorbed, in ``jax.numpy`` at
``Precision.HIGHEST``. Per layer, pre-norm:

- MLA: ``c_q = RMSNorm(x W_dq)``, ``q = c_q W_uq`` (or ``q = x W_q``
  without ``q_lora_rank``), split per head into nope and rope parts;
  ``[c_kv | k_r] = x W_dkv``, ``c_kv = RMSNorm(c_kv)``,
  ``k_nope = c_kv W_uk``, ``v = c_kv W_uv``; the rope part of the key is
  one per token, shared by every head; the score is
  ``(q_nope . k_nope + q_rope . k_rope) * softmax_scale``, causal softmax,
  then ``W_o``.
- RoPE with YaRN where ``rope_scaling.type`` is ``yarn``: DeepSeek's
  correction range and linear ramp between the interpolated and the
  original frequencies (``beta_fast``, ``beta_slow``,
  ``original_max_position_embeddings``, ``factor``);
  ``softmax_scale = mscale(factor, mscale_all_dim)^2 / sqrt(dn + dr)``
  with ``mscale(f, m) = 0.1 m ln f + 1`` for f > 1, and cos and sin
  times ``mscale(f, mscale) / mscale(f, mscale_all_dim)``.
- Dense SwiGLU for ``i < first_k_dense_replace``; after that, every
  ``moe_layer_freq`` layers, an expert layer (``expert_layer``): router
  logits in float32; ``softmax``/``greedy`` takes the top-k of the
  softmax; ``sigmoid``/``noaux_tc`` takes ``s = sigmoid(logits)``,
  chooses by ``s + b`` (``b`` the selection bias, logical weight
  ``layer{i}.ffn.router_bias``) within the ``topk_group`` groups of
  ``n_group`` whose two best biased scores sum highest, and weights the
  chosen by ``s``; weights divided by their sum over all chosen experts
  when ``norm_topk_prob``, times ``routed_scaling_factor``. Output: the
  shared experts plus the routed experts held here.

Held experts: one chip of ``deployment.expert_parallel`` holds the
logical experts ``[0, n_routed_experts)`` of the ``w_gu``/``w_down``
stacks (chip 0 of the group), so their weights do not depend on the
share, and the router is ``n_routed_experts * expert_parallel`` wide.
What the absent experts would add is left out, as in the program.

Embedding, Engram fusion, n-gram hash, final norm and head are
``dense_engram``'s. It imports nothing of the program under test; weights
come from ``chipbench.weights`` by logical name, one layer at a time (the
router and its bias in float32, as the program holds them).

Departures from the published models:

- RoPE is rotate-half over the rope dims, as in ``dense_engram`` and the
  program; DeepSeek rotates interleaved pairs. The two differ by a fixed
  relabelling of the rope columns of ``W_uq`` and ``W_dkv``, which random
  weights cannot tell apart.
- Under ``noaux_tc`` the experts of the groups not kept are masked with
  -inf, as DeepSeek-V3's inference code does (its Hugging Face code masks
  with 0, which can pick a masked expert when biased scores are
  negative).

``precision`` other than ``"float32"`` is the control: every matmul's
operands rounded to the next precision below the one the configuration
states (``"fp8"`` below bfloat16, ``"bfloat16"`` below float32), as in
``dense_engram``.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counters, loader
from chipbench import weights as W
from chipbench.references import dense_engram as D

HIGHEST = D.HIGHEST


# ------------------------------------------------------------------- rope

def mscale(factor: float, m: float) -> float:
    """YaRN's attention scale for a context ``factor`` times longer."""
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_table(c: dict, dim: int) -> tuple:
    """(inverse frequencies (dim/2,) float32, cos/sin multiplier, softmax
    multiplier) of the file's RoPE over ``dim`` rope dims."""
    theta = float(c["rope_theta"])
    base = (1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32)
                             / dim))).astype(np.float32)
    rs = loader.rope_scaling(c)
    if rs is None:
        return base, 1.0, 1.0
    if not loader.is_yarn(rs):
        raise ValueError(f"rope_scaling {rs!r} is not modelled")
    f = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def corr(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(rs.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                   # 1: the original frequency
    inv = (base / f * (1 - keep) + base * keep).astype(np.float32)
    m_all = rs.get("mscale_all_dim", 0)
    return inv, mscale(f, rs.get("mscale", 1)) / mscale(f, m_all), \
        mscale(f, m_all) ** 2


def _rope(x, pos, inv_freq, mult):
    """Rotate-half RoPE. x (S, H, D), pos (S,), inv_freq (D/2,)."""
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = (jnp.cos(ang) * mult)[:, None], (jnp.sin(ang) * mult)[:, None]
    d = x.shape[-1]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------- expert layer

def route(logits, bias, r: dict, top_k: int):
    """Chosen experts (T, top_k) and their weights (T, top_k) from router
    logits (T, routed), the selection bias (routed,) or None, and routing
    ``r`` (``loader.routing``)."""
    if r["scoring_func"] == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        s = jax.nn.softmax(logits, axis=-1)
    choice = s
    if r["topk_method"] == "noaux_tc":
        choice = s + bias
        T, E = s.shape
        G = int(r["n_group"])
        per = E // G
        best2 = jax.lax.top_k(choice.reshape(T, G, per), min(2, per))[0]
        _, kept = jax.lax.top_k(best2.sum(-1), int(r["topk_group"]))
        in_kept = jnp.zeros((T, G), bool).at[
            jnp.arange(T)[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(in_kept, per, axis=1), choice,
                           -jnp.inf)
    _, ids = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if r["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return ids, w * r["routed_scaling_factor"]


def _swiglu(x, wg, wu, wd, prec):
    return D._mm(jax.nn.silu(D._mm(x, wg, prec)) * D._mm(x, wu, prec), wd,
                 prec)


def expert_layer(x, ws: dict, r: dict, top_k: int, prec: str = "float32",
                 first: int = 0):
    """One expert layer's output for normed input x (T, d).

    ``ws``: ``router`` (d, routed), ``router_bias`` (routed,) under
    ``noaux_tc``, ``w_gu`` (count, d, 2F) and ``w_down`` (count, F, d) of
    the experts ``[first, first + count)`` held here, and, where they are
    counted here, the shared experts ``shared.{gate,up,down}``. Routing
    is over all ``routed`` experts; only the held ones are computed."""
    ids, w = route(D._mm(x, ws["router"], prec), ws.get("router_bias"), r,
                   top_k)
    count, F = ws["w_gu"].shape[0], ws["w_down"].shape[1]
    here = first + jnp.arange(count)
    comb = jnp.sum(jnp.where(ids[:, :, None] == here, w[:, :, None], 0.0),
                   axis=1)                                   # (T, count)
    gu = jax.vmap(lambda m: D._mm(x, m, prec))(ws["w_gu"])   # (count, T, 2F)
    a = jax.nn.silu(gu[..., :F]) * gu[..., F:]
    y = jax.vmap(lambda ai, m: D._mm(ai, m, prec))(a, ws["w_down"])
    out = jnp.einsum("etd,te->td", y, comb, precision=HIGHEST)
    if "shared.gate" in ws:
        out = out + _swiglu(x, ws["shared.gate"], ws["shared.up"],
                            ws["shared.down"], prec)
    return out


# ----------------------------------------------------------------- model

class Model(D.Model):
    """One configuration's reference for one seed (``dense_engram.Model``'s
    interface: ``final_hidden`` and ``head``)."""

    def __init__(self, config: dict, seed: int, precision: str = "float32"):
        super().__init__(config, seed, precision)
        c = config
        self.ql = c.get("q_lora_rank")
        self.kvr = c["kv_lora_rank"]
        self.dn, self.dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
        self.dv = c["v_head_dim"]
        inv, self.rope_mult, soft = rope_table(c, self.dr)
        self.inv_freq = tuple(float(v) for v in inv)
        self.scale = soft / math.sqrt(self.dn + self.dr)
        self.experts = frozenset(counters.expert_layers(c))
        self.held, self.routed = counters.expert_share(c)
        self.top_k = c.get("num_experts_per_tok")
        self.Fe = c.get("moe_intermediate_size")
        self.ns = c.get("n_shared_experts") or 0
        self.routing = loader.routing(c)
        self.key = json.dumps([
            D.Model.__hash__(self), self.ql, self.kvr, self.dn, self.dr,
            self.dv, self.inv_freq, self.rope_mult, self.scale,
            sorted(self.experts), self.held, self.routed, self.top_k,
            self.Fe, self.ns, sorted(self.routing.items())])

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, Model) and self.key == other.key

    # ---------------------------------------------------------- weights
    def specs(self, i: int) -> list:
        """(name within the layer, shape, dtype) of layer ``i``'s
        weights."""
        d, H, dt = self.d, self.H, self.dtype
        dn, dr, dv, kvr = self.dn, self.dr, self.dv, self.kvr
        if self.ql:
            out = [("mixer.wdq", (d, self.ql), dt),
                   ("mixer.wuq", (self.ql, H * (dn + dr)), dt)]
        else:
            out = [("mixer.wq", (d, H * (dn + dr)), dt)]
        out += [("mixer.wdkv", (d, kvr + dr), dt),
                ("mixer.wuk", (kvr, H * dn), dt),
                ("mixer.wuv", (kvr, H * dv), dt),
                ("mixer.wo", (H * dv, d), dt)]
        if i not in self.experts:
            return out + [("ffn.gate", (d, self.F), dt),
                          ("ffn.up", (d, self.F), dt),
                          ("ffn.down", (self.F, d), dt)]
        Fe = self.Fe
        out.append(("ffn.router", (d, self.routed), "float32"))
        if self.routing["topk_method"] == "noaux_tc":
            out.append(("ffn.router_bias", (self.routed,), "float32"))
        out += [("ffn.w_gu", (self.held, d, 2 * Fe), dt),
                ("ffn.w_down", (self.held, Fe, d), dt)]
        if self.ns:
            out += [("ffn.shared.gate", (d, self.ns * Fe), dt),
                    ("ffn.shared.up", (d, self.ns * Fe), dt),
                    ("ffn.shared.down", (self.ns * Fe, d), dt)]
        return out

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def _make(self, specs, words, nids):
        # in the stated dtype, computed in float32; ``layer0.<name>`` sets
        # the scale, the traced name id the values
        return [W.by_id(words, nids[k], shape, "layer0." + name,
                        dtype).astype(jnp.float32)
                for k, (name, shape, dtype) in enumerate(specs)]

    def layer_weights(self, i: int) -> dict:
        """Layer ``i``'s weights by their name within the layer."""
        specs = tuple(self.specs(i))
        nids = jnp.asarray(np.array([W.name_id(f"layer{i}.{n}")
                                     for n, _, _ in specs], np.uint32))
        vals = self._make(specs, self.words, nids)
        return {n.split(".", 1)[1]: v for (n, _, _), v in zip(specs, vals)}

    # ------------------------------------------------------------ pass
    def _attention(self, x, ws):
        p, H, eps = self.prec, self.H, self.eps
        dn, dr, dv, kvr = self.dn, self.dr, self.dv, self.kvr
        S = x.shape[0]
        pos = jnp.arange(S)
        if self.ql:
            q = D._mm(D._rms(D._mm(x, ws["wdq"], p), eps), ws["wuq"], p)
        else:
            q = D._mm(x, ws["wq"], p)
        q = q.reshape(S, H, dn + dr)
        kv = D._mm(x, ws["wdkv"], p)
        c_kv = D._rms(kv[:, :kvr], eps)
        k_rope = _rope(kv[:, None, kvr:], pos, self.inv_freq,
                       self.rope_mult)[:, 0]                 # (S, dr)
        k_nope = D._mm(c_kv, ws["wuk"], p).reshape(S, H, dn)
        v = D._mm(c_kv, ws["wuv"], p).reshape(S, H, dv)
        q_rope = _rope(q[..., dn:], pos, self.inv_freq, self.rope_mult)
        s = (jnp.einsum("qhd,khd->hqk", q[..., :dn], k_nope,
                        precision=HIGHEST)
             + jnp.einsum("qhd,kd->hqk", q_rope, k_rope,
                          precision=HIGHEST)) * self.scale
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                       precision=HIGHEST)
        return D._mm(o.reshape(S, H * dv), ws["wo"], p)

    @functools.partial(jax.jit, static_argnums=0)
    def _block(self, h, ws):
        h = h + self._attention(D._rms(h, self.eps), ws)
        x = D._rms(h, self.eps)
        if "router" in ws:
            return h + expert_layer(x, ws, self.routing, self.top_k,
                                    self.prec)
        return h + _swiglu(x, ws["gate"], ws["up"], ws["down"], self.prec)
