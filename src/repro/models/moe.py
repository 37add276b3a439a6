"""Mixture-of-Experts: router + three execution strategies.

  dense     — every expert on every token, combined by router weights.
              O(T·E·f) FLOPs; numerical reference for tests only.
  gather    — expert-parallel via shard_map over the expert ("model") axis.
              Tokens stay data-sharded (replicated along the expert axis
              inside the shard_map); each shard slices the globally-sorted
              row window belonging to its local experts (fixed capacity),
              runs a grouped GEMM (jax.lax.ragged_dot), scatter-adds its
              partial outputs and psums over the expert axis.
  alltoall  — production dispatch: tokens additionally sequence-sharded over
              the expert axis; rows are exchanged with fixed per-peer
              capacity via all_to_all, grouped-GEMM'd on the owner shard and
              returned by the reverse all_to_all. Collective bytes scale with
              top_k·capacity·d instead of the full gathered activation.

Every strategy returns (out, aux_loss). Shared experts run as a plain
TP-sharded dense MLP outside the shard_map.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig, MoEConfig
from ..sharding.rules import current_ctx, mesh_axes, shard
from .layers import mlp, mlp_defs
from .params import pd


def moe_defs(cfg: ModelConfig, dtype: str):
    m, d = cfg.moe, cfg.d_model
    defs = {
        "router": pd(d, m.n_experts, axes=(None, None), dtype="float32"),
        # fused gate+up: (E, d, 2f); down: (E, f, d)
        "w_gu": pd(m.n_experts, d, 2 * m.d_ff_expert,
                   axes=("experts", None, None), dtype=dtype),
        "w_down": pd(m.n_experts, m.d_ff_expert, d,
                     axes=("experts", None, None), dtype=dtype),
    }
    if m.n_shared > 0:
        defs["shared"] = mlp_defs(d, m.n_shared * m.d_ff_expert, dtype)
    return defs


def _route(m: MoEConfig, params, x_flat):
    """x_flat (T, d) -> (eids (T,k), weights (T,k), aux_loss)."""
    logits = x_flat.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, eids = jax.lax.top_k(probs, m.top_k)
    w = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    w = (w * m.router_scale).astype(x_flat.dtype)
    # switch-style load-balance loss
    frac = jnp.zeros((m.n_experts,), jnp.float32).at[eids.reshape(-1)].add(
        1.0 / eids.size)
    aux = m.n_experts * jnp.sum(frac * probs.mean(0)) * m.aux_loss_coef
    return eids, w, aux


def _expert_mlp_rows(params, rows, group_sizes, act: str):
    """Grouped GEMM over contiguous expert groups via ragged_dot."""
    f = params["w_down"].shape[-2]
    h = jax.lax.ragged_dot(rows, params["w_gu"], group_sizes)
    g, u = h[..., :f], h[..., f:]
    a = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g, approximate=True)
    return jax.lax.ragged_dot(a, params["w_down"], group_sizes)


# ---------------------------------------------------------------------------
# dense reference
# ---------------------------------------------------------------------------

def moe_dense(cfg: ModelConfig, params, x):
    m = cfg.moe
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    eids, w, aux = _route(m, params, xf)
    h = jnp.einsum("td,edf->tef", xf, params["w_gu"])
    f = m.d_ff_expert
    g, u = h[..., :f], h[..., f:]
    a = jax.nn.silu(g) if cfg.ffn_act == "silu" else jax.nn.gelu(g, approximate=True)
    y = jnp.einsum("tef,efd->ted", a, params["w_down"])
    comb = jnp.zeros((xf.shape[0], m.n_experts), x.dtype)
    comb = comb.at[jnp.arange(xf.shape[0])[:, None], eids].add(w)
    out = jnp.einsum("ted,te->td", y, comb)
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# local sorted-ragged core (reused by single-device path and EP-gather)
# ---------------------------------------------------------------------------

def _ep_local(cfg: ModelConfig, params_local, xf, eids, w, e0,
              e_loc: int, cap: int):
    """Partial MoE output for experts [e0, e0+e_loc) with capacity ``cap``.

    xf (T,d); eids/w (T,k); e0 may be traced (shard index). Returns (T, d)
    partial output (zeros for tokens not routed here). params_local
    w_gu/w_down are (e_loc, ...) slices.
    """
    T, d = xf.shape
    k = eids.shape[-1]
    R = T * k
    flat_e = eids.reshape(R)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = w.reshape(R)
    order = jnp.argsort(flat_e)                       # stable
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    start = jnp.sum(flat_e < e0)                      # first local row
    idx = start + jnp.arange(cap)
    in_range = idx < R
    idx = jnp.minimum(idx, R - 1)
    sel_e, sel_t, sel_w = se[idx], st[idx], sw[idx]
    valid = in_range & (sel_e >= e0) & (sel_e < e0 + e_loc)
    rows = xf[sel_t] * valid[:, None].astype(xf.dtype)
    group_sizes = jnp.bincount(
        jnp.where(valid, sel_e - e0, e_loc).astype(jnp.int32),
        length=e_loc + 1)[:e_loc].astype(jnp.int32)
    out_rows = _expert_mlp_rows(params_local, rows, group_sizes, cfg.ffn_act)
    out_rows = out_rows * (sel_w * valid.astype(sel_w.dtype))[:, None]
    tgt = jnp.where(valid, sel_t, T)                  # drop invalid at row T
    out = jnp.zeros((T + 1, d), xf.dtype).at[tgt].add(out_rows)
    return out[:T]


def moe_ragged_local(cfg: ModelConfig, params, x):
    """Single-device sort+ragged_dot path (capacity = all rows; dropless)."""
    m = cfg.moe
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    eids, w, aux = _route(m, params, xf)
    cap = xf.shape[0] * m.top_k
    out = _ep_local(cfg, params, xf, eids, w, 0, m.n_experts, cap)
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# EP-gather (shard_map over expert axis; tokens replicated along it)
# ---------------------------------------------------------------------------

def moe_ep_gather(cfg: ModelConfig, params, x, *, token_chunk: int = 4096):
    m = cfg.moe
    ctx = current_ctx()
    e_axes = mesh_axes("experts")
    if ctx is None or len(e_axes) != 1 or ctx.axis_prod(e_axes) == 1:
        return moe_ragged_local(cfg, params, x)
    ax = e_axes[0]
    ep = ctx.axis_prod(e_axes)
    if m.n_experts % ep != 0:
        return moe_ragged_local(cfg, params, x)
    e_loc = m.n_experts // ep
    B, S, d = x.shape

    def local_fn(w_gu, w_down, router, xl):
        pl = {"w_gu": w_gu, "w_down": w_down, "router": router}
        xf = xl.reshape(-1, d)
        T = xf.shape[0]
        eids, wts, aux = _route(m, pl, xf)
        e0 = jax.lax.axis_index(ax) * e_loc
        chunk = token_chunk if (T % token_chunk == 0 and T > token_chunk) else T
        nch = T // chunk
        cap = int(math.ceil(chunk * m.top_k * e_loc / m.n_experts
                            * m.capacity_factor))
        cap = max(16, min(cap, chunk * m.top_k))

        def one(args):
            xc, ec, wc = args
            return _ep_local(cfg, pl, xc, ec, wc, e0, e_loc, cap)

        if nch > 1:
            xs = (xf.reshape(nch, chunk, d), eids.reshape(nch, chunk, -1),
                  wts.reshape(nch, chunk, -1))
            out = jax.lax.map(one, xs).reshape(T, d)
        else:
            out = one((xf, eids, wts))
        out = jax.lax.psum(out, ax)
        aux = jax.lax.pmean(aux, ax)
        return out.reshape(xl.shape), aux

    # divisibility-aware batch spec (decode/long shapes can have B < |data|)
    spec_x = ctx.spec_for(x.shape, ("batch", None, None))
    fn = jax.shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(ax, None, None), P(ax, None, None), P(None, None), spec_x),
        out_specs=(spec_x, P()),
        check_vma=False,
    )
    return fn(params["w_gu"], params["w_down"], params["router"], x)


# ---------------------------------------------------------------------------
# EP-all-to-all (tokens additionally sequence-sharded over expert axis)
# ---------------------------------------------------------------------------

def moe_ep_alltoall(cfg: ModelConfig, params, x):
    m = cfg.moe
    ctx = current_ctx()
    e_axes = mesh_axes("experts")
    if ctx is None or len(e_axes) != 1 or ctx.axis_prod(e_axes) == 1:
        return moe_ragged_local(cfg, params, x)
    ax = e_axes[0]
    ep = ctx.axis_prod(e_axes)
    B, S, d = x.shape
    if m.n_experts % ep != 0 or S % ep != 0:
        return moe_ep_gather(cfg, params, x)
    e_loc = m.n_experts // ep

    def local_fn(w_gu, w_down, router, xl):
        pl = {"w_gu": w_gu, "w_down": w_down, "router": router}
        xf = xl.reshape(-1, d)                       # (T_dev, d)
        T = xf.shape[0]
        k = m.top_k
        eids, wts, aux = _route(m, pl, xf)
        R = T * k
        flat_e = eids.reshape(R)
        flat_t = jnp.repeat(jnp.arange(T), k)
        flat_w = wts.reshape(R)
        dest = flat_e // e_loc                       # owner shard per row
        order = jnp.argsort(dest)                    # stable: rows by peer
        s_dst, s_e, s_t = dest[order], flat_e[order], flat_t[order]
        cap = int(math.ceil(R / ep * m.capacity_factor))
        counts = jnp.bincount(dest, length=ep)
        starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(R) - starts[s_dst]          # rank within peer bucket
        ok = pos < cap
        pos_c = jnp.where(ok, pos, cap)              # overflow -> spill slot
        # send buffers have a spill slot at [:, cap] that is sliced away
        send_rows = jnp.zeros((ep, cap + 1, d), xf.dtype)
        send_le = jnp.full((ep, cap + 1), e_loc, jnp.int32)
        send_rid = jnp.full((ep, cap + 1), R, jnp.int32)
        send_rows = send_rows.at[s_dst, pos_c].set(xf[s_t])
        send_le = send_le.at[s_dst, pos_c].set((s_e % e_loc).astype(jnp.int32))
        send_rid = send_rid.at[s_dst, pos_c].set(order.astype(jnp.int32))
        send_rows, send_le = send_rows[:, :cap], send_le[:, :cap]
        send_rid = send_rid[:, :cap]
        # spilled slots were overwritten by later spills; re-mark validity:
        # a slot is valid iff its rid != R (never-written keeps R)
        recv_rows = jax.lax.all_to_all(send_rows, ax, 0, 0)
        recv_le = jax.lax.all_to_all(send_le, ax, 0, 0)
        # grouped GEMM on owner shard
        rr = recv_rows.reshape(ep * cap, d)
        rl = recv_le.reshape(ep * cap)
        o2 = jnp.argsort(rl)
        gs = jnp.bincount(rl, length=e_loc + 1)[:e_loc].astype(jnp.int32)
        out_rows = _expert_mlp_rows(pl, rr[o2], gs, cfg.ffn_act)
        inv = jnp.zeros_like(o2).at[o2].set(jnp.arange(o2.size))
        out_back = out_rows[inv].reshape(ep, cap, d)
        back = jax.lax.all_to_all(out_back, ax, 0, 0)
        # combine at source: back[p, c] answers send slot (p, c)
        rid = send_rid.reshape(ep * cap)             # original flat row ids
        valid = rid < R
        rid_s = jnp.minimum(rid, R - 1)
        w_r = jnp.where(valid, flat_w[rid_s], 0).astype(xf.dtype)
        t_r = jnp.where(valid, flat_t[rid_s], T)
        contrib = back.reshape(ep * cap, d) * w_r[:, None]
        out = jnp.zeros((T + 1, d), xf.dtype).at[t_r].add(contrib)[:T]
        return out.reshape(xl.shape), jax.lax.pmean(aux, ax)

    base = ctx.spec_for(x.shape, ("batch", None, None))
    b_entry = base[0] if len(base) > 0 else None
    spec_x = P(b_entry, ax, None)
    fn = jax.shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(ax, None, None), P(ax, None, None), P(None, None), spec_x),
        out_specs=(spec_x, P()),
        check_vma=False,
    )
    return fn(params["w_gu"], params["w_down"], params["router"], x)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def moe_ffn(cfg: ModelConfig, params, x, *, strategy: str = "gather"):
    """Full MoE ffn: routed experts (strategy) + shared experts. -> (out, aux)."""
    m = cfg.moe
    if strategy == "dense":
        out, aux = moe_dense(cfg, params, x)
    elif strategy == "ragged":
        out, aux = moe_ragged_local(cfg, params, x)
    elif strategy == "alltoall":
        out, aux = moe_ep_alltoall(cfg, params, x)
    else:
        out, aux = moe_ep_gather(cfg, params, x)
    if m.n_shared > 0:
        out = out + mlp(params["shared"], x, cfg.ffn_act)
    return out, aux
