"""Block assembly: heterogeneous layers, engram-segmented stack, layer scan.

The layer stack is split into *segments* at the Engram insertion points
(DESIGN.md §4.5: the retrieval for segment j+1 has no data dependency on
segment j's computation, which is exactly the paper's prefetch window).
Within a segment, layers are grouped into an optional unrolled prefix plus
a periodic tail that is stacked and scanned (compact HLO for 60+-layer
models); ``RunFlags.scan_layers=False`` unrolls everything (used by the
dry-run when exact per-op cost accounting is wanted).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding.rules import shard
from .attention import (attn_defs, attention, decode_attention, init_kv_cache,
                        layer_view)
from .layers import mlp, mlp_defs, rmsnorm, rmsnorm_defs
from .mamba import init_mamba_cache, mamba_defs, mamba_forward
from .mla import init_mla_cache, mla_attention, mla_decode, mla_defs
from .moe import moe_defs, moe_ffn
from .params import tree_stack_defs
from .xlstm import (init_xlstm_cache, mlstm_defs, mlstm_forward, slstm_defs,
                    slstm_forward)


@dataclass(frozen=True)
class RunFlags:
    """Runtime knobs that don't change parameters, only execution."""
    scan_layers: bool = True
    remat: bool = False
    moe_strategy: str = "gather"      # dense | ragged | gather | alltoall
    engram_strategy: Optional[str] = None
    q_chunk: int = 1024
    kv_chunk: int = 1024
    chunk_threshold: int = 2048
    logits_chunk: int = 2048
    # --- perf-iteration knobs (EXPERIMENTS.md §Perf) ------------------
    attn_bf16_scores: bool = False    # score matmuls via preferred_element_type
    #   instead of materializing f32 copies of the KV cache
    decode_window_slice: bool = False # local layers: slice the cache to the
    #   window during decode instead of masking the full context
    xent_remat: bool = False          # recompute logits chunks in backward
    embed_local_gather: bool = False  # vocab-sharded embed: masked local
    #   take + psum instead of XLA's table all-gather


def _sig(cfg: ModelConfig, i: int) -> tuple:
    return (cfg.layer_types[i], cfg.attn_kinds[i], cfg.ffn_types[i])


# ---------------------------------------------------------------------------
# segment planning
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Segment:
    layers: tuple[int, ...]          # absolute layer indices
    prefix_len: int                  # first prefix_len layers unrolled
    period: int                      # 0 => fully unrolled
    n_periods: int


def segment_plan(cfg: ModelConfig) -> list[Segment]:
    L = cfg.n_layers
    bounds = sorted({0, L, *[l for l in cfg.engram_layers() if 0 < l < L]})
    segs = []
    for a, b in zip(bounds[:-1], bounds[1:]):
        idxs = tuple(range(a, b))
        segs.append(_plan_one(cfg, idxs))
    return segs


def _plan_one(cfg: ModelConfig, idxs: tuple[int, ...]) -> Segment:
    n = len(idxs)
    sigs = [_sig(cfg, i) for i in idxs]
    best = None
    for k in range(0, min(n, 9)):                 # prefix length
        rest = n - k
        for p in range(1, 9):
            if rest < 2 * p or rest % p:
                continue
            pat = sigs[k:k + p]
            if all(sigs[k + j] == pat[j % p] for j in range(rest)):
                cand = (k + p, k, p)              # cost = unrolled layers
                if best is None or cand < best:
                    best = cand
                break
    if best is None:
        return Segment(idxs, n, 0, 0)
    _, k, p = best
    return Segment(idxs, k, p, (n - k) // p)


# ---------------------------------------------------------------------------
# per-block defs / apply
# ---------------------------------------------------------------------------

def block_defs(cfg: ModelConfig, i: int, dtype: str):
    t, kind, ffn = _sig(cfg, i)
    d = {"ln1": rmsnorm_defs(cfg.d_model)}
    if t == "attn":
        d["mixer"] = mla_defs(cfg, dtype) if cfg.attn_impl == "mla" \
            else attn_defs(cfg, dtype)
    elif t == "mamba":
        d["mixer"] = mamba_defs(cfg, dtype)
    elif t == "mlstm":
        d["mixer"] = mlstm_defs(cfg, dtype)
    elif t == "slstm":
        d["mixer"] = slstm_defs(cfg, dtype)
    else:
        raise ValueError(t)
    if cfg.post_block_norm:
        d["post_ln1"] = rmsnorm_defs(cfg.d_model)
    if ffn != "none":
        d["ln2"] = rmsnorm_defs(cfg.d_model)
        d["ffn"] = moe_defs(cfg, dtype) if ffn == "moe" \
            else mlp_defs(cfg.d_model, cfg.d_ff, dtype)
        if cfg.post_block_norm:
            d["post_ln2"] = rmsnorm_defs(cfg.d_model)
    return d


def init_block_cache(cfg: ModelConfig, i: int, batch: int, max_len: int,
                     dtype):
    t = cfg.layer_types[i]
    if t == "attn":
        if cfg.attn_impl == "mla":
            return init_mla_cache(cfg, batch, max_len, dtype)
        return init_kv_cache(cfg, batch, max_len, dtype)
    if t == "mamba":
        return init_mamba_cache(cfg, batch, dtype)
    return init_xlstm_cache(cfg, t, batch, dtype)


def apply_block(cfg: ModelConfig, flags: RunFlags, sig: tuple, params, h,
                positions, cache, mode: str, layer=None):
    """One transformer block. mode: train | prefill | decode.

    Returns (h, new_cache, aux). ``cache`` is None in train mode (recurrent
    mixers start from zeros; attention keeps no state).

    ``layer`` (decode only): ``cache`` is a layer stack and this block is
    layer ``layer`` of it; the whole stack is returned, updated. Attention
    writes only its new rows into it; a recurrent mixer replaces its whole
    (small) state, which is written back at ``layer``."""
    t, kind, ffn = sig
    aux = jnp.zeros((), jnp.float32)
    pre = rmsnorm(params["ln1"], h, cfg.norm_eps)
    if t == "attn":
        if mode == "decode":
            if cfg.attn_impl == "mla":
                out, new_cache = mla_decode(cfg, params["mixer"], pre, cache,
                                            positions, layer=layer,
                                            bf16_scores=flags.attn_bf16_scores)
            else:
                out, new_cache = decode_attention(
                    cfg, params["mixer"], pre, cache, positions, kind,
                    layer=layer, bf16_scores=flags.attn_bf16_scores,
                    window_slice=flags.decode_window_slice)
        else:
            if cfg.attn_impl == "mla":
                out, kv = mla_attention(cfg, params["mixer"], pre, positions,
                                        kind, q_chunk=flags.q_chunk,
                                        kv_chunk=flags.kv_chunk,
                                        chunk_threshold=flags.chunk_threshold,
                                        bf16_scores=flags.attn_bf16_scores)
            else:
                out, kv = attention(cfg, params["mixer"], pre, positions, kind,
                                    q_chunk=flags.q_chunk,
                                    kv_chunk=flags.kv_chunk,
                                    chunk_threshold=flags.chunk_threshold,
                                    bf16_scores=flags.attn_bf16_scores)
            new_cache = kv if mode == "prefill" else None
    else:
        mixer = {"mamba": mamba_forward, "mlstm": mlstm_forward,
                 "slstm": slstm_forward}.get(t)
        if mixer is None:
            raise ValueError(t)
        c = jax.tree.map(lambda x: layer_view(x, layer), cache)
        out, new_cache = mixer(cfg, params["mixer"], pre, c)
        if layer is not None:
            new_cache = jax.tree.map(
                lambda x, n: jax.lax.dynamic_update_index_in_dim(
                    x, n.astype(x.dtype), layer, 0), cache, new_cache)
    if cfg.post_block_norm:
        out = rmsnorm(params["post_ln1"], out, cfg.norm_eps)
    h = h + out

    if ffn != "none":
        pre2 = rmsnorm(params["ln2"], h, cfg.norm_eps)
        if ffn == "moe":
            out2, aux = moe_ffn(cfg, params["ffn"], pre2,
                                strategy=flags.moe_strategy)
        else:
            out2 = mlp(params["ffn"], pre2, cfg.ffn_act)
        if cfg.post_block_norm:
            out2 = rmsnorm(params["post_ln2"], out2, cfg.norm_eps)
        h = h + out2
    # "seq" resolves to () by default (baseline: replicated over model);
    # binding it to ("model",) turns the between-block residual into
    # sequence-parallel form — GSPMD then lowers the TP output reductions
    # as reduce-scatter + all-gather around the norms (§Perf iteration C4)
    h = shard(h, "batch", "seq", None)
    return h, new_cache, aux


# ---------------------------------------------------------------------------
# segment defs / caches / apply
# ---------------------------------------------------------------------------

def segment_defs(cfg: ModelConfig, seg: Segment, dtype: str):
    prefix = [block_defs(cfg, i, dtype) for i in seg.layers[:seg.prefix_len]]
    stack = []
    if seg.period:
        for pos in range(seg.period):
            instances = [block_defs(cfg, seg.layers[seg.prefix_len + r * seg.period + pos], dtype)
                         for r in range(seg.n_periods)]
            stack.append(tree_stack_defs(instances))
    return {"prefix": prefix, "stack": stack}


def init_segment_cache(cfg: ModelConfig, seg: Segment, batch: int,
                       max_len: int, dtype):
    prefix = [init_block_cache(cfg, i, batch, max_len, dtype)
              for i in seg.layers[:seg.prefix_len]]
    stack = []
    if seg.period:
        for pos in range(seg.period):
            per = [init_block_cache(
                cfg, seg.layers[seg.prefix_len + r * seg.period + pos],
                batch, max_len, dtype) for r in range(seg.n_periods)]
            stack.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
    return {"prefix": prefix, "stack": stack}


def apply_segment(cfg: ModelConfig, flags: RunFlags, seg: Segment, params, h,
                  positions, cache, mode: str):
    """Returns (h, new_cache_or_None, aux_sum)."""
    aux_tot = jnp.zeros((), jnp.float32)
    keep_cache = mode != "train"
    new_prefix = []
    for j in range(seg.prefix_len):
        li = seg.layers[j]
        c = cache["prefix"][j] if cache is not None else None
        h, nc, aux = apply_block(cfg, flags, _sig(cfg, li),
                                 params["prefix"][j], h, positions, c, mode)
        aux_tot += aux
        new_prefix.append(nc)
    new_stack = []
    if seg.period:
        sigs = [_sig(cfg, seg.layers[seg.prefix_len + pos])
                for pos in range(seg.period)]

        def period_body(carry, xs):
            h_, aux_ = carry
            p_stacked, c_stacked = xs
            ncs = []
            for pos in range(seg.period):
                c = c_stacked[pos] if c_stacked is not None else None
                h_, nc, aux = apply_block(cfg, flags, sigs[pos],
                                          p_stacked[pos], h_, positions, c,
                                          mode)
                aux_ = aux_ + aux
                ncs.append(nc)
            y = tuple(ncs) if keep_cache else None
            return (h_, aux_), y

        body = period_body
        if flags.remat and mode == "train":
            body = jax.checkpoint(period_body)

        def decode_body(carry, xs):
            # the stacked cache rides in the carry (as scan xs -> ys it
            # would need a second full-size cache buffer for the outputs)
            # and each block updates layer i of it in place: attention
            # writes one row a slot, recurrent state is written back whole
            h_, aux_, c_all = carry
            p_stacked, i = xs
            c_all = list(c_all)
            for pos in range(seg.period):
                h_, c_all[pos], aux = apply_block(
                    cfg, flags, sigs[pos], p_stacked[pos], h_, positions,
                    c_all[pos], mode, layer=i)
                aux_ = aux_ + aux
            return (h_, aux_, tuple(c_all)), None

        p_xs = tuple(params["stack"])
        c_xs = tuple(cache["stack"]) if cache is not None else None
        if flags.scan_layers and c_xs is not None:
            # only decode passes caches
            (h, aux_tot, c_new), _ = jax.lax.scan(
                decode_body, (h, aux_tot, c_xs),
                (p_xs, jnp.arange(seg.n_periods)))
            new_stack = list(c_new)
        elif flags.scan_layers:
            # train and prefill: no cache in, prefill's caches out as ys
            (h, aux_tot), ys = jax.lax.scan(
                lambda c, p: body(c, (p, None)), (h, aux_tot), p_xs)
            new_stack = list(ys) if keep_cache else []
        else:
            ys = []
            for r in range(seg.n_periods):
                p_r = jax.tree.map(lambda x: x[r], p_xs)
                c_r = (jax.tree.map(lambda x: x[r], c_xs)
                       if c_xs is not None else None)
                (h, aux_tot), y = body((h, aux_tot), (p_r, c_r))
                ys.append(y)
            if keep_cache:
                new_stack = list(jax.tree.map(lambda *x: jnp.stack(x), *ys))
    new_cache = ({"prefix": new_prefix, "stack": new_stack}
                 if keep_cache else None)
    return h, new_cache, aux_tot
