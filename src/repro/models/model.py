"""Full model: frontends -> embedding -> engram-segmented stack -> head.

Step builders (the public API consumed by launch/, serving/ and train/):

  build_train_step(cfg, flags)   (params, batch) -> loss            [+grads via train/]
  build_prefill_step(cfg, flags) (params, batch) -> (logits, state)
  build_decode_step(cfg, flags)  (params, state, token) -> (logits, state)

The Engram retrieval for every Engram layer is issued *before* the block
stack (root-level ops depending only on token IDs) — the compiled program
can overlap the pool fetch with layers 0..k-1, which is the paper's
prefetch-window claim (§3.1/§3.2).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.engram import engram_defs, engram_fuse, retrieve
from ..core.hashing import (decode_engram_indices, engram_indices,
                            update_last_tokens)
from ..sharding.rules import shard
from .layers import (chunked_xent, embed_defs, embed_lookup, head_defs,
                     head_logits, rmsnorm, rmsnorm_defs)
from .params import pd, tree_abstract, tree_axes, tree_init
from .transformer import (RunFlags, Segment, apply_segment,
                          init_segment_cache, segment_defs, segment_plan)


# ---------------------------------------------------------------------------
# defs
# ---------------------------------------------------------------------------

def model_defs(cfg: ModelConfig):
    dtype = cfg.dtype
    defs = {
        "embed": embed_defs(cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": rmsnorm_defs(cfg.d_model),
        "segments": [segment_defs(cfg, seg, dtype)
                     for seg in segment_plan(cfg)],
    }
    if not cfg.tie_embeddings:
        defs["head"] = head_defs(cfg.vocab_size, cfg.d_model, dtype)
    if cfg.frontend is not None:
        defs["frontend"] = {
            "proj": pd(cfg.frontend_dim, cfg.d_model, dtype=dtype),
            "norm": rmsnorm_defs(cfg.frontend_dim),
        }
    if cfg.engram is not None and cfg.engram.enabled and cfg.engram_layers():
        defs["engram"] = engram_defs(cfg, dtype)
    return defs


def abstract_params(cfg: ModelConfig):
    return tree_abstract(model_defs(cfg))


def params_logical_axes(cfg: ModelConfig):
    return tree_axes(model_defs(cfg))


def init_params(cfg: ModelConfig, seed: int = 0):
    return tree_init(model_defs(cfg), seed)


# ---------------------------------------------------------------------------
# embedding / frontends
# ---------------------------------------------------------------------------

def embed_inputs(cfg: ModelConfig, params, batch, flags: RunFlags = RunFlags()):
    """batch: tokens (B,S) [+ frames (B,S,fe) audio | patches (B,P,fe) vlm]."""
    if cfg.frontend == "audio":
        fr = batch["frames"]
        fr = rmsnorm(params["frontend"]["norm"], fr, cfg.norm_eps)
        h = fr @ params["frontend"]["proj"]
    else:
        if flags.embed_local_gather:
            from .layers import embed_lookup_local
            h = embed_lookup_local(params["embed"], batch["tokens"])
        else:
            h = embed_lookup(params["embed"], batch["tokens"])
        if cfg.frontend == "vision" and "patches" in batch:
            # image tokens occupy positions [0, P)
            pe = rmsnorm(params["frontend"]["norm"], batch["patches"],
                         cfg.norm_eps) @ params["frontend"]["proj"]
            P_ = pe.shape[1]
            h = jnp.concatenate([pe.astype(h.dtype), h[:, P_:]], axis=1)
    if cfg.scale_embeddings:
        h = h * math.sqrt(cfg.d_model)
    return shard(h.astype(jnp.dtype(cfg.dtype)), "batch", None, None)


# ---------------------------------------------------------------------------
# engram pre-retrieval (the prefetch)
# ---------------------------------------------------------------------------

def _engram_rows_all_layers(cfg: ModelConfig, flags: RunFlags, params, idx,
                            precomputed=None):
    """Retrieve rows for every engram layer up front. idx (B,S,T)."""
    if precomputed is not None:
        return precomputed
    e = cfg.engram
    rows = []
    for j, _ in enumerate(cfg.engram_layers()):
        tab = params["engram"]["layers"][j]["tables"]
        rows.append(retrieve(e, tab, idx, flags.engram_strategy))
    return rows


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, flags: RunFlags, params, batch, mode: str,
            positions=None, caches=None, engram_rows=None):
    """Shared forward. Returns (h_final, new_caches, aux).

    mode train/prefill: positions (S,) default arange; decode: (B,).
    """
    h = embed_inputs(cfg, params, batch, flags)
    B, S = h.shape[:2]
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)

    eng_layers = cfg.engram_layers()
    rows = []
    if eng_layers and "engram" in params:
        if engram_rows is not None:
            rows = engram_rows
        else:
            idx = engram_indices(cfg.engram, batch["tokens"])
            rows = _engram_rows_all_layers(cfg, flags, params, idx)

    plan = segment_plan(cfg)
    new_caches = [] if mode != "train" else None
    aux_tot = jnp.zeros((), jnp.float32)
    for si, seg in enumerate(plan):
        if si > 0 and rows:
            # segment boundary == engram layer: fuse before the block
            fuse_p = params["engram"]["layers"][si - 1]
            h = engram_fuse(cfg, fuse_p, h, rows[si - 1])
        c = caches[si] if caches is not None else None
        h, nc, aux = apply_segment(cfg, flags, seg, params["segments"][si],
                                   h, positions, c, mode)
        aux_tot += aux
        if new_caches is not None:
            new_caches.append(nc)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return h, new_caches, aux_tot


def _head_params(cfg: ModelConfig, params):
    return params["embed"] if cfg.tie_embeddings else params["head"]


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_loss_fn(cfg: ModelConfig, flags: RunFlags):
    def loss_fn(params, batch):
        h, _, aux = forward(cfg, flags, params, batch, "train")
        loss = chunked_xent(_head_params(cfg, params), h, batch["labels"],
                            batch.get("loss_mask"),
                            final_cap=cfg.final_logit_softcap,
                            tied=cfg.tie_embeddings,
                            chunk=flags.logits_chunk,
                            remat_body=flags.xent_remat)
        return loss + aux
    return loss_fn


def init_decode_state(cfg: ModelConfig, flags: RunFlags, batch: int,
                      max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    plan = segment_plan(cfg)
    caches = [init_segment_cache(cfg, seg, batch, max_len, dtype)
              for seg in plan]
    max_order = max(cfg.engram.orders) if cfg.engram_layers() else 1
    return {
        "caches": caches,
        "positions": jnp.zeros((batch,), jnp.int32),
        "last_tokens": jnp.full((batch, max_order - 1),
                                cfg.engram.pad_token if cfg.engram else 0,
                                jnp.int32),
    }


def _pad_caches_to(caches, max_len: int):
    """Pad prefill attention caches out to decode capacity.

    Seq axis counted from the END (leaves may carry leading layer-stack
    axes): k/v are (..., S, H, D) -> axis -3; c_kv/k_rope are (..., S, R)
    -> axis -2."""
    seq_axis = {"k": -3, "v": -3, "c_kv": -2, "k_rope": -2}

    def pad(path, leaf):
        if leaf is None:
            return None
        key = None
        for p in reversed(path):
            k = getattr(p, "key", None)
            if isinstance(k, str):
                key = k
                break
        ax = seq_axis.get(key)
        if ax is not None and leaf.ndim >= -ax and leaf.shape[ax] < max_len:
            cfgpad = [(0, 0)] * leaf.ndim
            cfgpad[leaf.ndim + ax] = (0, max_len - leaf.shape[ax])
            return jnp.pad(leaf, cfgpad)
        return leaf

    return jax.tree_util.tree_map_with_path(pad, caches)


def build_prefill_step(cfg: ModelConfig, flags: RunFlags, max_len: int = 0):
    """(params, batch{tokens, [lengths]}) -> (last_logits, state)."""
    assert not cfg.is_encoder, "encoder archs have no prefill/decode"

    def prefill_step(params, batch):
        tokens = batch["tokens"]
        B, S = tokens.shape
        h, caches, _ = forward(cfg, flags, params, batch, "prefill")
        lengths = batch.get("lengths")
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        h_last = jnp.take_along_axis(
            h, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)
        logits = head_logits(_head_params(cfg, params), h_last[:, 0],
                             cfg.final_logit_softcap, cfg.tie_embeddings)
        cap = max_len or S
        caches = _pad_caches_to(caches, cap)
        max_order = max(cfg.engram.orders) if cfg.engram_layers() else 1
        no = max_order - 1
        last = jax.vmap(lambda t, l: jax.lax.dynamic_slice_in_dim(
            t, jnp.maximum(l - no, 0), no))(tokens, lengths) \
            if no > 0 else jnp.zeros((B, 0), jnp.int32)
        state = {"caches": caches, "positions": lengths,
                 "last_tokens": last}
        return logits, state

    return prefill_step


def _decode_one(cfg: ModelConfig, flags: RunFlags, params, state, token,
                rows=None):
    """One decode step: (state, token (B,)) -> (logits (B,V), new_state).
    Shared by the single-token step and the multi-token verify step so the
    two paths are numerically identical."""
    batch = {"tokens": token[:, None]}
    positions = state["positions"]
    eng_layers = cfg.engram_layers()
    if eng_layers and "engram" in params and rows is None:
        idx = decode_engram_indices(cfg.engram, state["last_tokens"],
                                    token)
        rows = _engram_rows_all_layers(cfg, flags, params, idx)
    h, new_caches, _ = forward(cfg, flags, params, batch, "decode",
                               positions=positions, caches=state["caches"],
                               engram_rows=rows)
    logits = head_logits(_head_params(cfg, params), h[:, 0],
                         cfg.final_logit_softcap, cfg.tie_embeddings)
    new_state = {
        "caches": new_caches,
        "positions": positions + 1,
        "last_tokens": update_last_tokens(state["last_tokens"], token),
    }
    return logits, new_state


def build_decode_step(cfg: ModelConfig, flags: RunFlags,
                      external_rows: bool = False):
    """(params, state, token (B,) [, rows]) -> (logits (B,V), new_state).

    ``external_rows=True`` takes the Engram rows as an argument — the
    serving engine's prefetch path (retrieval dispatched as its own call
    before the decode step is enqueued, per the paper's §4.3).

    Both forms are named ``decode_step``, so a jitted step lowers as
    ``jit_decode_step`` and its device events carry that name."""
    assert not cfg.is_encoder

    if external_rows:
        def decode_step(params, state, token, rows):
            return _decode_one(cfg, flags, params, state, token, rows)
    else:
        def decode_step(params, state, token):
            return _decode_one(cfg, flags, params, state, token)
    return decode_step


def build_multitoken_decode(cfg: ModelConfig, flags: RunFlags,
                            external_rows: bool = False):
    """Multi-token verify step for speculative decoding.

    (params, state, block (B,m) [, rows]) ->
        (logits (B,m,V), final_state, snapshots)

    Unrolls m single-token decode steps (m is static at trace time) over
    the block — position s attends the block's own earlier positions
    through the in-place KV writes, exactly as sequential decode would —
    and records a ``snapshot_recurrent`` of the state after every step so
    the caller can roll rejected positions back per slot
    (serving/slots.rollback_state).

    ``external_rows=True``: per-layer rows for the WHOLE block,
    (B, m, orders*emb) each — the engine's speculated-window prefetch.
    """
    assert not cfg.is_encoder
    from ..serving.slots import snapshot_recurrent

    def multitoken_step(params, state, block, rows=None):
        m = block.shape[1]
        snaps = [snapshot_recurrent(state)]
        logits_all = []
        st = state
        for s in range(m):
            rows_s = None
            if rows is not None:
                rows_s = [r[:, s:s + 1] for r in rows]
            logits, st = _decode_one(cfg, flags, params, st, block[:, s],
                                     rows_s)
            logits_all.append(logits)
            snaps.append(snapshot_recurrent(st))
        return jnp.stack(logits_all, axis=1), st, snaps

    if external_rows:
        return lambda params, state, block, rows: multitoken_step(
            params, state, block, rows)
    return lambda params, state, block: multitoken_step(params, state, block)


def build_chunk_prefill(cfg: ModelConfig, flags: RunFlags):
    """Chunked-prefill step for ragged admission.

    (params, state, chunk (B,C), lens (B,)) -> (logits (B,V), new_state)

    Unrolls C single-token decode steps (C static at trace time) over a
    fixed-size chunk of each row's prompt, starting from an arbitrary
    per-row prefill offset carried in ``state['positions']`` — the decode
    path is the one machine that advances EVERY cache type (attention KV,
    MLA latents, SSM/conv, xLSTM cells) one position at a time, so a chunk
    is just a gated run of it. Rows whose chunk is shorter than C
    (``lens``) stop advancing at their length (``serving.slots.gate_state``);
    the returned logits are each row's LAST VALID step's logits — for the
    final chunk of a prompt that is exactly the prefill logits the first
    sampled token comes from.
    """
    assert not cfg.is_encoder
    from ..serving.slots import gate_state

    def chunk_step(params, state, chunk, lens):
        C = chunk.shape[1]
        logits_keep = None
        st = state
        for s in range(C):
            valid = lens > s
            logits, new_st = _decode_one(cfg, flags, params, st, chunk[:, s])
            st = gate_state(valid, new_st, st)
            logits_keep = logits if logits_keep is None else \
                jnp.where(valid[:, None], logits, logits_keep)
        return logits_keep, st

    return chunk_step


def build_encoder_step(cfg: ModelConfig, flags: RunFlags):
    """Encoder forward: (params, batch) -> logits (B,S,V)."""
    def encoder_step(params, batch):
        h, _, _ = forward(cfg, flags, params, batch, "train")
        return head_logits(_head_params(cfg, params), h,
                           cfg.final_logit_softcap, cfg.tie_embeddings)
    return encoder_step
