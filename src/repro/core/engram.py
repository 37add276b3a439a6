"""Engram conditional memory: pooled tables, retrieval strategies, gated fusion.

Retrieval strategies (the paper's storage tiers, mapped to a TPU mesh):

  local       — table replicated per device ("local DRAM" baseline of the
                paper, Table 2 '+Engram (DRAM)'): plain gather.
  tp          — table row-sharded over the model axis: masked local gather
                + psum_scatter(model). Output arrives already sharded along
                the embedding dim, exactly what the TP projection consumes.
  pooled      — the CXL-pool analogue: table row-sharded over EVERY mesh
                axis (512-way on the multi-pod mesh); requests are routed to
                owner shards by a fixed-capacity all_to_all over the
                flattened mesh, owners gather rows, a reverse all_to_all
                returns payloads (~S_layer bytes/token, the paper's pool
                traffic model).
  pooled_host — like `local`/`tp` but the table lives in `pinned_host`
                memory and the gather runs under compute_on('device_host')
                (TPU host-offload; single-device only on the CPU backend —
                see DESIGN.md §2).

The retrieval is split from the fusion so callers can issue it at step
start (the paper's prefetch: indices depend only on token IDs).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..configs.base import EngramConfig, ModelConfig
from ..sharding.rules import current_ctx, mesh_axes, shard
from ..models.params import pd
from ..models.layers import rmsnorm
from .hashing import engram_indices

TABLE_PAD = 4096   # pad table_vocab so any mesh up to 4096 chips divides it


def padded_vocab(ecfg: EngramConfig) -> int:
    return -(-ecfg.table_vocab // TABLE_PAD) * TABLE_PAD


# ---------------------------------------------------------------------------
# parameter defs
# ---------------------------------------------------------------------------

def engram_defs(cfg: ModelConfig, dtype: str):
    """Each Engram layer owns its table set (the paper's N_eng independent
    per-layer fetches) plus its fusion params."""
    e = cfg.engram
    v_pad = padded_vocab(e)
    fuse_dim = len(e.orders) * e.emb_dim
    per_layer = {
        # rows stored lane-padded (EngramConfig.table_lanes); every
        # retrieval path reads the first head_dim lanes only
        "tables": pd(e.n_tables, v_pad, e.table_lanes,
                     axes=(None, "eng_vocab", None), dtype=dtype),
        "proj": pd(fuse_dim, cfg.d_model, axes=("eng_emb", None), dtype=dtype),
        "gate": pd(cfg.d_model, cfg.d_model, axes=(None, None), dtype=dtype),
        "norm": {"scale": pd(fuse_dim, init="ones")},
    }
    return {"layers": [per_layer for _ in cfg.engram_layers()]}


# ---------------------------------------------------------------------------
# retrieval strategies
# ---------------------------------------------------------------------------

def _take_rows(tables, idx, hd: int):
    """tables (T,V,lanes); idx (B,S,T) -> (B,S,T,hd): one gather over the
    flattened tables, then each row's first ``hd`` lanes (slicing the
    tables first would copy them)."""
    T, V, lanes = tables.shape
    gid = idx + jnp.arange(T, dtype=idx.dtype) * V
    return jnp.take(tables.reshape(T * V, lanes), gid, axis=0)[..., :hd]


def retrieve_local(ecfg: EngramConfig, tables, idx):
    rows = _take_rows(tables, idx, ecfg.head_dim)
    B, S, T, hd = rows.shape
    return rows.reshape(B, S, T * hd)


def retrieve_local_kernel(ecfg: EngramConfig, tables, idx, *,
                          interpret: bool = False):
    """Local gather through the Pallas DMA kernel (kernels/engram_gather).
    The kernel compiles for a TPU only; off the chip a caller (a test)
    asks for the Pallas interpreter with ``interpret=True``."""
    from ..kernels.engram_gather.ops import engram_gather
    rows = engram_gather(tables, idx, interpret=interpret)
    B, S, T, _ = rows.shape
    hd = ecfg.head_dim
    return rows[..., :hd].reshape(B, S, T * hd)


def retrieve_tp(ecfg: EngramConfig, tables, idx):
    """Table sharded over the model axis; masked gather + psum_scatter."""
    ctx = current_ctx()
    axes = tuple(a for a in ("model",) if ctx and a in ctx.mesh.axis_names)
    if ctx is None or not axes:
        return retrieve_local(ecfg, tables, idx)
    ax = axes[0]
    n = ctx.mesh.shape[ax]
    v_pad = padded_vocab(ecfg)
    if v_pad % n != 0:
        return retrieve_local(ecfg, tables, idx)
    v_loc = v_pad // n
    T, hd = ecfg.n_tables, ecfg.head_dim

    def local_fn(tab, ix):
        # tab (T, v_loc, lanes); ix (B_loc, S, T)
        base = jax.lax.axis_index(ax) * v_loc
        rel = ix - base
        okm = (rel >= 0) & (rel < v_loc)
        rel = jnp.clip(rel, 0, v_loc - 1)
        rows = _take_rows(tab, rel, hd)
        rows = rows * okm[..., None].astype(rows.dtype)
        B, S = ix.shape[:2]
        rows = rows.reshape(B, S, T * hd)
        # reduce-scatter: output sharded along the fused-embedding dim
        return jax.lax.psum_scatter(rows, ax, scatter_dimension=2, tiled=True)

    # divisibility-aware batch spec (long_500k has B=1 < |data|)
    spec_i = ctx.spec_for(idx.shape, ("batch", None, None))
    b_entry = spec_i[0] if len(spec_i) > 0 else None
    fn = jax.shard_map(local_fn, mesh=ctx.mesh,
                       in_specs=(P(None, ax, None), spec_i),
                       out_specs=P(b_entry, None, ax),
                       check_vma=False)
    return fn(tables, idx)


def retrieve_pooled(ecfg: EngramConfig, tables, idx, *, slack: float = 2.0):
    """CXL-pool analogue: fixed-capacity request/reply all_to_all over the
    whole mesh (table 512-way sharded on the multi-pod mesh)."""
    ctx = current_ctx()
    if ctx is None:
        return retrieve_local(ecfg, tables, idx)
    pool_axes = tuple(ctx.rules.get("eng_vocab", ()))
    pool_axes = tuple(a for a in pool_axes if a in ctx.mesh.axis_names)
    if not pool_axes:
        return retrieve_local(ecfg, tables, idx)
    N = ctx.axis_prod(pool_axes)
    v_pad = padded_vocab(ecfg)
    if N == 1 or v_pad % N != 0:
        return retrieve_local(ecfg, tables, idx)
    v_loc = v_pad // N
    T, hd = ecfg.n_tables, ecfg.head_dim

    def local_fn(tab, ix):
        # tab (T, v_loc, lanes) — this device's pool shard (owner of rows
        # [o*v_loc, (o+1)*v_loc) where o = linear index over pool_axes).
        # ix (B_loc', S, T) — this device's share of requests.
        B, S = ix.shape[:2]
        # flatten requests: tag with table id so owners can address sub-tables
        flat_i = ix.reshape(-1)                                   # (R,)
        flat_tid = jnp.tile(jnp.arange(T, dtype=jnp.int32), B * S)
        R = flat_i.shape[0]

        # --- dedup: each unique (table, row) is fetched ONCE per device.
        # Real text is Zipf-skewed — a hot bigram hashes every occurrence
        # to the same row; without dedup those duplicates pile onto one
        # owner and overflow the fixed capacity (dropped -> zero rows).
        # With dedup, capacity is spent on unique keys only, and hot rows
        # cost one fetch regardless of frequency (also a bandwidth win).
        key = flat_tid * jnp.int32(v_pad) + flat_i                # unique key
        korder = jnp.argsort(key)
        sk = key[korder]
        is_first = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
        gid_sorted = jnp.cumsum(is_first) - 1                     # group per pos
        first_pos = jnp.where(is_first, jnp.arange(R), R)
        cpos = jnp.sort(first_pos)          # cpos[g] = sorted-pos of group g
        u_valid = cpos < R
        u_key = sk[jnp.minimum(cpos, R - 1)]
        u_row = (u_key % v_pad).astype(jnp.int32)
        u_tid = (u_key // v_pad).astype(jnp.int32)

        dest = jnp.where(u_valid, u_row // v_loc, N)              # N = drop
        order = jnp.argsort(dest)
        s_dst = dest[order]
        s_row, s_tid = u_row[order], u_tid[order]
        cap = int(math.ceil(R / N * slack))
        counts = jnp.bincount(dest, length=N)                     # uniques only
        starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(R) - starts[jnp.minimum(s_dst, N - 1)]
        ok = (pos < cap) & (s_dst < N)
        pos_c = jnp.where(ok, pos, cap)
        dst_c = jnp.minimum(s_dst, N - 1)
        send_req = jnp.full((N, cap + 1), -1, jnp.int32)
        send_tid = jnp.zeros((N, cap + 1), jnp.int32)
        send_rid = jnp.full((N, cap + 1), R, jnp.int32)
        send_req = send_req.at[dst_c, pos_c].set(
            (s_row % v_loc).astype(jnp.int32))
        send_tid = send_tid.at[dst_c, pos_c].set(s_tid)
        send_rid = send_rid.at[dst_c, pos_c].set(order.astype(jnp.int32))
        send_req, send_tid = send_req[:, :cap], send_tid[:, :cap]
        send_rid = send_rid[:, :cap]
        # request -> owner
        recv_req = _a2a(send_req, pool_axes)
        recv_tid = _a2a(send_tid, pool_axes)
        # owner-side gather (the pool read; maps to kernels/engram_gather)
        safe = jnp.clip(recv_req, 0, v_loc - 1)
        rows = tab[recv_tid.reshape(-1), safe.reshape(-1), :hd]   # (N*cap, hd)
        rows = rows * (recv_req.reshape(-1) >= 0)[:, None].astype(rows.dtype)
        # reply -> requester; rid is the unique-group slot, so rows land
        # in the compact unique buffer, then fan out to every duplicate
        back = _a2a(rows.reshape(N, cap, hd), pool_axes)
        rid = send_rid.reshape(N * cap)
        valid = rid < R
        rows_u = jnp.zeros((R + 1, hd), rows.dtype)
        rows_u = rows_u.at[jnp.where(valid, rid, R)].add(
            back.reshape(N * cap, hd))
        out_sorted = rows_u[gid_sorted]                           # (R, hd)
        out = jnp.zeros((R, hd), rows.dtype).at[korder].set(out_sorted)
        return out.reshape(B, S, T * hd)

    # divisibility-aware batch spec (long_500k has B=1 < |data|)
    spec_i = ctx.spec_for(idx.shape, ("batch", None, None))
    fn = jax.shard_map(local_fn, mesh=ctx.mesh,
                       in_specs=(P(None, pool_axes, None), spec_i),
                       out_specs=spec_i,
                       check_vma=False)
    return fn(tables, idx)


def _linear_index(axes, ctx):
    acc = jnp.zeros((), jnp.int32)
    for a in axes:
        acc = acc * ctx.mesh.shape[a] + jax.lax.axis_index(a)
    return acc


def _a2a(x, axes):
    """all_to_all over possibly-multiple mesh axes (flattened order)."""
    if len(axes) == 1:
        return jax.lax.all_to_all(x, axes[0], 0, 0, tiled=False)
    # multi-axis: a2a over the joint axis (jax supports tuple axis names)
    return jax.lax.all_to_all(x, axes, 0, 0, tiled=False)


def retrieve_host(ecfg: EngramConfig, tables, idx):
    """Host-offloaded gather (pinned_host table + compute_on). Single-device
    meshes on CPU; SPMD-capable on real TPU (see DESIGN.md §2)."""
    from jax.experimental import compute_on

    with compute_on.compute_on("device_host"):
        rows = _take_rows(tables, idx, ecfg.head_dim)
    B, S, T, hd = rows.shape
    return rows.reshape(B, S, T * hd)


# ---------------------------------------------------------------------------
# strategy registry — placement only; cost semantics live in pool/store.py
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StrategySpec:
    """A retrieval strategy: *where* the rows live and which collectives
    move them. What that placement costs (tier latency, hot-row cache,
    prefetch windows) is the store's concern — ``spec.store(ecfg)``
    resolves the matching ``EngramStore`` backend via
    ``pool.store.STRATEGY_TIERS``."""
    name: str
    fn: object                        # (ecfg, tables, idx) -> rows

    def store(self, ecfg: EngramConfig):
        from ..pool.store import store_for_strategy
        return store_for_strategy(ecfg, self.name)


STRATEGIES = {
    s.name: s for s in (
        StrategySpec("local", retrieve_local),
        StrategySpec("local_kernel", retrieve_local_kernel),
        StrategySpec("tp", retrieve_tp),
        StrategySpec("pooled", retrieve_pooled),
        StrategySpec("pooled_host", retrieve_host),
    )
}


def retrieve(ecfg: EngramConfig, tables, idx, strategy: str = None):
    s = strategy or ecfg.strategy
    return STRATEGIES[s].fn(ecfg, tables, idx)


def strategy_store(ecfg: EngramConfig, strategy: str = None):
    """The EngramStore modelling the cost of ``strategy``'s placement."""
    return STRATEGIES[strategy or ecfg.strategy].store(ecfg)


# ---------------------------------------------------------------------------
# fusion (gating into hidden states, before the attention block)
# ---------------------------------------------------------------------------

def engram_fuse(cfg: ModelConfig, fuse_params, h, rows,
                use_kernel: bool = False):
    """h (B,S,d) + retrieved rows (B,S,orders*emb) -> h'."""
    rows = rmsnorm(fuse_params["norm"], rows, cfg.norm_eps)
    if use_kernel:
        from ..kernels.gated_fuse.ops import engram_gated_fuse
        out = engram_gated_fuse(h, rows, fuse_params["gate"],
                                fuse_params["proj"])
    else:
        update = rows @ fuse_params["proj"]
        gate = jax.nn.sigmoid((h @ fuse_params["gate"]).astype(jnp.float32))
        out = h + (gate.astype(h.dtype) * update)
    return shard(out, "batch", None, None)


# ---------------------------------------------------------------------------
# convenience: full lookup for a token batch (used by serving prefetch)
# ---------------------------------------------------------------------------

def engram_lookup(cfg: ModelConfig, eng_params, tokens, layer_slot: int = 0,
                  strategy=None):
    """tokens (B,S) -> rows (B,S,orders*emb). Retrieval only, no fusion."""
    e = cfg.engram
    idx = engram_indices(e, tokens)
    return retrieve(e, eng_params["layers"][layer_slot]["tables"], idx,
                    strategy)
