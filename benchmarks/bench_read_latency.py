"""Figs 3/5/6: Engram read latency vs retrieval batch size.

Two sources, reported side by side:
  * the calibrated tier simulator (DRAM / CXL / RDMA / CXL->GPU), which
    reproduces the paper's measured curves;
  * a real measured local gather (jit'd XLA take on this host) — the
    "local DRAM" ground truth available in this container, anchoring the
    simulator's DRAM curve.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ENGRAM_27B, ENGRAM_40B, EngramConfig
from repro.pool.cache import LRUHotRowCache, zipf_keys
from repro.pool.simulator import latency_sweep
from repro.pool.store import CachedStore, TableFetcher, TierStore

from .common import emit, timeit, write_csv

BATCHES = (1, 8, 32, 64, 128, 256, 512, 1024)


def measured_local_gather_us(ecfg: EngramConfig, batch: int,
                             table_rows: int = 65536) -> float:
    """Wall time of the actual Engram gather on this host's DRAM (table
    truncated to fit CPU memory; per-segment cost is row-count-invariant
    for sparse random access)."""
    rng = np.random.RandomState(0)
    tables = jnp.asarray(
        rng.randn(ecfg.n_tables, table_rows, ecfg.head_dim).astype(np.float32))
    idx = jnp.asarray(rng.randint(0, table_rows,
                                  (batch, 1, ecfg.n_tables)), jnp.int32)

    @jax.jit
    def gather(t, i):
        outs = [jnp.take(t[k], i[..., k], axis=0)
                for k in range(t.shape[0])]
        return jnp.stack(outs, axis=-2)

    return timeit(gather, tables, idx, warmup=2, iters=5) * 1e6


def measured_miss_gather_us(ecfg: EngramConfig, n_miss: int,
                            table_rows: int = 65536) -> float:
    """Host wall time of a variable-count cache-miss gather through the
    store's miss path (XLA take on this host; the Pallas kernel is a TPU
    path and its interpreter timing would mean nothing)."""
    small = EngramConfig(orders=ecfg.orders, n_heads=ecfg.n_heads,
                         emb_dim=ecfg.emb_dim, table_vocab=table_rows,
                         layers=ecfg.layers)
    rng = np.random.RandomState(0)
    tables = jnp.asarray(
        rng.randn(small.n_tables, table_rows, small.table_lanes)
        .astype(np.float32))
    fetch = TableFetcher(small, tables, impl="take")
    keys = rng.randint(0, small.n_tables * table_rows, size=n_miss)
    return timeit(lambda k: fetch(k), keys, warmup=2, iters=5) * 1e6


def cached_rescue_sweep(ecfg: EngramConfig, batches, *, cache_rows: int,
                        alpha: float = 1.2, waves: int = 64) -> list:
    """Measured §6 rescue: drive a CachedStore(RDMA) with a Zipf segment
    stream and report per-batch modelled latency at the *measured* LRU hit
    rate (vs the uncached RDMA latency)."""
    out = []
    for b in batches:
        store = CachedStore(TierStore(ecfg, "RDMA"), cache_tier="DRAM",
                            cache=LRUHotRowCache(cache_rows))
        plain = TierStore(ecfg, "RDMA")          # dedup'd but uncached:
        n_seg = b * ecfg.n_tables                # isolates the cache's win
        stream = zipf_keys(waves * n_seg, ecfg.table_vocab * ecfg.n_tables,
                           alpha=alpha, seed=b)
        lat = lat_plain = 0.0
        for w in range(waves):
            wave = stream[w * n_seg:(w + 1) * n_seg]
            lat = store.prefetch(wave).latency_s     # steady-state last wave
            lat_plain = plain.prefetch(wave).latency_s
        s = store.stats()
        out.append({"batch": b, "hit_rate": s.hit_rate,
                    "cached_us": lat * 1e6,
                    "uncached_us": lat_plain * 1e6})
    return out


def run(fast: bool = False) -> None:
    batches = BATCHES if not fast else (1, 64, 256)
    for name, preset in (("engram27b", ENGRAM_27B), ("engram40b", ENGRAM_40B)):
        e = EngramConfig(**preset)
        sweep = latency_sweep(e, batch_sizes=batches)
        rows = []
        for i, b in enumerate(batches):
            meas = measured_local_gather_us(e, b) if not fast else float("nan")
            rows.append([b,
                         round(sweep["DRAM"][i][1], 2),
                         round(sweep["CXL"][i][1], 2),
                         round(sweep["RDMA"][i][1], 2),
                         round(sweep["CXL->GPU"][i][1], 2),
                         round(meas, 2)])
        write_csv(f"read_latency_{name}",
                  ["batch", "dram_us", "cxl_us", "rdma_us", "cxl_gpu_us",
                   "measured_local_us"], rows)
        mid = len(batches) // 2
        emit(f"read_latency/{name}/cxl_b{batches[mid]}",
             sweep["CXL"][mid][1],
             f"dram={sweep['DRAM'][mid][1]:.1f}us "
             f"rdma={sweep['RDMA'][mid][1]:.1f}us")

    # §6 rescue, measured through the store: Zipf stream -> LRU hit rate
    e27 = EngramConfig(**ENGRAM_27B)
    rescue = cached_rescue_sweep(e27, (64, 256) if fast else (64, 256, 1024),
                                 cache_rows=500_000)
    write_csv("read_latency_cached_rescue",
              ["batch", "hit_rate", "cached_us", "uncached_us"],
              [[r["batch"], round(r["hit_rate"], 3),
                round(r["cached_us"], 2), round(r["uncached_us"], 2)]
               for r in rescue])
    for r in rescue:
        emit(f"read_latency/cached_rescue_b{r['batch']}", r["cached_us"],
             f"hit={r['hit_rate']:.2f} uncached={r['uncached_us']:.1f}us")
    if not fast:
        for n_miss in (7, 100, 1000):
            us = measured_miss_gather_us(e27, n_miss)
            emit(f"read_latency/miss_take_gather_n{n_miss}", us,
                 "miss-path take gather (host wall time)")


if __name__ == "__main__":
    run()
