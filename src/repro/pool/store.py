"""Tiered EngramStore: one object owning tier/latency/cache semantics.

Before this subsystem the pool story was smeared across three layers —
analytic tier math in ``pool/simulator.py``, retrieval strategies in
``core/engram.py``, and a hand-rolled stall injector in
``serving/engine.py`` — so the §6 hot-row cache existed only as a formula
and never touched the serving path. The store unifies them:

  * ``TierStore``     — one backend per ``TierSpec`` (HBM / DRAM / CXL /
                        RDMA / RDMA-agg). Its latency IS
                        ``TierSpec.read_latency_s`` on the segment count:
                        the single code path the simulator tables and the
                        serving engine both read from.
  * ``LocalStore``    — weights resident on-device; no emulated pool cost
                        (the engine's ``pool=None`` baseline).
  * ``CachedStore``   — an LRU hot-row cache (``pool/cache.py``) in front
                        of any backing store. Per wave it measures real
                        hit/miss counts against the Zipf assumption and
                        feeds the *measured* split into the same
                        max(hit-path, miss-path) formula that
                        ``simulator.cached_read_latency_s`` evaluates with
                        an assumed rate.

Division of labour with ``core/engram.py``: a retrieval *strategy* decides
placement (which devices hold the rows and which collectives move them);
the *store* decides what that placement costs (tier latency, cache,
prefetch accounting). ``STRATEGY_TIERS`` maps each strategy onto the tier
whose semantics it emulates.

The protocol is deliberately tiny::

    handle = store.prefetch(tokens_or_keys)   # issue the wave's retrieval
    rows   = store.gather(handle)             # block on / materialize rows
    stats  = store.stats()                    # measured hit rates + stalls

``prefetch`` accepts either a flat array of packed segment keys (measured
mode — the engine passes the wave's real (layer, table, row) stream) or a
bare token count (analytic mode — the simulator's batch sweeps).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import numpy as np

from ..configs.base import EngramConfig
from .cache import LRUHotRowCache, TinyLFUAdmission, WaveAccess
from .tiers import TIERS, TierSpec, is_chain


# ---------------------------------------------------------------------------
# segment geometry + key packing
# ---------------------------------------------------------------------------

def segment_bytes(ecfg: EngramConfig) -> int:
    return ecfg.head_dim * 2                       # bf16 rows


def segment_count(ecfg: EngramConfig, batch_tokens: int) -> int:
    return batch_tokens * ecfg.n_tables


def segment_keys(ecfg: EngramConfig, idx, layer_slot: int = 0) -> np.ndarray:
    """Pack table-row indices ``idx (..., T)`` into flat int64 segment keys
    ``(layer_slot * T + t) * table_vocab + row`` — the cache's identity.

    Host-side reference packing. The serving hot path packs the same keys
    on-device inside the jitted index fns (``core.hashing.pack_segment_keys``)
    so one sync per wave delivers every layer's stream; this function remains
    the ground truth the device path is tested bit-identical against."""
    a = np.asarray(idx, dtype=np.int64)
    T = ecfg.n_tables
    assert a.shape[-1] == T, (a.shape, T)
    tid = np.arange(T, dtype=np.int64) + layer_slot * T
    return (a + tid * ecfg.table_vocab).reshape(-1)


def keys_to_gid(ecfg: EngramConfig, keys: np.ndarray,
                table_rows: Optional[int] = None) -> np.ndarray:
    """Packed segment keys -> flat row ids in one layer's ``(T*V_pad, hd)``
    table space. ``table_rows`` is the table's actual (possibly padded)
    per-table row count; when it equals ``table_vocab`` the whole
    decomposition collapses to one modulo."""
    keys = np.asarray(keys, np.int64)
    V = ecfg.table_vocab if table_rows is None else int(table_rows)
    if V == ecfg.table_vocab:
        return keys % (ecfg.n_tables * ecfg.table_vocab)
    tid = (keys // ecfg.table_vocab) % ecfg.n_tables
    return tid * V + keys % ecfg.table_vocab


# ---------------------------------------------------------------------------
# handles + stats
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Segments:
    """Analytic charge unit: an explicit (hits, misses) split, bypassing
    both token->segment expansion and the cache. The simulator's trace
    replay (``simulator.replay_stall_s``) feeds the engine's *recorded*
    per-wave splits back through the same store code path — the one-clock
    regression contract. ``shards``: optional recorded per-shard split
    (``pool/fabric.py``) so a fabric-charged wave replays its exact
    multi-node fan-out instead of re-deriving it from keys it no longer
    has."""
    hits: int
    misses: int
    shards: Optional[tuple] = None

    @property
    def n(self) -> int:
        return self.hits + self.misses


@dataclasses.dataclass
class PrefetchHandle:
    """An issued (in-flight) retrieval wave."""
    n_segments: int                    # unique segments actually fetched
    latency_s: float                   # store-modelled completion latency
    hits: int = 0
    misses: int = 0
    fetch: Optional[Callable[[], Any]] = None    # materializes the rows
    rows: Any = None
    gathered: bool = False
    wait_s: float = 0.0                # queueing delay on shared links
    issued_at_s: float = 0.0           # virtual issue time (clock-bound)
    reservations: list = dataclasses.field(default_factory=list)
    shards: Optional[tuple] = None     # per-shard split (fabric-backed)


@dataclasses.dataclass
class StoreStats:
    """Measured store-side accounting (the engine surfaces this verbatim)."""
    tier: str
    cache_tier: Optional[str] = None
    cache_rows: int = 0
    prefetches: int = 0
    gathers: int = 0
    segments: int = 0                  # unique segments fetched
    hits: int = 0
    misses: int = 0
    waves: int = 0                     # scheduler-charged waves
    hidden_waves: int = 0              # waves fully inside the window
    stall_s: float = 0.0               # accumulated overshoot
    retrieval_s: float = 0.0           # accumulated modelled latency
    wait_s: float = 0.0                # queue delay on shared clock links
    # ---- speculative prefetch accounting (spec/ + scheduler) ------------
    spec_waves: int = 0                # speculative (multi-token) waves
    spec_tokens: int = 0               # tokens emitted by speculative waves
    accepted_segments: int = 0         # prefetched segments that were used
    wasted_segments: int = 0           # prefetched for a rejected position
    spec_depth_sum: float = 0.0        # accumulated measured window depth
    # per-slot attribution of the speculative split (slot -> segments).
    # Counted per slot independently, so a key shared by two slots in one
    # fused wave is attributed to both — the sums can exceed the
    # accepted/wasted aggregates above, which stay dedup-true (the
    # scheduler splits each position's fused unique stream by the union
    # of keys the *surviving* slots actually fetched).
    slot_accepted: dict = dataclasses.field(default_factory=dict)
    slot_wasted: dict = dataclasses.field(default_factory=dict)
    # ---- per-traffic-class pool occupancy (KV pages vs Engram rows) -----
    # bytes / link busy-seconds this store put on the shared medium, split
    # by class ("engram": row fetches; "kv": preemption spills/restores,
    # pool/kvpool.py; "promote"/"demote": tier-chain migration traffic,
    # pool/tierchain.py) — the arbitration observable of ROADMAP item 1
    class_bytes: dict = dataclasses.field(default_factory=dict)
    class_busy_s: dict = dataclasses.field(default_factory=dict)
    # ---- three-level chain accounting (pool/tierchain.py) ---------------
    # hits/misses above stay the front-cache split (hits = DRAM front);
    # these split the miss side by which backing level actually served it,
    # plus the CXL<->SSD migration counts whose bytes ride the class
    # ledgers under "promote"/"demote"
    warm_hits: int = 0                 # served by the warm (CXL) level
    cold_misses: int = 0               # served by the cold (SSD) level
    promotions: int = 0                # rows promoted cold -> warm
    demotions: int = 0                 # rows written back warm -> cold

    @property
    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0

    @property
    def stall_s_per_wave(self) -> float:
        return self.stall_s / self.waves if self.waves else 0.0

    @property
    def spec_window_steps(self) -> float:
        """Measured prefetch window depth, in emitted-token decode steps:
        the lead time of the deepest *accepted* position between prefetch
        issue and consumption, averaged over speculative waves. Driven by
        verified acceptance, not a config knob — all-rejected waves
        collapse it below one step."""
        return self.spec_depth_sum / self.spec_waves if self.spec_waves \
            else 0.0

    @property
    def wasted_prefetch_rate(self) -> float:
        n = self.accepted_segments + self.wasted_segments
        return self.wasted_segments / n if n else 0.0


@runtime_checkable
class EngramStore(Protocol):
    def prefetch(self, tokens, fetch: Optional[Callable[[], Any]] = None
                 ) -> PrefetchHandle: ...
    def gather(self, handle: PrefetchHandle) -> Any: ...
    def stats(self) -> StoreStats: ...
    def read_latency_s(self, batch_tokens: int) -> float: ...


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------

class _StoreBase:
    """Shared prefetch/gather bookkeeping; subclasses define the latency.

    A store may be *clock-bound*: ``bind_cursor`` attaches the owning
    replica's ``serving/clock.py`` cursor, and the subclass registers the
    shared ``Link``(s) its transfers occupy. A charged wave then adds the
    link's queueing delay (another replica's transfer still in flight) to
    its modelled latency — the bandwidth-split contention the paper's
    Table 3 measures. Unbound stores (``clock=None``) behave exactly as
    before: pure tier model, zero wait."""

    def __init__(self, ecfg: EngramConfig, tier_name: str):
        self.ecfg = ecfg
        self._stats = StoreStats(tier=tier_name)
        self.cursor = None

    def bind_cursor(self, cursor) -> None:
        """Attach the owning replica's timeline cursor (serving/clock.py)."""
        self.cursor = cursor

    # latency model -----------------------------------------------------
    def latency_for_segments(self, n_segments: int) -> float:
        raise NotImplementedError

    def occupancy_s(self, n_segments: int) -> float:
        """Shared-medium occupancy of a wave (what a clock link books);
        0 for stores with no shared resource."""
        return 0.0

    def read_latency_s(self, batch_tokens: int) -> float:
        """Analytic read latency for a full (uncached) token batch."""
        return self.latency_for_segments(segment_count(self.ecfg, batch_tokens))

    # protocol ----------------------------------------------------------
    def _classify(self, tokens) -> tuple[int, int, int]:
        """-> (n_segments, hits, misses) for a wave.

        Measured mode (key array) counts *unique* keys: in-wave dedup is a
        property of the retrieval path itself (the pooled strategy dedups
        identically), not of the cache — pricing duplicates here would
        misattribute dedup savings to the LRU when cached and uncached
        runs are compared. Analytic mode (int token count) keeps the
        paper's raw B-discrete-reads convention; ``Segments`` pins an
        explicit split (trace replay)."""
        if isinstance(tokens, Segments):
            return tokens.n, tokens.hits, tokens.misses
        if np.isscalar(tokens) or isinstance(tokens, int):
            n = segment_count(self.ecfg, int(tokens))
        else:
            n = int(np.unique(np.asarray(tokens, dtype=np.int64)).size)
        return n, 0, n

    def prefetch(self, tokens, fetch: Optional[Callable[[], Any]] = None
                 ) -> PrefetchHandle:
        n, hits, misses = self._classify(tokens)
        lat, wait, resv = self._charged_latency(hits, misses)
        h = PrefetchHandle(n_segments=n, latency_s=lat, hits=hits,
                           misses=misses, fetch=fetch, wait_s=wait,
                           issued_at_s=self.cursor.now_s if self.cursor
                           is not None else 0.0,
                           reservations=resv)
        s = self._stats
        s.prefetches += 1
        s.segments += n
        s.hits += hits
        s.misses += misses
        s.retrieval_s += lat
        s.wait_s += wait
        return h

    def _split_latency(self, hits: int, misses: int) -> float:
        return self.latency_for_segments(hits + misses)

    def _charged_latency(self, hits: int, misses: int
                         ) -> tuple[float, float, list]:
        """Modelled latency + shared-link queue wait for one wave ->
        (latency incl. wait, wait alone, link reservations)."""
        lat = self._split_latency(hits, misses)
        wait, resv = self._reserve(hits + misses)
        return lat + wait, wait, resv

    def note_class(self, klass: str, nbytes: int, busy_s: float) -> None:
        """Attribute ``nbytes`` / ``busy_s`` of shared-medium occupancy to
        a traffic class (per-class split in ``StoreStats``). The engram
        charge path calls this on every reservation; the engine calls it
        for KV spill/restore transfers it books directly on the pool link
        (negative values roll back a refunded booking)."""
        s = self._stats
        s.class_bytes[klass] = s.class_bytes.get(klass, 0) + int(nbytes)
        s.class_busy_s[klass] = s.class_busy_s.get(klass, 0.0) + busy_s

    def _reserve(self, n_segments: int) -> tuple[float, list]:
        link = getattr(self, "_link", None)
        if link is None or self.cursor is None or n_segments <= 0:
            return 0.0, []
        occ = self.occupancy_s(n_segments)
        nbytes = n_segments * segment_bytes(self.ecfg)
        wait, tr = link.reserve(self.cursor.now_s, occ, nbytes=nbytes,
                                wave=self.cursor.wave_tag(), klass="engram")
        self.note_class("engram", nbytes, occ)
        return wait, [tr]

    def reserve_prefetch(self, n_segments: int):
        """Book a *future* wave's occupancy on the shared medium now (the
        engine's pipelined speculative prefetch issues wave N+1's transfer
        during wave N). Returns the ``Transfer`` (or None when unbound);
        the engine refunds it at the next wave — where the normal charge
        path re-prices the real keys — or on mid-flight ``cancel()``."""
        link = getattr(self, "_link", None)
        if link is None or self.cursor is None or n_segments <= 0:
            return None
        _, tr = link.reserve(self.cursor.now_s,
                             self.occupancy_s(n_segments),
                             nbytes=n_segments * segment_bytes(self.ecfg))
        return tr

    def gather(self, handle: PrefetchHandle) -> Any:
        if not handle.gathered:
            if handle.fetch is not None:
                handle.rows = handle.fetch()
            handle.gathered = True
            self._stats.gathers += 1
        return handle.rows

    def note_wave(self, stall_s: float, hidden: bool) -> None:
        s = self._stats
        s.waves += 1
        s.stall_s += stall_s
        s.hidden_waves += int(hidden)

    def note_spec_wave(self, stall_s: float, hidden: bool, tokens: int,
                       depth_steps: float, accepted_segments: int,
                       wasted_segments: int, per_slot=None) -> None:
        """Account one verified speculative wave: ``tokens`` were emitted,
        the wave's deepest accepted position enjoyed ``depth_steps`` of
        measured lookahead, and the prefetched segments split into used
        vs. mis-speculated (fetched for a rejected draft). ``per_slot``
        (optional): ``{slot: (accepted_segments, wasted_segments)}`` — the
        per-slot attribution of that split."""
        self.note_wave(stall_s, hidden)
        s = self._stats
        s.spec_waves += 1
        s.spec_tokens += int(tokens)
        s.spec_depth_sum += float(depth_steps)
        s.accepted_segments += int(accepted_segments)
        s.wasted_segments += int(wasted_segments)
        if per_slot:
            for slot, (acc, waste) in per_slot.items():
                s.slot_accepted[slot] = s.slot_accepted.get(slot, 0) + int(acc)
                s.slot_wasted[slot] = s.slot_wasted.get(slot, 0) + int(waste)

    def stats(self) -> StoreStats:
        return self._stats

    def reset_stats(self) -> None:
        old = self._stats
        self._stats = StoreStats(tier=old.tier, cache_tier=old.cache_tier,
                                 cache_rows=old.cache_rows)


class TierStore(_StoreBase):
    """Engram rows resident in one memory tier of the paper's fabric.

    ``clock``: bind the tier's shared medium as a fleet-wide ``Link``
    (keyed by tier name, so every replica's TierStore on the same clock
    contends on one budget — the pool is shared infrastructure)."""

    def __init__(self, ecfg: EngramConfig, tier: TierSpec | str, clock=None):
        tier = TIERS[tier] if isinstance(tier, str) else tier
        super().__init__(ecfg, tier.name)
        self.tier = tier
        self._link = clock.link(f"tier:{tier.name}", tier.bandwidth_Bps) \
            if clock is not None else None

    def latency_for_segments(self, n_segments: int) -> float:
        if n_segments <= 0:
            return 0.0
        return self.tier.read_latency_s(n_segments, segment_bytes(self.ecfg))

    def occupancy_s(self, n_segments: int) -> float:
        return self.tier.service_s(n_segments, segment_bytes(self.ecfg))


class LocalStore(_StoreBase):
    """Rows co-resident with the activations (device HBM / local weights):
    the retrieval is part of the forward pass, no emulated pool cost."""

    def __init__(self, ecfg: EngramConfig):
        super().__init__(ecfg, "local")

    def latency_for_segments(self, n_segments: int) -> float:
        return 0.0


class CachedStore(_StoreBase):
    """LRU hot-row cache (``cache_tier``) in front of a backing store.

    Hit and miss paths proceed in parallel (independent hardware), so the
    wave completes at ``max(hit path, miss path)`` — the same formula
    ``simulator.cached_read_latency_s`` uses, evaluated here with the
    *measured* per-wave split instead of an assumed Zipf hit rate.

    Clock-bound, the two paths occupy two distinct links: misses the
    backing tier's fleet-wide link, hits the cache's own DRAM channel
    (``cache_link``). A *shared* hot-row cache hands every replica the
    same link — N replicas hitting one DRAM cache split its bandwidth —
    while private caches each own theirs (free parallelism, the baseline).
    """

    def __init__(self, backing: TierStore, cache_tier: TierSpec | str = "DRAM",
                 cache: Optional[LRUHotRowCache] = None, clock=None,
                 cache_link=None):
        super().__init__(backing.ecfg, backing.tier.name)
        self.backing = backing
        self.cache_tier = TIERS[cache_tier] if isinstance(cache_tier, str) \
            else cache_tier
        self.cache = cache
        if cache_link is not None:
            self._cache_link = cache_link
        elif clock is not None:
            self._cache_link = clock.link(f"cache:{id(self):x}",
                                          self.cache_tier.bandwidth_Bps)
        else:
            self._cache_link = None
        self._stats.cache_tier = self.cache_tier.name
        # NB: the cache defines __len__, so test identity, not truthiness
        self._stats.cache_rows = 0 if cache is None else cache.capacity_rows

    def bind_cursor(self, cursor) -> None:
        super().bind_cursor(cursor)
        self.backing.bind_cursor(cursor)

    def latency_for_segments(self, n_segments: int) -> float:
        return self.backing.latency_for_segments(n_segments)

    def occupancy_s(self, n_segments: int) -> float:
        # pre-reservations assume the miss path (the backing medium)
        return self.backing.occupancy_s(n_segments)

    def reserve_prefetch(self, n_segments: int):
        return self.backing.reserve_prefetch(n_segments)

    def _split_latency(self, hits: int, misses: int) -> float:
        seg = segment_bytes(self.ecfg)
        t_hit = self.cache_tier.read_latency_s(hits, seg) if hits else 0.0
        t_miss = self.backing.latency_for_segments(misses)
        return max(t_hit, t_miss)

    def _charged_latency(self, hits: int, misses: int
                         ) -> tuple[float, float, list]:
        seg = segment_bytes(self.ecfg)
        resv = []
        t_hit = self.cache_tier.read_latency_s(hits, seg) if hits else 0.0
        w_hit = w_miss = 0.0
        charge_miss = getattr(self.backing, "charge_misses", None)
        if charge_miss is not None:
            # fabric-backed: the miss wave fans out per shard (node links
            # + switch), charged by the fabric itself — a single backing-
            # link booking would hide the multi-node contention
            miss_path, w_miss, trs = charge_miss(misses) if misses \
                else (0.0, 0.0, [])
            resv.extend(trs)
        else:
            t_miss = self.backing.latency_for_segments(misses)
            if (misses and self.cursor is not None
                    and getattr(self.backing, "_link", None) is not None):
                occ = self.backing.occupancy_s(misses)
                w_miss, tr = self.backing._link.reserve(
                    self.cursor.now_s, occ, nbytes=misses * seg,
                    wave=self.cursor.wave_tag(), klass="engram")
                self.note_class("engram", misses * seg, occ)
                resv.append(tr)
            miss_path = t_miss + w_miss
        if hits and self.cursor is not None and self._cache_link is not None:
            w_hit, tr = self._cache_link.reserve(
                self.cursor.now_s, self.cache_tier.service_s(hits, seg),
                nbytes=hits * seg, wave=self.cursor.wave_tag())
            resv.append(tr)
        lat = max(t_hit + w_hit, miss_path)
        return lat, max(w_hit, w_miss), resv

    def ideal_latency_s(self, batch_tokens: int, hit_rate: float) -> float:
        """Analytic mode (the §6 formula): assume ``hit_rate`` instead of
        consulting the LRU — used by the simulator's rescue sweeps."""
        n = segment_count(self.ecfg, batch_tokens)
        hits = int(round(n * hit_rate))
        return self._split_latency(hits, n - hits)

    def _classify(self, tokens) -> tuple[int, int, int]:
        if (isinstance(tokens, Segments) or np.isscalar(tokens)
                or isinstance(tokens, int) or self.cache is None):
            return super()._classify(tokens)
        wave: WaveAccess = self.cache.access_wave(tokens)
        return wave.n_segments, wave.hits, wave.misses


# ---------------------------------------------------------------------------
# row materialization (cache-miss gathers through the Pallas path)
# ---------------------------------------------------------------------------

class TableFetcher:
    """Materializes rows for flat packed segment keys from one layer's
    lane-padded Engram tables ``(T, V, table_lanes)``. The fetcher holds
    the params leaf itself: no second copy of the table on the device.

    ``impl`` selects the gather, and the caller always names it:
      * ``"take"``   — a jitted XLA ``jnp.take`` (runs on every backend).
      * ``"kernel"`` — the variable-count Pallas DMA gather
        (``kernels/engram_gather.gather_rows_padded``): compiled for the
        TPU; ``interpret=True`` runs its body in the Pallas interpreter,
        a correctness harness for CPU tests and never a data path.
    """

    def __init__(self, ecfg: EngramConfig, tables, impl: str = "take",
                 interpret: bool = False):
        import jax
        import jax.numpy as jnp
        from ..kernels.engram_gather.ops import gather_rows_padded
        assert impl in ("kernel", "take"), impl
        self.ecfg = ecfg
        self.T, self.V, lanes = tables.shape
        assert lanes == ecfg.table_lanes, (tables.shape, ecfg.table_lanes)
        self.hd = ecfg.head_dim
        self.impl = impl
        self.tables = tables
        hd = self.hd
        if impl == "take":
            def engram_row_gather(t, g):
                return jnp.take(t.reshape(-1, t.shape[-1]), g,
                                axis=0)[:, :hd]
            self._gather = jax.jit(engram_row_gather)
        else:
            self._gather = lambda t, g: gather_rows_padded(
                t, g, width=hd, interpret=interpret)

    def gid_for(self, keys) -> np.ndarray:
        """Flat row ids in this fetcher's (padded) table space for packed
        segment keys — compute once per wave, feed ``__call__(gid=...)``."""
        return keys_to_gid(self.ecfg, keys, table_rows=self.V).reshape(-1)

    def __call__(self, keys=None, *, gid=None) -> Any:
        """Gather rows by packed segment ``keys`` or pre-split flat row ids
        ``gid`` (callers on the packed-key hot path already hold the
        in-layer row ids — passing them skips the redundant decomposition)."""
        if gid is None:
            gid = self.gid_for(keys)
        return self._gather(self.tables, np.asarray(gid, np.int32))


# ---------------------------------------------------------------------------
# strategy mapping + factory
# ---------------------------------------------------------------------------

# Which tier's latency semantics each retrieval strategy emulates when no
# explicit pool tier is requested (strategy = placement; store = cost).
STRATEGY_TIERS: dict[str, Optional[str]] = {
    "local": None,             # replicated next to the activations
    "local_kernel": None,      # same placement, Pallas gather path
    "tp": None,                # row-sharded over the model axis (HBM)
    "pooled": "CXL",           # the paper's CXL pool
    "pooled_host": "DRAM",     # host pinned memory
}


def make_store(ecfg: EngramConfig, tier: TierSpec | str | None,
               store_cfg=None, cache=None, clock=None,
               cache_link=None, fabric=None) -> EngramStore:
    """Build the store for a backing tier, honouring ``ecfg.store`` knobs
    (cache capacity / tier / admission). ``tier=None`` -> LocalStore.

    ``cache``: mount an externally-owned hot-row cache (e.g. a
    ``SharedCache.view()`` shared across engine replicas) instead of a
    private LRU — the DP front-end the router builds.

    ``clock``: bind the store to a fleet ``VirtualClock`` — the backing
    tier contends on one fleet-wide link, and the hot-row cache on
    ``cache_link`` when given (the router passes one link for a shared
    cache) or a private per-store link otherwise.

    ``fabric``: mount a sharded ``pool/fabric.PoolFabric`` as the backing
    instead of a single-link tier — the fabric owns its own clock links,
    so ``clock`` only matters for the cache front-end then."""
    scfg = store_cfg if store_cfg is not None else ecfg.store
    if tier is not None and is_chain(tier):
        from .tierchain import TierChain
        assert cache is None, \
            "shared hot-row cache views are unsupported over a tier chain " \
            "(the chain owns its DRAM front internally)"
        return TierChain(ecfg, tier, store_cfg=scfg, clock=clock,
                         fabric=fabric)
    if tier is None and fabric is None:
        return LocalStore(ecfg)
    if fabric is not None:
        from .fabric import FabricStore
        base = FabricStore(ecfg, fabric)
    else:
        base = TierStore(ecfg, tier, clock=clock)
    if cache is not None:
        tier_name = scfg.cache_tier if scfg is not None else "DRAM"
        return CachedStore(base, cache_tier=tier_name, cache=cache,
                           clock=clock, cache_link=cache_link)
    if scfg is not None and scfg.cache_rows > 0:
        admission = getattr(scfg, "admission", "lru")
        assert admission in ("lru", "tinylfu"), admission
        adm = TinyLFUAdmission() if admission == "tinylfu" else None
        return CachedStore(base, cache_tier=scfg.cache_tier,
                           cache=LRUHotRowCache(scfg.cache_rows,
                                                admission=adm),
                           clock=clock, cache_link=cache_link)
    return base


def store_for_strategy(ecfg: EngramConfig,
                       strategy: Optional[str] = None) -> EngramStore:
    """Resolve a retrieval strategy to the store modelling its tier."""
    s = strategy or ecfg.strategy
    return make_store(ecfg, STRATEGY_TIERS[s])
