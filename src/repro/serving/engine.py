"""Continuous-batching serving engine with Engram prefetch (mini-SGLang).

The engine owns the *wave primitives* — `_admit` (batched prefill into free
slots), `_decode_wave`, `_spec_wave` — each returning per-request token
events; the request-lifecycle surface (stepwise `step()`, streaming,
`cancel()`, multi-replica routing) lives above them in
`serving/runtime.py` / `serving/router.py`, and `run()` is a thin drain
loop over `runtime().step()`.

Maps the paper's §4.3 integration onto a self-contained JAX engine:

  * Initialization — the engine owns the model params; the Engram tables
    are conceptually the shared pool (strategy `pooled`/`pooled_host` on a
    mesh; `local` single-device).
  * Prefetching — on each decode wave the engine *dispatches* the Engram
    retrieval for the next tokens as its own jitted call before the decode
    step is enqueued (JAX async dispatch = the paper's asynchronous launch;
    XLA chains the dependency). Indices depend only on token IDs, so this
    is issued the moment the previous wave's tokens are sampled.
  * Computation — slot-based continuous batching: a fixed decode batch of
    ``max_batch`` slots; finished slots are freed and refilled by new
    prefills mid-flight (requests join/leave without draining the batch).
  * Speculation — with a ``SpecConfig`` the engine runs in ``speculate``
    mode: each wave a proposer drafts k tokens per live slot, the Engram
    prefetch covers the *entire* speculated window, a batched verifier
    scores the block in one pass, and rejected tails are rolled back per
    slot (serving/slots.rollback_state). With ``SpecConfig.pipeline`` the
    proposer drafts wave N+1's block *during* wave N's verify (the verify
    is dispatched asynchronously; the host proposes while it runs), so a
    surviving prediction's prefetch is issued a full verify pass early.

Single-sync wave hot path
-------------------------
Host orchestration used to cost more than the window it protected: the
index block was synced to the host and packed into segment keys in Python
twice per wave, and every emitted token was pulled with its own ``int()``.
Now the jitted index fns pack the keys on-device
(``core.hashing.pack_segment_keys``) and each wave materializes exactly
ONE device->host array through ``_host()``:

  * decode wave N ends with one fused pull carrying [this wave's sampled
    tokens | wave N+1's packed (B, 1, L, T) key tensor] — wave N+1 starts
    with its keys already on host (``_next_keys``), so its charge + miss
    fetch need zero additional syncs;
  * the speculative wave pulls one packed (B, m, L, T) key tensor and one
    fused (B, m+1) verdict ([preds | n_accept]); when pipelined proposals
    are on and EVERY live slot's prediction survived, the key tensor was
    already packed host-side from the prediction
    (``core.hashing.host_block_keys``, bit-identical) — the verdict is
    the wave's ONLY sync;
  * batched admission runs ONE multi-slot prefill per prompt bucket (not
    one batch-1 jit call per queued request) whose single pull carries
    [first tokens | the whole group's prompt keys], and the store is
    charged once per admission wave.

``stats.d2h_pulls`` counts these syncs; ``_host`` wraps them in
``jax.transfer_guard_device_to_host("allow")`` so callers can pin the
whole wave under a ``"disallow"`` guard (benchmarks/bench_hotpath.py,
tests/test_hotpath.py, chip_smoke.py). On the CPU backend the guard is
inert (host and device share memory), so the counter is the enforced
budget there; on a TPU the guard raises on any other pull.

Host spans: each wave opens ``jax.profiler.TraceAnnotation`` spans, one
per wave or group and never per key, that a profiler trace records
beside the device's programs: ``repro.step`` (runtime), ``repro.admit`` and
``repro.admit.group``, ``repro.decode``, ``repro.store.charge``,
``repro.store.stall`` (a modelled stall slept into wall time) and
``repro.sync`` (``_host``). With no profiler running a span costs under
a microsecond.

Pool-tier emulation: on real hardware the Engram fetch either hides inside
the prefetch window or stalls the step (paper §3.2). The engine delegates
that entirely to the tiered ``EngramStore`` subsystem (pool/store.py): a
``PrefetchScheduler`` issues each wave's retrieval through the store —
which owns tier latency, the optional hot-row cache, and measured hit-rate
accounting — and the engine sleeps (real point) or accounts (emulated
point) only the overshoot the scheduler reports. On pool runs the decode
rows are materialized through ``TableFetcher`` (an XLA take or the Pallas
DMA gather, as ``gather=`` names), so cache-miss materialization is
on-device end-to-end.
`pool=None` (weights local/HBM) resolves to a ``LocalStore`` with zero
emulated cost: that is the baseline, and the '+Engram (DRAM-local)'
configs of Table 2 differ only by engram compute. ``engine.store.stats()``
exposes the store-measured hit rates, stall totals, and speculation
counters (accepted/wasted prefetch, measured window depth).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..configs.base import ModelConfig, SpecConfig
from ..core.engram import retrieve
from ..core.hashing import (block_engram_indices, block_engram_keys,
                            decode_engram_indices, decode_engram_keys,
                            engram_indices, host_block_keys,
                            pack_segment_keys, prefix_chain_keys)
from ..models.model import (build_chunk_prefill, build_decode_step,
                            build_prefill_step, init_params)
from ..models.model import init_decode_state as _init_decode_state
from ..models.transformer import RunFlags
from ..pool.kvpool import KVPagePool, PoolArbiter
from ..pool.scheduler import PrefetchScheduler
from ..pool.store import TableFetcher, make_store, segment_bytes
from ..pool.tiers import pool_tier
from .clock import VirtualClock
from .slo import OverloadPolicy
from .slots import (extract_prefix, gate_state, restore_prefix,
                    select_slots, update_slots)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    submitted_s: float = 0.0
    first_token_s: float = 0.0
    done_s: float = 0.0
    status: str = "queued"     # queued | running | preempted | done |
    #                            cancelled | deferred | shed
    klass: str = "uniform"           # workload traffic class (zipf|uniform)
    slo: str = "batch"               # SLO class (serving/slo.py)
    preemptions: int = 0             # times this request was preempted
    # decoded-token count at the last idle spill: a restored slot must
    # decode another ``idle_spill_tokens`` past this ratchet before it is
    # eligible to park again (the anti-thrash guard of long-context spill)
    spill_mark: int = 0
    # virtual-clock lifecycle stamps (serving/clock.py): deterministic
    # TTFT/latency under offered load, independent of host wall time
    submitted_v: float = 0.0
    first_token_v: float = 0.0
    done_v: float = 0.0
    # per-emitted-token virtual stamps (appended by the runtime, one per
    # token in ``out`` order): consecutive diffs are the request's
    # inter-token gaps — the decode-smoothness observable bench_prefill's
    # admission-stall claim is asserted on
    stamps: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _PrefillJob:
    """One request's chunked-prefill progress: a slot is held from
    admission, and each chunk wave advances ``pos`` by up to
    ``prefill_chunk`` prompt tokens until the prompt is fully in KV and
    the slot goes live. ``restore`` is a pending prefix-cache snapshot
    (consumed lazily at the job's first chunk wave); ``resv`` holds the
    queued clock-link bookings (prefix fetch, next-chunk engram prefetch)
    outstanding between waves — refunded LIFO at the next wave or on
    mid-prefill ``cancel()``."""
    req: Request
    slot: int
    pos: int = 0                     # prompt tokens already in the KV cache
    restore: object = None           # pending prefix snapshot (host tree)
    restore_tokens: int = 0          # tokens the snapshot carries
    restore_bytes: int = 0           # snapshot bytes (the tier-fetch charge)
    chain: list = dataclasses.field(default_factory=list)  # block chain keys
    resv: list = dataclasses.field(default_factory=list)   # queued bookings
    started: bool = False


@dataclasses.dataclass
class _SpilledReq:
    """One preempted request's engine-side record (the KV snapshot itself
    is parked in the ``KVPagePool``). Lifecycle: ``phase="spilled"`` — the
    request holds no slot, its spill's write-behind link bookings sit
    outstanding in ``resv`` (refunded LIFO on cancel); a restore claims a
    free slot (``phase="restoring"``, fetch booked into ``resv``) and the
    NEXT admission wave completes it — refund-and-re-price at the wave's
    timeline position, scatter the restored state in, go live (the
    ``_PrefillJob`` restore doctrine)."""
    req: Request
    nbytes: int                      # snapshot bytes (the spill transfer)
    pages: tuple                     # kv_page_keys over the decoded stream
    n_tokens: int                    # KV positions the snapshot carries
    last_token: int                  # next decode input (tokens[] mirror)
    snapshot: object = None          # extract_prefix host tree
    slot: int = -1                   # claimed slot (phase "restoring")
    phase: str = "spilled"           # spilled | restoring
    resv: list = dataclasses.field(default_factory=list)   # queued bookings


def _rate(num: float, den: float) -> float:
    """Division-safe rate: fresh/reset stats report 0.0, never NaN/inf —
    guards against den being 0, 0.0, NaN, or negative timer noise."""
    den = float(den)
    if not (den > 0.0):               # catches 0, NaN, and negatives
        return 0.0
    return float(num) / den


@dataclasses.dataclass
class EngineStats:
    decode_steps: int = 0
    prefills: int = 0
    generated_tokens: int = 0
    wall_s: float = 0.0
    stall_s: float = 0.0
    emu_time_s: float = 0.0          # accumulated emulated step + stall time
    # --- virtual clock ----------------------------------------------------
    v_time_s: float = 0.0            # replica cursor position (clock time)
    ttft_v_sum: float = 0.0          # summed virtual submit -> first token
    # --- request lifecycle ------------------------------------------------
    requests_completed: int = 0
    requests_cancelled: int = 0
    ttft_s_sum: float = 0.0          # summed submit -> first-token latency
    queue_wait_s_sum: float = 0.0    # summed submit -> admission start
    # --- speculation ------------------------------------------------------
    spec_waves: int = 0              # verify waves run
    proposed_tokens: int = 0         # drafts proposed (k per live slot-wave)
    accepted_tokens: int = 0         # drafts that survived verification
    pipelined_hits: int = 0          # slot-waves served by a pipelined block
    pipelined_misses: int = 0        # predictions invalidated by verification
    # per-workload-class proposer quality: {klass: {proposed, accepted}}
    spec_by_class: dict = dataclasses.field(default_factory=dict)
    # --- hot path ---------------------------------------------------------
    d2h_pulls: int = 0               # device->host syncs through _host()
    # --- prefill path (chunked prefill + prefix cache) --------------------
    prefill_waves: int = 0           # admission-group / chunk compute waves
    prefill_tokens: int = 0          # useful prompt tokens actually computed
    prefill_pad_tokens: int = 0      # executed pad positions (rows + steps)
    prefill_tokens_restored: int = 0 # prompt tokens restored from the cache
    prefix_lookup_blocks: int = 0    # whole prompt blocks eligible for reuse
    prefix_hit_blocks: int = 0       # blocks served by the prefix cache
    # --- preemption + KV spill (slo.py / pool/kvpool.py) ------------------
    preemptions: int = 0             # running slots preempted under pressure
    resumes: int = 0                 # preempted requests restored + resumed
    kv_spill_bytes: int = 0          # KV bytes paged out to the pool tier
    kv_restore_bytes: int = 0        # KV bytes fetched back on resume
    kv_spill_pages: int = 0          # fixed-size pages spilled
    idle_spills: int = 0             # long-context spills (no preemption)

    @property
    def tokens_per_s(self) -> float:
        return _rate(self.generated_tokens, self.wall_s)

    @property
    def tokens_per_s_emulated(self) -> float:
        """Throughput at the emulated operating point (paper-scale steps)."""
        return _rate(self.generated_tokens, self.emu_time_s)

    @property
    def acceptance_rate(self) -> float:
        return _rate(self.accepted_tokens, self.proposed_tokens)

    @property
    def pipeline_hit_rate(self) -> float:
        """How often the proposer's during-verify draft for wave N+1
        survived wave N's verification (SpecConfig.pipeline)."""
        return _rate(self.pipelined_hits,
                     self.pipelined_hits + self.pipelined_misses)

    @property
    def tokens_per_step(self) -> float:
        return _rate(self.generated_tokens, self.decode_steps)

    @property
    def pad_row_fraction(self) -> float:
        """Fraction of executed prefill token-positions that were padding
        (pow2 group rows + right-pad / chunk-tail steps) — the compute the
        monolithic pow2 group prefill burns and chunking reclaims."""
        return _rate(self.prefill_pad_tokens,
                     self.prefill_pad_tokens + self.prefill_tokens)

    @property
    def prefix_hit_rate(self) -> float:
        """Block-granular prefix-cache hit rate over admitted prompts."""
        return _rate(self.prefix_hit_blocks, self.prefix_lookup_blocks)

    @property
    def prefill_waves_per_request(self) -> float:
        return _rate(self.prefill_waves, self.prefills)

    @property
    def prefill_compute_tokens(self) -> float:
        """Executed prefill token-positions (useful + pad): the
        prefill-FLOPs proxy ``bench_prefill`` sweeps — restored prefix
        tokens cost a tier fetch, not a forward pass, so they are absent.
        Float like every stats property (division-safe contract)."""
        return float(self.prefill_tokens + self.prefill_pad_tokens)

    @property
    def requests_per_s(self) -> float:
        return _rate(self.requests_completed, self.wall_s)

    @property
    def mean_ttft_s(self) -> float:
        """Mean submit -> first-token latency over admitted requests."""
        return _rate(self.ttft_s_sum, self.prefills)

    @property
    def mean_ttft_v(self) -> float:
        """Mean *virtual* TTFT (offered-load arrival -> first token on the
        fleet clock) over admitted requests."""
        return _rate(self.ttft_v_sum, self.prefills)

    def merge(self, other: "EngineStats") -> "EngineStats":
        """Aggregate another replica's counters into this one (the router's
        fleet view). Counters add; the clock quantities ``wall_s``,
        ``emu_time_s``, and ``v_time_s`` take the max — replicas model
        parallel hardware sharing one clock, not a serial loop (summing
        them would halve the fleet's reported throughput per doubling of
        DP). Dict fields (per-class speculation) merge key-wise."""
        for f in dataclasses.fields(self):
            a, b = getattr(self, f.name), getattr(other, f.name)
            if f.name in ("wall_s", "emu_time_s", "v_time_s"):
                setattr(self, f.name, max(a, b))
            elif isinstance(a, dict):
                for k, sub in b.items():
                    tgt = a.setdefault(k, {})
                    for kk, vv in sub.items():
                        tgt[kk] = tgt.get(kk, 0) + vv
            else:
                setattr(self, f.name, a + b)
        return self


def _bucket(n: int, bucket: int) -> int:
    return max(bucket, -(-n // bucket) * bucket)


class Engine:
    def __init__(self, cfg: ModelConfig, *, params=None,
                 flags: RunFlags = RunFlags(), max_batch: int = 8,
                 max_len: int = 512, prompt_bucket: int = 32,
                 pool: Optional[str] = None, seed: int = 0,
                 step_latency_hint_s: Optional[float] = None,
                 emulate_step_s: Optional[float] = None,
                 spec: Optional[SpecConfig] = None, proposer=None,
                 store=None, name: Optional[str] = None,
                 rid_start: int = 0, clock: Optional[VirtualClock] = None,
                 prefill_chunk: Optional[int] = None, prefix_cache=None,
                 emu_prefill_scaled: bool = False,
                 fabric=None, fabric_nodes: Optional[int] = None,
                 slo_policy: Optional[OverloadPolicy] = None,
                 kv_pool: Optional[KVPagePool] = None,
                 arbiter: Optional[PoolArbiter] = None,
                 idle_spill_tokens: Optional[int] = None,
                 gather: str = "take", device=None):
        """``emulate_step_s``: evaluate the pool stalls at a production
        operating point (ms-scale decode steps) instead of this host's
        CPU step times — stalls are then accounted in ``emu_time_s``
        rather than slept (Table 2/3 emulation).

        ``prefill_chunk``: admission runs CHUNKED — a queued request takes
        a slot immediately but its prompt enters the KV cache
        ``prefill_chunk`` tokens per ``_chunk_wave``, interleaved with the
        running slots' decode waves, so a long prompt never head-of-line-
        blocks in-flight decodes with one monolithic pow2-padded group
        prefill. None (default) keeps the legacy monolithic admission.

        ``prefix_cache``: a ``pool.cache.PrefixKVCache`` (or a fleet
        view): prompt prefix blocks are chain-hashed
        (``core.hashing.prefix_chain_keys``, block size = the chunk) and
        completed chunk-boundary states are spilled / restored through it,
        charged on the pool's clock link as byte transfers — a prefix hit
        costs a tier fetch, not a prefill pass. Requires ``prefill_chunk``
        (snapshots only exist at chunk boundaries).

        ``emu_prefill_scaled``: at the emulated operating point, charge a
        prefill wave ``emulate_step_s * executed_tokens / max_batch``
        (compute-proportional) instead of the legacy flat one-step cost —
        the model under which chunking's bounded per-wave work is visible
        in decode-wave inter-token gaps.

        ``spec``: run in speculate mode (overrides ``cfg.spec``);
        ``proposer``: inject a custom draft proposer (tests/benches);
        ``store``: inject an externally-built ``EngramStore`` (e.g. a
        ``CachedStore`` whose hot-row cache is shared across replicas —
        the router's DP front-end) instead of building one from the
        config; ``name``: replica label for router stats; ``rid_start``:
        base of this engine's request-id space (the router gives each
        replica a disjoint range so fleet-wide rids stay unique);
        ``clock``: the fleet ``VirtualClock`` (serving/clock.py) — the
        router shares one across replicas so their waves and store
        transfers interleave on a single timeline; a lone engine gets a
        private clock.

        ``fabric`` / ``fabric_nodes``: back the pool with a sharded
        ``pool/fabric.PoolFabric`` — pass a built fabric (the router
        shares ONE across replicas) or a node count for a lone engine to
        build its own on its clock. Needs a pooled tier.

        ``slo_policy``: an ``OverloadPolicy`` (serving/slo.py) — admission
        runs priority-first / deadline-ordered over the SLO classes, and
        (``policy.preempt``) a queued higher-priority request may preempt
        a strictly-lower-priority running slot: its KV is extracted
        (slots.extract_prefix), paged into ``kv_pool`` (a ``KVPagePool``;
        the router passes ONE shared pool per fleet, a lone engine builds
        its own from the policy's budget), the spill booked on the pool
        link, and the request restored-and-resumed later bit-identically.
        ``arbiter``: a ``PoolArbiter`` metering that KV traffic against
        Engram rows on the shared link + hot-row cache. ``None`` (default)
        keeps every legacy admission path bit-exact.

        ``gather``: how pool runs materialize a wave's Engram rows
        (``pool.store.TableFetcher``): ``"take"`` (XLA gather, any
        backend) or ``"kernel"`` (the Pallas DMA gather, TPU only). The
        engine never picks one by backend; ``self.gather`` says which ran.

        ``device``: a ``jax.Device`` to hold this engine's params and
        decode state (the router puts replica i on device i); None leaves
        placement to JAX's default device."""
        assert not cfg.is_encoder, "serving needs a decoder"
        self.cfg = cfg
        self.name = name
        self.flags = flags
        self.max_batch = max_batch
        self.max_len = max_len
        self.prompt_bucket = prompt_bucket
        # a chain spec ("CXL+SSD", pool/tierchain.py) resolves to its warm
        # TierSpec for engine-side gating; the store owns the full chain
        self.pool = pool_tier(pool) if pool else None
        self.emulate_step_s = emulate_step_s
        self.clock = clock if clock is not None else VirtualClock()
        self.cursor = self.clock.cursor(name if name else "engine")
        self.device = device
        self.params = params if params is not None else init_params(cfg, seed)
        if device is not None:
            self.params = jax.device_put(self.params, device)
        self.has_engram = bool(cfg.engram_layers()) and "engram" in self.params
        self._n_eng = len(cfg.engram_layers())

        spec_cfg = spec if spec is not None else cfg.spec
        self.spec = spec_cfg if (spec_cfg is not None and spec_cfg.enabled) \
            else None

        # tiered store + prefetch scheduler (pool/store.py): the single
        # owner of tier latency / cache / stall semantics. pool=None maps
        # to a LocalStore (no emulated pool cost — the Table 2 baseline).
        self.store = None
        self.scheduler = None
        self._fetchers = None
        self.fabric = fabric
        if self.has_engram:
            # link contention is modelled only at the emulated operating
            # point, where wave cadence is clock-driven and replica
            # cursors are commensurate. In real mode the cursor mirrors
            # host wall time (compile noise, serialized replicas), so
            # cross-replica queueing would double-count what the host
            # already serializes — and sleep the bogus wait.
            link_clock = self.clock if emulate_step_s is not None else None
            if store is None and fabric is None and fabric_nodes:
                assert pool is not None, "fabric_nodes needs a pooled tier"
                from ..pool.fabric import PoolFabric
                # chain specs shard their WARM level over the fabric
                self.fabric = PoolFabric(cfg.engram, int(fabric_nodes),
                                         tier=self.pool, clock=link_clock)
            self.store = store if store is not None \
                else make_store(cfg.engram, pool, clock=link_clock,
                                fabric=self.fabric)
            if hasattr(self.store, "bind_cursor"):
                # the store's link reservations run on this replica's
                # timeline position (contention is cross-replica)
                self.store.bind_cursor(self.cursor)
            self.scheduler = PrefetchScheduler(self.store, cfg.engram,
                                               layers=cfg.engram_layers(),
                                               n_layers=cfg.n_layers)
            if self.pool is not None:
                # decode miss-path materialization: the store's pool read
                # gathers the wave's rows on the device, straight from the
                # params' tables
                self._fetchers = [
                    TableFetcher(cfg.engram,
                                 self.params["engram"]["layers"][j]["tables"],
                                 impl=gather)
                    for j in range(self._n_eng)]
        self.gather = gather

        self._pool_mode = self.pool is not None and self.has_engram
        # jitted fused index+key fns: keys are packed on-device (one int64
        # (B, S, L, T) tensor covers every Engram layer's stream), so each
        # charged wave costs ONE host sync instead of sync + L Python packs
        def decode_keys(last, tok):
            return decode_engram_keys(cfg.engram, last, tok, self._n_eng)

        self._decode_keys = jax.jit(decode_keys) if self._pool_mode \
            else None
        self._wave_sync = (jax.jit(self._wave_sync_fn)
                           if self._pool_mode else None)
        # unpadded prefill caches: update_slots writes them at [0, S) of
        # the slot, so no max_len-padded copy of the group's KV exists
        self._prefill_fn = build_prefill_step(cfg, flags)
        self._prefill = jax.jit(self._prefill_fn)
        # every step that returns the engine's next decode state donates
        # the current one: the KV cache is updated in place instead of a
        # second max_batch x max_len copy living through each wave
        self._admit_wave = jax.jit(self._admit_wave_fn, donate_argnums=(1, 2))
        # chunked-prefill admission (None = legacy monolithic groups)
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        self.prefix_cache = prefix_cache
        self.emu_prefill_scaled = bool(emu_prefill_scaled)
        self._prefill_jobs: dict[int, _PrefillJob] = {}
        self._chunk_wave_jit = None
        if self.prefix_cache is not None:
            assert self.prefill_chunk is not None, \
                "prefix_cache needs prefill_chunk (snapshots live at " \
                "chunk boundaries)"
            assert self.prefix_cache.block_tokens == self.prefill_chunk, \
                (self.prefix_cache.block_tokens, self.prefill_chunk)
        if self.prefill_chunk is not None:
            self._chunk_core = build_chunk_prefill(cfg, flags)
            self._chunk_wave_jit = jax.jit(self._chunk_wave_fn,
                                           donate_argnums=(1, 2))
            # fresh-slot template: zeroed batch-1 state scattered over a
            # freed slot before its first chunk (positions/last_tokens of
            # the previous occupant must not leak into the new prompt)
            self._state1 = self._fresh_state(1)
        self._decode_fn = build_decode_step(cfg, flags)
        self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
        self._decode_ext_fn = build_decode_step(cfg, flags,
                                                external_rows=True) \
            if self.has_engram else None
        self._decode_ext = jax.jit(self._decode_ext_fn, donate_argnums=(1,)) \
            if self._decode_ext_fn else None
        # chunked mode: while prefill jobs are in flight, decode waves run
        # GATED (serving/slots.gate_state) — a mid-prefill slot's
        # positions/last_tokens must not advance under it between chunk
        # waves (the decode wave's garbage KV write at the un-advanced
        # position is overwritten by the job's next real write there)
        self._decode_gated = None
        self._decode_ext_gated = None
        if self.prefill_chunk is not None:
            assert self.spec is None, \
                "chunked prefill does not compose with speculative " \
                "decoding (the verify pass is ungated)"
            self._decode_gated = jax.jit(self._decode_gated_fn,
                                         donate_argnums=(1,))
            if self._decode_ext_fn is not None:
                self._decode_ext_gated = jax.jit(self._decode_ext_gated_fn,
                                                 donate_argnums=(1,))
        self._prefetch = jax.jit(self._prefetch_fn) if self.has_engram else None
        self._insert = jax.jit(update_slots, donate_argnums=(0,))

        # speculate mode: verifier + proposer + block-shaped retrieval
        self.proposer = None
        self._verify = None
        self._verify_ext = None
        self._block_keys = None
        self._block_prefetch = None
        if self.spec is not None:
            from ..spec.proposer import make_proposer
            from ..spec.verifier import build_verifier
            self.proposer = proposer if proposer is not None \
                else make_proposer(cfg, self.spec, flags=flags, seed=seed)
            self._verify = jax.jit(
                self._fuse_verdict(build_verifier(cfg, flags)),
                donate_argnums=(1,))
            if self.has_engram:
                self._verify_ext = jax.jit(self._fuse_verdict(
                    build_verifier(cfg, flags, external_rows=True)),
                    donate_argnums=(1,))
                if self._pool_mode:
                    def block_keys(last, block):
                        return block_engram_keys(cfg.engram, last, block,
                                                 self._n_eng)
                    self._block_keys = jax.jit(block_keys)
                self._block_prefetch = jax.jit(self._block_prefetch_fn)

        self.state = self._fresh_state(max_batch)
        self.slots: list[Optional[Request]] = [None] * max_batch
        self.tokens = self._place(jnp.zeros((max_batch,), jnp.int32))
        self.queue: deque[Request] = deque()
        self.done: dict[int, Request] = {}
        self.cancelled: dict[int, Request] = {}
        self.stats = EngineStats()
        self._rid = int(rid_start)
        self._runtime = None
        # wall time of the last waves: the stall model's hideable window
        self._step_times: deque[float] = deque(maxlen=32)
        if step_latency_hint_s:
            self._step_times.append(step_latency_hint_s)
        # --- single-sync hot-path state ---------------------------------
        self._free: deque[int] = deque(range(max_batch))   # free slot ids
        self._tokens_host = np.zeros((max_batch,), np.int64)  # self.tokens
        self._next_keys: Optional[np.ndarray] = None  # (B,1,L,T) prefetched
        self._prompt_buf = np.zeros((max_batch, prompt_bucket), np.int32)
        # slot -> (base_len, expected_tail, next_drafts, host_keys, resv):
        # the pipelined prediction for the slot's next wave, plus (pool
        # mode) the host-packed keys that make a fully-hit spec wave
        # single-sync and the clock link reservation its prefetch booked
        self._pipelined: dict[int, tuple] = {}

        # --- overload policy: SLO admission + preemption (serving/slo.py)
        self.slo_policy = slo_policy
        self.arbiter = arbiter
        self.kv_pool = kv_pool
        if slo_policy is not None and slo_policy.preempt:
            assert self.spec is None, \
                "preemption does not compose with speculative decoding " \
                "(a preempted slot's pipelined drafts have no rollback)"
            if self.kv_pool is None:
                self.kv_pool = KVPagePool(slo_policy.spill_pool_bytes,
                                          slo_policy.spill_page_tokens)
        # --- long-context idle spill (no preemption; ROADMAP item 1) -----
        # a running slot whose decoded stream has grown by this many
        # tokens since admission / its last spill may park its KV in the
        # pool when queued demand exceeds the free slots — freeing the
        # slot for fresh admits without any SLO-priority preemption. The
        # two-phase restore path resumes it bit-identically later.
        self.idle_spill_tokens = int(idle_spill_tokens) \
            if idle_spill_tokens else None
        if self.idle_spill_tokens is not None:
            assert self.spec is None, \
                "idle spill does not compose with speculative decoding " \
                "(a parked slot's pipelined drafts have no rollback)"
            assert self.prefill_chunk is None, \
                "idle spill rides the monolithic admission wave"
            if self.kv_pool is None:
                self.kv_pool = KVPagePool(1 << 30, 8)
        # rid -> _SpilledReq: preempted requests parked in the KV pool
        self._spilled: dict[int, _SpilledReq] = {}

    def _place(self, tree):
        """Commit ``tree`` to this engine's device (no-op without one)."""
        if self.device is None:
            return tree
        return jax.device_put(tree, self.device)

    def _fresh_state(self, batch: int):
        """Zeroed decode state for ``batch`` slots, made by one program on
        this engine's device. Built eagerly, each layer stack would be
        concatenated from per-layer zeros: the KV cache twice over."""
        out = None if self.device is None else \
            jax.sharding.SingleDeviceSharding(self.device)

        def init_decode_state():
            return _init_decode_state(self.cfg, self.flags, batch,
                                      self.max_len)
        return jax.jit(init_decode_state, out_shardings=out)()

    # ------------------------------------------------------------ public API

    def submit(self, prompt: list, max_new: int = 16,
               arrival_s: Optional[float] = None,
               klass: str = "uniform", slo: str = "batch") -> int:
        """Queue a request. ``arrival_s``: its arrival time on the fleet's
        virtual clock (offered-load workloads); an idle replica fast-
        forwards to it, a busy one queues the request from that instant —
        the difference is measured queueing delay in the virtual TTFT.
        ``slo``: the request's SLO class (serving/slo.py) — drives
        priority admission and preemption under an ``OverloadPolicy``."""
        self._rid += 1
        if arrival_s is not None:
            self.cursor.advance_to(arrival_s)
        req = Request(self._rid, list(prompt), max_new,
                      submitted_s=time.perf_counter(),
                      klass=klass or "uniform", slo=slo or "batch",
                      submitted_v=arrival_s if arrival_s is not None
                      else self.cursor.now_s)
        self.queue.append(req)
        return self._rid

    @property
    def busy(self) -> bool:
        """Anything queued or mid-flight?"""
        return (bool(self.queue) or bool(self._prefill_jobs)
                or bool(self._spilled)
                or any(s is not None for s in self.slots))

    def runtime(self) -> "EngramRuntime":
        """The engine's request-lifecycle front-end (serving/runtime.py):
        stepwise `step()`, per-request streaming, `cancel()`. One runtime
        per engine — `run()` drives the same object, so batch and
        lifecycle callers share handles and stats."""
        if self._runtime is None:
            from .runtime import EngramRuntime
            self._runtime = EngramRuntime(engine=self)
        return self._runtime

    def run(self) -> EngineStats:
        """Process until queue empty and all slots idle — a thin drain
        loop over the runtime's `step()` (the legacy batch entry point)."""
        return self.runtime().drain()

    def cancel(self, rid: int) -> bool:
        """Cancel a request: drop it from the queue, or free its slot
        mid-flight. The freed slot's decode state needs no surgery — slot
        state is only ever read for live slots, and the next `_admit`
        scatter-writes a fresh prefill over it (`update_slots`), which is
        exactly the rollback. Returns False if the rid already finished
        (or was never submitted): cancelling a done request is a no-op."""
        for req in self.queue:
            if req.rid == rid:
                self.queue.remove(req)
                self._mark_cancelled(req)
                return True
        for job in list(self._prefill_jobs.values()):
            if job.req.rid == rid:
                # mid-prefill cancel: free the slot and refund the queued
                # bookings. The partially-restored / partially-prefilled
                # KV needs no surgery — slot state is only read for live
                # slots, and the next job's _start_job scatter-writes a
                # fresh (or restored) batch-1 state over it.
                self._drop_job(job)
                self._mark_cancelled(job.req)
                return True
        for slot, req in enumerate(self.slots):
            if req is not None and req.rid == rid:
                self.slots[slot] = None
                self._free.append(slot)
                self._drop_pipelined(slot)
                if self.proposer is not None:
                    self.proposer.end(slot)
                self._mark_cancelled(req)
                return True
        entry = self._spilled.get(rid)
        if entry is not None:
            # cancel mid-spill (phase "spilled": refund the write-behind
            # spill bookings) or mid-restore (phase "restoring": refund
            # the in-flight fetch AND release the claimed slot) — either
            # way NEWEST-FIRST, the Link.refund tail-rollback doctrine
            for tr in entry.resv[::-1]:
                self.clock.refund(tr)
            entry.resv.clear()
            if entry.phase == "restoring":
                self._free.append(entry.slot)
            self.kv_pool.free(rid)
            del self._spilled[rid]
            self._mark_cancelled(entry.req)
            return True
        return False

    def _drop_pipelined(self, slot: int) -> None:
        """Discard a slot's pipelined prediction and REFUND the clock-link
        bandwidth its queued speculative prefetch had booked — a cancelled
        request's in-flight transfer stops delaying other replicas."""
        pipe = self._pipelined.pop(slot, None)
        if pipe is not None and pipe[4] is not None:
            self.clock.refund(pipe[4])

    def _drop_job(self, job: _PrefillJob) -> None:
        """Retire a chunked-prefill job: refund its outstanding clock-link
        bookings NEWEST-FIRST (``Link.refund`` only rolls back the tail,
        and the job booked in issue order, so LIFO unwinds the whole run —
        the PR 5 invariant ``_propose_block`` documents) and release the
        slot."""
        for tr in job.resv[::-1]:
            self.clock.refund(tr)
        job.resv.clear()
        self._prefill_jobs.pop(job.slot, None)
        self._free.append(job.slot)

    def _mark_cancelled(self, req: Request) -> None:
        req.status = "cancelled"
        req.done_s = time.perf_counter()
        req.done_v = self.cursor.now_s
        self.cancelled[req.rid] = req
        self.stats.requests_cancelled += 1

    def warmup(self) -> None:
        """Trigger the prefill/decode compiles outside measured runs."""
        rid = self.submit([1, 2, 3], max_new=2)
        self.run()
        self.done.pop(rid, None)
        self.reset_stats()

    def reset_stats(self) -> None:
        self.stats = EngineStats()

    # -------------------------------------------------------- host syncing

    def _host(self, arr) -> np.ndarray:
        """The wave's device->host sync point. Every host materialization
        on the serving hot path goes through here, so (a) ``d2h_pulls``
        counts real syncs and (b) callers can wrap a whole wave in
        ``jax.transfer_guard_device_to_host("disallow")`` and still let
        this one pull through — any stray sync elsewhere raises."""
        self.stats.d2h_pulls += 1
        with TraceAnnotation("repro.sync"), \
                jax.transfer_guard_device_to_host("allow"):
            return np.asarray(arr)

    # ---------------------------------------------------------- prefill path

    def _admit_wave_fn(self, params, state, tokens, batch, slots):
        """One fused admission group: multi-slot prefill + argmax + slot
        scatter + (pool mode) on-device prompt-key packing. Returns the new
        engine state plus ONE packed int64 vector [first tokens | keys] —
        the group's single host pull."""
        logits, pstate = self._prefill_fn(params, batch)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)      # (n,)
        state = update_slots(state, pstate, slots)
        tokens = tokens.at[slots].set(tok)
        packed = tok
        if self._pool_mode:
            e = self.cfg.engram
            idx = engram_indices(e, batch["tokens"])             # (n,S,T)
            pk = pack_segment_keys(e, idx, self._n_eng)          # (n,S,L,T)
            packed = jnp.concatenate([tok.astype(pk.dtype), pk.reshape(-1)])
        return state, tokens, packed

    def _prompt_view(self, n: int, S: int) -> np.ndarray:
        """Zeroed (n, S) view of the preallocated prompt buffer (grown as
        needed) — admission re-fills one buffer instead of allocating a
        fresh numpy array per request."""
        if self._prompt_buf.shape[1] < S or self._prompt_buf.shape[0] < n:
            self._prompt_buf = np.zeros(
                (max(n, self._prompt_buf.shape[0]),
                 max(S, self._prompt_buf.shape[1])), np.int32)
        view = self._prompt_buf[:n, :S]
        view[:] = 0
        return view

    def _admit(self) -> list:
        """Admit queued requests into free slots — batched: one multi-slot
        prefill per prompt bucket plus ONE fused store charge for the whole
        admission wave (the old path ran a batch-1 jit call and a separate
        charge per request).

        Wave primitive: returns ``(request, emitted_tokens, finished)``
        tuples — the runtime turns them into ``TokenEvent`` streams."""
        if self.prefill_chunk is not None:
            return self._admit_chunked()
        fills = []
        if self.slo_policy is not None:
            # SLO admission: restores complete + preemption may free slots
            # even when the queue is empty, so this runs unconditionally
            for req in self._overload_admit():
                self.queue.remove(req)
                fills.append((self._free.popleft(), req))
            if not fills:
                return []
        elif self.idle_spill_tokens is not None:
            # long-context spill: complete last wave's restores, park
            # eligible long-running slots when the queue outstrips the
            # free slots, fill fresh admits FIRST, then let parked
            # requests claim only the leftover slots (park/resume thrash
            # would otherwise ping-pong one slot between two requests)
            self._complete_restores()
            self._idle_spill_for_queue()
            while self._free and self.queue:
                fills.append((self._free.popleft(), self.queue.popleft()))
            parked = sorted((e for e in self._spilled.values()
                             if e.phase == "spilled"),
                            key=lambda e: e.req.rid)
            for entry in parked:
                if not self._free:
                    break
                self._begin_restore(entry, self._free.popleft())
            if not fills:
                return []
        else:
            if not (self._free and self.queue):
                return []
            while self._free and self.queue:
                fills.append((self._free.popleft(), self.queue.popleft()))
        with TraceAnnotation("repro.admit", n=len(fills)):
            return self._admit_groups(fills)

    def _admit_groups(self, fills: list) -> list:
        """Prefill ``fills`` (``(slot, request)`` pairs), one admission
        group per prompt bucket, then charge the wave's prompt keys to the
        store once."""
        events = []
        groups: dict[int, list] = {}
        for slot, req in fills:
            S = _bucket(len(req.prompt), self.prompt_bucket)
            groups.setdefault(S, []).append((slot, req))
        charge = [[] for _ in range(self._n_eng)] if self._pool_mode else None
        for S, group in sorted(groups.items()):
            n = len(group)
            # pad the group batch to a power of two: admission traces stay
            # O(log max_batch) shapes per prompt bucket instead of one per
            # group size (a churny serve loop would recompile every wave).
            # Pad rows scatter to slot ``max_batch`` — out of bounds, so
            # the state write is dropped — and their keys/tokens are
            # sliced off on the host.
            n_pad = 1 << (n - 1).bit_length()
            with TraceAnnotation("repro.admit.group", S=S, n=n, n_pad=n_pad,
                                 rids=" ".join(str(r.rid) for _, r in group)):
                self.cursor.next_wave()
                t_g = time.perf_counter()
                buf = self._prompt_view(n_pad, S)
                lens = np.ones((n_pad,), np.int32)
                for r, (_, req) in enumerate(group):
                    buf[r, :len(req.prompt)] = req.prompt
                    lens[r] = len(req.prompt)
                    self.stats.queue_wait_s_sum += t_g - req.submitted_s
                # prefill compute accounting: the group executes every one
                # of its n_pad x S token-positions — right-pad and pow2 pad
                # rows included — which is exactly the waste chunking
                # reclaims
                useful = int(lens[:n].sum())
                self.stats.prefill_waves += 1
                self.stats.prefill_tokens += useful
                self.stats.prefill_pad_tokens += n_pad * S - useful
                emu_s = None
                if self.emulate_step_s is not None:
                    # one bucketed multi-slot prefill: flat one batched
                    # step, or compute-proportional under emu_prefill_scaled
                    emu_s = self._prefill_step_s(n_pad * S)
                    self.stats.emu_time_s += emu_s
                slots_j = jnp.asarray([s for s, _ in group]
                                      + [self.max_batch] * (n_pad - n),
                                      jnp.int32)
                batch = {"tokens": jnp.asarray(buf),
                         "lengths": jnp.asarray(lens)}
                self.state, self.tokens, packed = self._admit_wave(
                    self.params, self.state, self.tokens, batch, slots_j)
                packed = self._host(packed)          # ONE pull per group
                toks = packed[:n]
                if self._pool_mode:
                    pk = packed[n_pad:].reshape(n_pad, S, self._n_eng,
                                                -1)[:n]
                    for r, (_, req) in enumerate(group):
                        live = pk[r, :lens[r]]   # drop right-pad positions
                        for j in range(self._n_eng):
                            charge[j].append(live[:, j, :].reshape(-1))
                t_now = time.perf_counter()
                # the group's prefill is one batched step on the timeline
                self.cursor.advance(emu_s if emu_s is not None
                                    else t_now - t_g)
                for r, (slot, req) in enumerate(group):
                    tok = int(toks[r])
                    req.out.append(tok)
                    req.first_token_s = t_now
                    req.status = "running"
                    self.slots[slot] = req
                    self._tokens_host[slot] = tok
                    self.stats.prefills += 1
                    self.stats.generated_tokens += 1
                    self.stats.ttft_s_sum += t_now - req.submitted_s
                    if self.proposer is not None:
                        self.proposer.begin(slot, req.prompt + req.out)
                    events.append((req, [tok], self._finish_if_done(slot),
                                   len(req.out) - 1))
        if self._pool_mode:
            # one fused charge: the admission wave's full prompt-key
            # stream per layer (a configured hot-row cache warms on it)
            self._charge_wave([np.concatenate(c) for c in charge])
        # virtual first-token stamps AFTER the fused charge: the prompt
        # retrieval's stall is part of the admission wave, so the
        # tier-dependent term lands in every admitted request's TTFT_v
        t_v = self.cursor.now_s
        for req, _, finished, _ in events:
            req.first_token_v = t_v
            self.stats.ttft_v_sum += t_v - req.submitted_v
            if finished:
                req.done_v = t_v
        self._next_keys = None      # decode keys were computed pre-admit
        return events

    # ------------------------------------------------- chunked prefill path

    def _admit_chunked(self) -> list:
        """Chunked admission: a queued request claims a free slot
        immediately as a ``_PrefillJob`` — no compute happens here. Its
        prompt enters the KV cache ``prefill_chunk`` tokens per
        ``_chunk_wave`` (the runtime interleaves one chunk wave with each
        decode wave), so a long prompt never head-of-line-blocks the
        running slots behind a monolithic pow2-padded group prefill.

        With a prefix cache, the prompt's chained block keys are looked up
        here and the deepest cached boundary state is scheduled for
        restore; the hit's bytes are booked on the pool's clock link now —
        a prefix hit costs a tier fetch, not a prefill pass. The booking
        stays outstanding (refundable) until the job's first chunk wave,
        so a mid-prefill ``cancel()`` returns the bandwidth.

        Wave primitive: returns no events — a job's first token is
        emitted by the chunk wave that finishes its prompt."""
        if self.slo_policy is not None:
            reqs = self._overload_admit()
            for req in reqs:
                self.queue.remove(req)
        else:
            reqs = [self.queue.popleft()
                    for _ in range(min(len(self._free), len(self.queue)))]
        if reqs:
            with TraceAnnotation("repro.admit", n=len(reqs)):
                for req in reqs:
                    self._claim_job(req, self._free.popleft())
        return []

    def _claim_job(self, req: Request, slot: int) -> None:
        """Claim one free slot as a ``_PrefillJob`` (with the prefix-cache
        lookup + restorable-depth booking when configured)."""
        C = self.prefill_chunk
        self.stats.queue_wait_s_sum += time.perf_counter() - req.submitted_s
        job = _PrefillJob(req=req, slot=slot)
        if self.prefix_cache is not None:
            job.chain = prefix_chain_keys(req.prompt, C)
            # restorable depth is capped so >= 1 prompt token remains
            # to compute: snapshots carry KV state, not the logits
            # that sample the request's first token
            usable = job.chain[:(len(req.prompt) - 1) // C]
            self.stats.prefix_lookup_blocks += len(usable)
            if usable:
                n_hit, snap, nbytes = self.prefix_cache.lookup(usable)
                if n_hit:
                    job.restore = snap
                    job.restore_tokens = n_hit * C
                    job.restore_bytes = int(nbytes)
                    job.pos = n_hit * C
                    self.stats.prefix_hit_blocks += n_hit
                    self.stats.prefill_tokens_restored += n_hit * C
                    tr = self._reserve_bytes(nbytes)
                    if tr is not None:
                        job.resv.append(tr)
        req.status = "running"
        self._prefill_jobs[slot] = job

    def _start_job(self, job: _PrefillJob) -> None:
        """Lazy first-wave start: scatter a fresh batch-1 state — or the
        prefix-cache restore, KV padded back to decode capacity — over the
        job's slot. Deferred from admission so the prefix-fetch booking is
        outstanding (and refundable) until the job actually computes."""
        if job.restore is not None:
            sub = restore_prefix(job.restore, self.max_len)
            job.restore = None
        else:
            sub = self._state1
        self.state = self._insert(self.state, sub,
                                  jnp.asarray([job.slot], jnp.int32))
        job.started = True

    def _chunk_wave_fn(self, params, state, tokens, chunk, lens, slots):
        """One fused chunk-prefill wave over the active jobs: gather the
        job slots' sub-state, unroll ``prefill_chunk`` gated decode steps
        over the ragged chunk, scatter back, and sample each row's last
        valid logits. Returns the new state plus ONE packed int64 vector
        [sampled tokens | the chunk's packed engram keys] (pool mode) —
        the wave's single host pull. Pad rows (pow2 group) gather a
        clamped slot, run fully masked, and scatter out of bounds (the
        write is dropped)."""
        sub = select_slots(state, slots)
        pk = None
        if self._pool_mode:
            e = self.cfg.engram
            kidx = block_engram_indices(e, sub["last_tokens"], chunk)
            pk = pack_segment_keys(e, kidx, self._n_eng)   # (n, C, L, T)
        logits, new_sub = self._chunk_core(params, sub, chunk, lens)
        state = update_slots(state, new_sub, slots)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tokens = tokens.at[slots].set(tok)
        packed = tok
        if pk is not None:
            packed = jnp.concatenate([tok.astype(pk.dtype), pk.reshape(-1)])
        return state, tokens, packed

    def _chunk_wave(self) -> list:
        """Advance every in-flight prefill job by one chunk — a bounded
        compute wave interleaved between decode waves, with ONE host pull.
        Jobs that consume their last prompt token emit their first sampled
        token and go live as decode slots.

        Completed chunk boundaries are spilled into the prefix cache
        (host snapshot + byte-charged pool-link write), so concurrent and
        future requests sharing the prefix skip the work fleet-wide.

        Wave primitive: returns ``(request, emitted_tokens, finished)``
        tuples for the jobs whose prompt completed."""
        if not self._prefill_jobs:
            return []
        jobs = [self._prefill_jobs[s] for s in sorted(self._prefill_jobs)]
        C = self.prefill_chunk
        with TraceAnnotation("repro.admit", n=len(jobs)):
            t0 = time.perf_counter()
            self.cursor.next_wave()
            # settle the inter-wave bookings NEWEST-FIRST: Link.refund only
            # rolls back the tail, and the bookings were issued in job order,
            # so LIFO unwinds the whole run (the _propose_block doctrine) —
            # the wave re-charges through the normal path below
            for job in jobs[::-1]:
                for tr in job.resv[::-1]:
                    self.clock.refund(tr)
                job.resv.clear()
            for job in jobs:
                if not job.started:
                    if job.restore is not None and job.restore_bytes:
                        # the prefix hit's tier fetch, re-priced at this
                        # wave's timeline position; the snapshot must be on
                        # device before the chunk computes, so the transfer's
                        # completion is a charged stall
                        tr = self._reserve_bytes(job.restore_bytes)
                        if tr is not None and tr.end_s > self.cursor.now_s:
                            stall = tr.end_s - self.cursor.now_s
                            self.stats.stall_s += stall
                            self.stats.emu_time_s += stall
                            self.cursor.advance(stall)
                    self._start_job(job)
            n = len(jobs)
            # pow2 row padding: O(log max_batch) unroll traces, not one per
            # job count (same admission-trace argument as the legacy groups)
            n_pad = 1 << (n - 1).bit_length()
            buf = self._prompt_view(n_pad, C)
            lens = np.zeros((n_pad,), np.int32)
            for r, job in enumerate(jobs):
                take = min(C, len(job.req.prompt) - job.pos)
                buf[r, :take] = job.req.prompt[job.pos:job.pos + take]
                lens[r] = take
            slots_j = jnp.asarray([j.slot for j in jobs]
                                  + [self.max_batch] * (n_pad - n), jnp.int32)
            self.state, self.tokens, packed = self._chunk_wave_jit(
                self.params, self.state, self.tokens, jnp.asarray(buf),
                jnp.asarray(lens), slots_j)
            packed = self._host(packed)            # ONE pull per chunk wave
            toks = packed[:n_pad]
            # prefill compute accounting: the unroll executes n_pad x C
            # token-positions; pad = pow2 rows + each job's ragged tail steps
            useful = int(lens[:n].sum())
            self.stats.prefill_waves += 1
            self.stats.prefill_tokens += useful
            self.stats.prefill_pad_tokens += n_pad * C - useful
            emu_s = None
            if self.emulate_step_s is not None:
                emu_s = self._prefill_step_s(n_pad * C)
                self.stats.emu_time_s += emu_s
            if self._pool_mode:
                pk = packed[n_pad:].reshape(n_pad, C, self._n_eng, -1)
                charge = [[] for _ in range(self._n_eng)]
                for r in range(n):
                    live = pk[r, :lens[r]]         # drop ragged-tail positions
                    for j in range(self._n_eng):
                        charge[j].append(live[:, j, :].reshape(-1))
                self._charge_wave([np.concatenate(c) for c in charge],
                                  step_s=emu_s)
            t_now = time.perf_counter()
            self.cursor.advance(emu_s if emu_s is not None else t_now - t0)
            self._step_times.append(time.perf_counter() - t0)
            reserve = getattr(self.store, "reserve_prefetch", None) \
                if self._pool_mode else None
            events = []
            t_v = self.cursor.now_s
            for r, job in enumerate(jobs):
                job.pos += int(lens[r])
                req = job.req
                done_prompt = job.pos >= len(req.prompt)
                # spill the completed block boundary: the state at job.pos IS
                # the boundary state (KV is positional; a finishing full-block
                # wave lands exactly on one too) — future/concurrent requests
                # sharing the prefix fetch it instead of recomputing
                bi = job.pos // C - 1
                if (self.prefix_cache is not None and job.pos % C == 0
                        and 0 <= bi < len(job.chain)
                        and job.chain[bi] not in self.prefix_cache):
                    with jax.transfer_guard_device_to_host("allow"):
                        snap, nbytes = extract_prefix(self.state, job.slot,
                                                      job.pos)
                    self.stats.d2h_pulls += 1      # the spill's host snapshot
                    if self.prefix_cache.insert(job.chain[bi], snap, job.pos,
                                                nbytes):
                        self._reserve_bytes(nbytes)   # write-behind spill
                if done_prompt:
                    tok = int(toks[r])
                    req.out.append(tok)
                    req.first_token_s = t_now
                    req.first_token_v = t_v
                    self.slots[job.slot] = req
                    self._tokens_host[job.slot] = tok
                    self._prefill_jobs.pop(job.slot)
                    self.stats.prefills += 1
                    self.stats.generated_tokens += 1
                    self.stats.ttft_s_sum += t_now - req.submitted_s
                    self.stats.ttft_v_sum += t_v - req.submitted_v
                    if self.proposer is not None:
                        self.proposer.begin(job.slot, req.prompt + req.out)
                    events.append((req, [tok], self._finish_if_done(job.slot),
                                   len(req.out) - 1))
                    # the previous decode wave's prefetched keys predate this
                    # slot going live — force a recompute next decode wave
                    self._next_keys = None
                elif reserve is not None:
                    # book the NEXT chunk's engram prefetch now — in flight
                    # between waves, refunded (LIFO) and re-priced with the
                    # real keys at the next wave, or refunded outright by a
                    # mid-prefill cancel
                    nxt = min(C, len(req.prompt) - job.pos)
                    tr = reserve(nxt * self.cfg.engram.n_tables * self._n_eng)
                    if tr is not None:
                        job.resv.append(tr)
            return events

    # ----------------------------------------------------------- decode path

    def _prefetch_fn(self, params, last_tokens, token):
        e = self.cfg.engram
        idx = decode_engram_indices(e, last_tokens, token)
        rows = []
        for j, _ in enumerate(self.cfg.engram_layers()):
            tab = params["engram"]["layers"][j]["tables"]
            rows.append(retrieve(e, tab, idx, self.flags.engram_strategy))
        return rows

    def _wave_sync_fn(self, last_tokens, new_tok):
        """End-of-wave fused sync: [this wave's sampled tokens | next
        wave's packed (B·1·L·T) decode keys] in ONE integer vector — the
        decode wave's single device->host transfer."""
        keys = decode_engram_keys(self.cfg.engram, last_tokens, new_tok,
                                  self._n_eng)
        return jnp.concatenate([new_tok.astype(keys.dtype), keys.reshape(-1)])

    def _miss_fetches(self, keys: np.ndarray):
        """Per-layer fetch closures materializing a wave's rows through
        the miss-path gather (``TableFetcher``). ``keys``
        is the FULL batch's (B, S, L, T) packed-key block — decode consumes
        rows for every slot, while the store is charged with live keys
        only. Row ids are derived from the packed keys exactly once per
        wave (``TableFetcher.gid_for``) instead of the old pack-here /
        unpack-there round trip."""
        B, S = keys.shape[:2]

        def layer_fetch(j):
            gid = self._fetchers[j].gid_for(keys[:, :, j, :])
            return lambda: self._fetchers[j](gid=gid).reshape(B, S, -1)

        return [layer_fetch(j) for j in range(len(self._fetchers))]

    def _decode_gated_fn(self, params, state, tokens, live):
        """Decode step gated by slot liveness (chunked mode): dead and
        mid-prefill rows keep their positions / recurrent state — the
        prefill jobs' partial KV must not advance under a decode wave."""
        logits, new_state = self._decode_fn(params, state, tokens)
        return logits, gate_state(live, new_state, state)

    def _decode_ext_gated_fn(self, params, state, tokens, rows, live):
        logits, new_state = self._decode_ext_fn(params, state, tokens, rows)
        return logits, gate_state(live, new_state, state)

    def _decode_wave(self) -> list:
        """One batched greedy-decode wave over the live slots — exactly one
        device->host sync in steady state (see module docstring).

        Wave primitive: returns ``(request, emitted_tokens, finished)``
        tuples (see ``_admit``)."""
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return []
        with TraceAnnotation("repro.decode", live=len(active)):
            t0 = time.perf_counter()
            self.cursor.next_wave()
            B = self.max_batch
            if self.emulate_step_s is not None:
                self.stats.emu_time_s += self.emulate_step_s
            rows = None
            if self._pool_mode:
                # the active slots' real segment-key stream: the store's
                # cache measures hit rates on it, the scheduler charges the
                # overshoot.
                # Steady state reuses the keys prefetched by the previous
                # wave's fused sync; only post-admission waves recompute.
                keys = self._next_keys
                if keys is None:
                    keys = self._host(self._decode_keys(
                        self.state["last_tokens"], self.tokens))
                self._next_keys = None
                act = keys[np.asarray(active)]               # (A, 1, L, T)
                per_layer = [act[:, :, j, :].reshape(-1)
                             for j in range(self._n_eng)]
                fetch = self._miss_fetches(keys) \
                    if self._decode_ext is not None else None
                rows = self._charge_wave(per_layer, fetch=fetch)
            elif self._decode_ext is not None:
                # the paper's prefetch: retrieval dispatched as its own call,
                # materialized through the store (prefetch -> gather)
                fetch = lambda: self._prefetch(self.params,
                                               self.state["last_tokens"],
                                               self.tokens)
                rows = self.store.gather(
                    self.store.prefetch(len(active), fetch=fetch))
            if self.prefill_chunk is not None and self._prefill_jobs:
                # prefill jobs in flight: gate the state update by liveness so
                # their partial KV / positions are untouched by this wave
                live = np.zeros((B,), np.bool_)
                live[np.asarray(active)] = True
                live_j = jnp.asarray(live)
                if self._decode_ext is not None:
                    logits, self.state = self._decode_ext_gated(
                        self.params, self.state, self.tokens, rows, live_j)
                else:
                    logits, self.state = self._decode_gated(
                        self.params, self.state, self.tokens, live_j)
            elif self._decode_ext is not None:
                logits, self.state = self._decode_ext(self.params, self.state,
                                                      self.tokens, rows)
            else:
                logits, self.state = self._decode(self.params, self.state,
                                                  self.tokens)
            new_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self.tokens = new_tok
            if self._pool_mode:
                # the wave's ONE sync: sampled tokens + next wave's keys fused
                sync = self._host(self._wave_sync(self.state["last_tokens"],
                                                  new_tok))
                toks = sync[:B]
                self._next_keys = sync[B:].reshape(B, 1, self._n_eng, -1)
            else:
                toks = self._host(new_tok)
            self._tokens_host[:] = toks
            dt = time.perf_counter() - t0
            self._step_times.append(dt)
            # the wave's compute on the timeline (real runs already slept the
            # stall inside _charge_wave, so dt covers it; emulated runs add
            # the stall advance in _charge_wave itself)
            self.cursor.advance(self.emulate_step_s
                                if self.emulate_step_s is not None else dt)
            self.stats.decode_steps += 1
            events = []
            for i in active:
                req = self.slots[i]
                req.out.append(int(toks[i]))
                self.stats.generated_tokens += 1
                events.append((req, [int(toks[i])], self._finish_if_done(i),
                               len(req.out) - 1))
            return events

    # ------------------------------------------------------ speculate path

    def _block_prefetch_fn(self, params, last_tokens, block):
        """Fused block retrieval for pool=None speculation (LocalStore)."""
        e = self.cfg.engram
        idx = block_engram_indices(e, last_tokens, block)
        rows = []
        for j, _ in enumerate(self.cfg.engram_layers()):
            tab = params["engram"]["layers"][j]["tables"]
            rows.append(retrieve(e, tab, idx, self.flags.engram_strategy))
        return rows

    @staticmethod
    def _fuse_verdict(verify):
        """Wrap a verifier so its host-bound outputs — preds (B, m) and
        n_accept (B,) — come back as ONE (B, m+1) int32 verdict tensor:
        the speculative wave's single post-verify pull."""
        def verify_step(params, state, block, rows=None):
            preds, n_accept, next_tok, new_state = (
                verify(params, state, block, rows) if rows is not None
                else verify(params, state, block))
            verdict = jnp.concatenate([preds, n_accept[:, None]], axis=1)
            return verdict, next_tok, new_state
        return verify_step

    def _propose_block(self, active, k: int) -> tuple:
        """Build the wave's (B, m) block on the host: pending tokens from
        the host mirror (no device pull), drafts from surviving pipelined
        predictions where available, else fresh proposals. Returns the
        block, the hit set, and the surviving host-packed key tensors
        ``{slot: (m, L, T)}`` (the single-sync path's device-pull skip)."""
        B = self.max_batch
        block = np.zeros((B, k + 1), np.int32)
        block[:, 0] = self._tokens_host
        hits = set()
        pipe_keys: dict[int, np.ndarray] = {}
        pipes = {i: self._pipelined.pop(i, None) for i in active}
        # settle the queued prefetch bookings NEWEST-FIRST: Link.refund
        # only rolls back the tail, and the bookings were made in slot
        # order, so LIFO unwinds the whole batch (each rollback exposes
        # the previous booking as the new tail) — ascending order would
        # leak every booking but the last onto the link each wave. Either
        # way the wave re-charges through the normal path: a surviving
        # prediction at the same timeline position, a miss with the real
        # keys.
        for pipe in [p for p in pipes.values() if p is not None][::-1]:
            if pipe[4] is not None:
                self.clock.refund(pipe[4])
        for i in active:
            req = self.slots[i]
            stream = req.prompt + req.out
            drafts = None
            pipe = pipes[i]
            if pipe is not None:
                base_len, expected_tail, next_drafts, pkeys, resv = pipe
                if (len(stream) == base_len + len(expected_tail)
                        and stream[base_len:] == expected_tail):
                    drafts = next_drafts
                    hits.add(i)
                    if pkeys is not None:
                        pipe_keys[i] = pkeys
                    self.stats.pipelined_hits += 1
                else:
                    self.stats.pipelined_misses += 1
            if drafts is None:
                drafts = self.proposer.propose(i, stream, k)
            block[i, 1:] = drafts
        return block, hits, pipe_keys

    def _pipeline_proposals(self, active, block: np.ndarray, k: int) -> None:
        """Draft wave N+1's blocks while wave N's verify is in flight (the
        verify was dispatched asynchronously; this host work overlaps it).
        The optimistic context assumes full acceptance; the prediction is
        used next wave only if the emitted tail — accepted drafts plus the
        bonus token — matches it exactly.

        Pool mode additionally packs the predicted block's segment keys
        HOST-side (``core.hashing.host_block_keys``, bit-identical to the
        device path) and books the prefetch's occupancy on the pool's
        clock link now — the transfer is in flight during the verify. If
        every live slot's prediction survives, the next spec wave needs no
        device key pull at all (one sync: the fused verdict); the booking
        is refunded when the prediction is consumed or the request is
        cancelled mid-flight."""
        e = self.cfg.engram
        o = max(e.orders) if self.has_engram else 1
        reserve = getattr(self.store, "reserve_prefetch", None)
        for i in active:
            req = self.slots[i]
            stream = req.prompt + req.out
            drafts = [int(t) for t in block[i, 1:]]
            ahead = [int(t) for t in
                     self.proposer.propose(i, stream + drafts, k + 1)]
            pkeys = resv = None
            if self._pool_mode and len(stream) + len(drafts) >= o - 1:
                pkeys = host_block_keys(e, stream + drafts, ahead,
                                        self._n_eng)
                if reserve is not None:
                    resv = reserve(int(np.unique(pkeys).size))
            # surviving tail = this wave's drafts + the predicted bonus
            self._pipelined[i] = (len(stream), drafts + [ahead[0]],
                                  ahead[1:], pkeys, resv)

    def _spec_wave(self) -> list:
        """One speculative wave: propose k drafts per live slot, prefetch
        the whole block's Engram window, verify in one batched pass, roll
        back rejected tails, charge stalls for surviving positions only.
        Two host syncs total: the packed (B, m, L, T) key tensor and the
        fused (B, m+1) verdict.

        Wave primitive: returns ``(request, emitted_tokens, finished)``
        tuples (see ``_admit``)."""
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return []
        with TraceAnnotation("repro.decode", live=len(active)):
            t0 = time.perf_counter()
            self.cursor.next_wave()
            k = self.spec.max_draft
            m = k + 1
            B = self.max_batch

            block, pipe_hits, pipe_keys = self._propose_block(active, k)
            block_j = jnp.asarray(block)

            # the verify pass costs ~one decode step (memory-bound) plus a
            # small per-extra-token compute term
            step_s = self._step_estimate_s()
            verify_s = step_s * (1.0 + self.spec.verify_overhead * (m - 1))
            if self.emulate_step_s is not None:
                self.stats.emu_time_s += verify_s

            spec_report = None
            rows = None
            if self.has_engram:
                if self._pool_mode:
                    all_hit = bool(active) and \
                        all(i in pipe_keys for i in active)
                    if all_hit:
                        # SINGLE-SYNC wave: every live slot's block was
                        # predicted last wave and its keys packed host-side
                        # (bit-identical to the device path) — skip the
                        # packed-key pull; the fused verdict is the wave's
                        # only device->host transfer
                        keys = np.zeros((B, m, self._n_eng,
                                         self.cfg.engram.n_tables), np.int64)
                        for i in active:
                            keys[i] = pipe_keys[i]
                    else:
                        # ONE packed pull covers every (position, slot, layer)
                        # stream; numpy views replace the old per-cell Python
                        # packing nest, and the scheduler dedups with one sort
                        keys = self._host(self._block_keys(
                            self.state["last_tokens"], block_j))  # (B,m,L,T)
                    act = np.asarray(active)
                    ka = keys[act]                               # (A,m,L,T)
                    keys_by_pos = [
                        [ka[:, s, j, :].reshape(-1)
                         for j in range(self._n_eng)]
                        for s in range(m)]
                    # a fully pipelined block was issued a verify pass early;
                    # one straggler slot drags the fused fetch back to wave
                    # start, so the credit needs every live slot to have hit
                    early = verify_s if (active and
                                         all(i in pipe_hits for i in active)) \
                        else 0.0
                    with TraceAnnotation("repro.store.charge", keys=ka.size):
                        spec_report = self.scheduler.speculative_wave(
                            keys_by_pos, verify_s,
                            slot_keys=ka.reshape(len(active), m, -1),
                            slot_ids=active, early_issue_s=early)
                        rows = [f() for f in self._miss_fetches(keys)]
                elif self._verify_ext is not None:
                    fetch = lambda: self._block_prefetch(
                        self.params, self.state["last_tokens"], block_j)
                    rows = self.store.gather(
                        self.store.prefetch(len(active) * m, fetch=fetch))

            if rows is not None:
                verdict, next_tok, new_state = self._verify_ext(
                    self.params, self.state, block_j, rows)
            else:
                verdict, next_tok, new_state = self._verify(
                    self.params, self.state, block_j)
            self.state = new_state
            self.tokens = next_tok

            if self.spec.pipeline:
                # wave N+1's proposals, drafted while the verify is in flight
                self._pipeline_proposals(active, block, k)

            verdict = self._host(verdict)                  # (B, m+1)
            preds_np = verdict[:, :m]
            n_acc = verdict[:, m]
            # host mirror of next_tok: preds[b, n_accept[b]] by construction
            self._tokens_host[:] = preds_np[np.arange(B), n_acc]
            if spec_report is not None:
                acc_active = n_acc[np.asarray(active)]
                n_keep = int(acc_active.max()) + 1
                with TraceAnnotation("repro.store.charge"):
                    stall = self.scheduler.charge_spec(
                        spec_report, n_keep,
                        tokens_emitted=int((acc_active + 1).sum()),
                        n_keep_by_slot={i: int(n_acc[i]) + 1
                                        for i in active})
                    self.stats.stall_s += stall
                    if self.emulate_step_s is None:
                        self._sleep_stall(stall)
                    else:
                        self.stats.emu_time_s += stall
                        self.cursor.advance(stall)

            dt = time.perf_counter() - t0
            self._step_times.append(dt)
            self.cursor.advance(verify_s if self.emulate_step_s is not None
                                else dt)
            self.stats.decode_steps += 1
            self.stats.spec_waves += 1
            events = []
            for i in active:
                req = self.slots[i]
                a = int(n_acc[i])
                room = req.max_new - len(req.out)
                emit = [int(t) for t in preds_np[i, :a + 1][:room]]
                req.out.extend(emit)
                self.stats.generated_tokens += len(emit)
                self.stats.proposed_tokens += k
                self.stats.accepted_tokens += a
                by = self.stats.spec_by_class.setdefault(
                    req.klass or "uniform", {"proposed": 0, "accepted": 0})
                by["proposed"] += k
                by["accepted"] += a
                self.proposer.observe(i, req.prompt + req.out)
                events.append((req, emit, self._finish_if_done(i),
                               len(req.out) - len(emit)))
            return events

    def _finish_if_done(self, slot: int) -> bool:
        req = self.slots[slot]
        if req is not None and len(req.out) >= req.max_new:
            req.done_s = time.perf_counter()
            req.done_v = self.cursor.now_s
            req.status = "done"
            self.done[req.rid] = req
            self.slots[slot] = None
            self._free.append(slot)
            self._drop_pipelined(slot)
            self.stats.requests_completed += 1
            if self.proposer is not None:
                self.proposer.end(slot)
            return True
        return False

    # ------------------------------------- preemption + KV spill (slo.py)

    def preempt(self, slot: int) -> bool:
        """Preempt a RUNNING slot: extract its KV prefix at the decoded
        position (``slots.extract_prefix``), page the snapshot into the
        KV pool (``pool/kvpool.py``), book the spill write-behind on the
        pool link (the bookings sit outstanding in the entry, refunded
        LIFO by a mid-spill ``cancel``), and free the slot for higher-
        priority work. Returns False — and leaves the victim running —
        when the pool refuses the spill at capacity (backpressure: a
        preemption that cannot park its KV does not happen)."""
        req = self.slots[slot]
        if (req is None or req.status != "running"
                or self.kv_pool is None or not req.out):
            return False
        # KV-valid length: len(prompt) positions from prefill plus one per
        # decode wave EXCEPT the newest sampled token (out[-1]), which is
        # the next wave's input — it has no KV row yet
        pos = len(req.prompt) + len(req.out) - 1
        with jax.transfer_guard_device_to_host("allow"):
            snap, nbytes = extract_prefix(self.state, slot, pos)
        self.stats.d2h_pulls += 1          # the spill's host snapshot
        stream = (req.prompt + req.out)[:pos]
        pages = self.kv_pool.spill(req.rid, stream, snap, pos, int(nbytes))
        if pages is None:
            return False
        entry = _SpilledReq(req=req, nbytes=int(nbytes), pages=pages,
                            n_tokens=pos, last_token=int(req.out[-1]),
                            snapshot=snap)
        entry.resv = self._book_kv(entry.nbytes, len(pages), req.rid)
        self._occupy_kv_cache(entry.nbytes, pages)
        self._note_kv(entry.nbytes)
        self.slots[slot] = None
        self._free.append(slot)
        self._drop_pipelined(slot)
        if self.proposer is not None:
            self.proposer.end(slot)
        req.status = "preempted"
        req.preemptions += 1
        self._spilled[req.rid] = entry
        self.stats.preemptions += 1
        self.stats.kv_spill_bytes += entry.nbytes
        self.stats.kv_spill_pages += len(pages)
        return True

    def _book_kv(self, nbytes: int, n_pages: int, rid: int) -> list:
        """Book one KV spill/restore transfer on the pool link. With a
        page-granular arbiter each page is its own reservation under the
        shared ``"kv"`` flow owner — the link's processor-sharing wait
        lets concurrent Engram waves fair-share past the spill. Without
        one the transfer is a single monolithic UNTAGGED booking (serial
        FIFO: every Engram wave behind it eats the full horizon) — the
        no-arbiter control bench_overload measures against. Returns the
        transfers (refundable LIFO); [] when clock-unbound."""
        link = self._pool_link()
        if link is None or not nbytes or not link.bandwidth_Bps:
            return []
        resv = []
        if self.arbiter is not None and self.arbiter.paged_link and n_pages:
            base, rem = divmod(int(nbytes), n_pages)
            for p in range(n_pages):
                nb = base + (rem if p == n_pages - 1 else 0)
                if nb <= 0:
                    continue
                _, tr = link.reserve(self.cursor.now_s,
                                     float(nb) / link.bandwidth_Bps,
                                     nbytes=nb, wave=("kv", rid, p),
                                     klass="kv")
                resv.append(tr)
        else:
            _, tr = link.reserve(self.cursor.now_s,
                                 float(nbytes) / link.bandwidth_Bps,
                                 nbytes=int(nbytes), klass="kv")
            resv.append(tr)
        return resv

    def _note_kv(self, nbytes: int) -> None:
        """Charge one logical KV transfer (spill, or COMPLETED restore) to
        the store's per-class occupancy ledger (StoreStats.class_bytes) —
        claim-time pre-bookings are link-side only, so
        ``class_bytes["kv"] == kv_spill_bytes + kv_restore_bytes``."""
        note = getattr(self.store, "note_class", None)
        if note is None:
            return
        link = self._pool_link()
        busy = (float(nbytes) / link.bandwidth_Bps
                if link is not None and link.bandwidth_Bps else 0.0)
        note("kv", int(nbytes), busy)

    def _occupy_kv_cache(self, nbytes: int, pages: tuple) -> None:
        """Model landed KV pages pressuring the DRAM front (hot-row
        cache): an uncapped landing (no arbiter) occupies up to the full
        row capacity, evicting hot Engram rows — the hit-rate degradation
        bench_overload scenario C measures; the arbiter caps it at
        ``kv_cache_share``. Synthetic keys carry bit 62 so they can never
        collide with real packed segment keys."""
        cache = getattr(self.store, "cache", None)
        if cache is None or not hasattr(cache, "occupy") or not pages:
            return
        rows = max(1, int(nbytes) // max(1, segment_bytes(self.cfg.engram)))
        cap = int(getattr(cache, "capacity_rows", 0))
        if self.arbiter is not None:
            rows = self.arbiter.cache_occupancy_rows(rows, cap)
        else:
            rows = min(rows, cap)
        if rows <= 0:
            return
        base = (int(pages[0]) & 0x3FFFFFFF) << 30
        keys = (np.arange(rows, dtype=np.int64) + base) | np.int64(1 << 62)
        cache.occupy(keys)

    def _overload_admit(self) -> list:
        """SLO admission (``OverloadPolicy``): complete last wave's
        restores, preempt strictly-lower-priority running slots for the
        high-priority queue head, then fill the free slots priority-first
        / deadline-ordered from the union of spilled (resume) and queued
        candidates — a resume outranks a same-priority fresh admit (it
        holds pooled capacity and has already paid its prefill). Returns
        the queued requests to admit this wave (still in ``self.queue``;
        the caller removes them and claims slots)."""
        pol = self.slo_policy
        self._complete_restores()
        if pol.preempt and self.kv_pool is not None:
            self._preempt_for_queue()
        cands = []
        for req in self.queue:
            cands.append((-pol.priority(req.slo), 1, pol.deadline_v(req),
                          req.rid, req))
        for e in self._spilled.values():
            if e.phase == "spilled":
                cands.append((-pol.priority(e.req.slo), 0,
                              pol.deadline_v(e.req), e.req.rid, e))
        cands.sort(key=lambda c: c[:4])
        chosen = []
        budget = len(self._free)
        for c in cands:
            if budget <= 0:
                break
            if isinstance(c[4], _SpilledReq):
                self._begin_restore(c[4], self._free.popleft())
            else:
                chosen.append(c[4])
            budget -= 1
        return chosen

    def _idle_spill_for_queue(self) -> None:
        """Long-context KV spill WITHOUT priority preemption (the last
        ROADMAP item 1 bullet): when queued demand exceeds the free
        slots, running slots whose decoded stream has grown by
        ``idle_spill_tokens`` since admission (or their last spill) park
        their KV in the pool via the preempt/spill path — longest
        resident context first (the biggest capacity win), near-done
        requests spared (their restore would cost more than letting them
        finish). ``spill_mark`` ratchets at each park so a restored slot
        must decode another threshold's worth before it is eligible
        again. Per-row greedy decode is batch-composition-independent, so
        the parked request's resumed stream is bit-identical."""
        need = len(self.queue) - len(self._free)
        if need <= 0:
            return
        cands = []
        for slot, req in enumerate(self.slots):
            if req is None or req.status != "running":
                continue
            if len(req.out) - req.spill_mark < self.idle_spill_tokens:
                continue
            if req.max_new - len(req.out) <= 1:      # about to finish
                continue
            cands.append((-(len(req.prompt) + len(req.out)), slot, req))
        cands.sort()
        for _, slot, req in cands[:need]:
            mark = len(req.out)
            if self.preempt(slot):                   # may refuse (pool full)
                req.spill_mark = mark
                self.stats.idle_spills += 1

    def _preempt_for_queue(self) -> None:
        """Free slots for queued requests that strictly outrank a running
        victim. Victim choice: lowest priority first, most remaining
        decode work first (near-done requests are spared — their restore
        would cost more than letting them finish). A freed slot is
        earmarked for the queued request that forced it, so the spare
        budget is unchanged by a successful preemption."""
        pol = self.slo_policy
        waiting = sorted(self.queue,
                         key=lambda r: (-pol.priority(r.slo),
                                        pol.deadline_v(r), r.rid))
        spare = len(self._free)
        for req in waiting:
            if spare > 0:
                spare -= 1
                continue
            prio = pol.priority(req.slo)
            victim, vkey = -1, None
            for slot, run in enumerate(self.slots):
                if run is None or run.status != "running":
                    continue
                vprio = pol.priority(run.slo)
                if vprio >= prio:
                    continue
                key = (vprio, -(run.max_new - len(run.out)), slot)
                if vkey is None or key < vkey:
                    victim, vkey = slot, key
            if victim < 0 or not self.preempt(victim):
                break               # no eligible victim / pool refused

    def _begin_restore(self, entry: _SpilledReq, slot: int) -> None:
        """Phase 1 of the two-phase resume: claim the free slot and book
        the KV fetch. The spill's write-behind bookings are committed here
        (the KV is durably pooled; only the fetch remains refundable —
        a mid-restore ``cancel`` returns it and the slot). The NEXT
        admission wave completes the resume (``_complete_restores``) —
        the ``_PrefillJob`` restore doctrine."""
        entry.slot = slot
        entry.phase = "restoring"
        entry.resv = self._book_kv(entry.nbytes, len(entry.pages),
                                   entry.req.rid)

    def _complete_restores(self) -> None:
        """Phase 2: for each slot claimed last wave, refund the claim-time
        fetch NEWEST-FIRST and re-price it at this wave's timeline
        position (``Link.refund`` rolls back only the tail — the
        ``_propose_block`` doctrine), stall to the transfer's completion
        (the snapshot must be on device before the slot decodes), scatter
        the restored state in, and resume decode: per-row greedy decode
        is independent of batch composition, so the resumed token stream
        is bit-identical to the never-preempted one."""
        entries = [e for e in self._spilled.values()
                   if e.phase == "restoring"]
        if not entries:
            return
        entries.sort(key=lambda e: e.req.rid)
        for entry in entries[::-1]:
            for tr in entry.resv[::-1]:
                self.clock.refund(tr)
            entry.resv.clear()
        for entry in entries:
            resv = self._book_kv(entry.nbytes, len(entry.pages),
                                 entry.req.rid)
            end = max((tr.end_s for tr in resv), default=self.cursor.now_s)
            if end > self.cursor.now_s:
                stall = end - self.cursor.now_s
                self.stats.stall_s += stall
                if self.emulate_step_s is not None:
                    self.stats.emu_time_s += stall
                self.cursor.advance(stall)
            req = entry.req
            sub = restore_prefix(entry.snapshot, self.max_len)
            self.state = self._insert(self.state, sub,
                                      jnp.asarray([entry.slot], jnp.int32))
            self.tokens = self.tokens.at[entry.slot].set(
                jnp.int32(entry.last_token))
            self._tokens_host[entry.slot] = entry.last_token
            self.slots[entry.slot] = req
            req.status = "running"
            if self.proposer is not None:
                self.proposer.begin(entry.slot, req.prompt + req.out)
            self._note_kv(entry.nbytes)
            self.kv_pool.free(req.rid, restored=True)
            del self._spilled[req.rid]
            self.stats.resumes += 1
            self.stats.kv_restore_bytes += entry.nbytes
        # prefetched decode keys predate the restored slots going live
        self._next_keys = None

    # ------------------------------------------------------- pool emulation

    def _step_estimate_s(self) -> float:
        if self.emulate_step_s is not None:
            return self.emulate_step_s
        if not self._step_times:
            return 1e-3
        return float(np.median(self._step_times))

    def _prefill_step_s(self, executed_tokens: int) -> float:
        """Emulated cost of one prefill wave that executed
        ``executed_tokens`` token-positions: the legacy flat one-batched-
        step charge, or — under ``emu_prefill_scaled`` — compute-
        proportional, normalized so ``max_batch`` token-positions (one
        decode wave's worth of work) cost one decode step. Under the
        scaled model a monolithic pow2 group prefill's cost lands between
        two decode waves as one long stall, while a chunk wave's bounded
        work keeps inter-token gaps flat — the operating point at which
        chunking's claim is measurable."""
        if not self.emu_prefill_scaled:
            return self.emulate_step_s
        return self.emulate_step_s * max(1.0,
                                         executed_tokens / self.max_batch)

    def _pool_link(self):
        """The pool tier's clock link (prefix snapshots travel over the
        same shared medium as the engram segment fetches); None when
        clock-unbound (real mode / no pool tier)."""
        if self.store is None:
            return None
        link = getattr(self.store, "_link", None)
        if link is None:
            backing = getattr(self.store, "backing", None)
            if backing is not None:
                link = getattr(backing, "_link", None)
        return link

    def _reserve_bytes(self, nbytes: int):
        """Book a prefix-snapshot transfer (fetch or spill) on the pool
        tier's link: ``nbytes`` at the tier's bandwidth, queued at this
        replica's timeline position. Returns the ``Transfer`` (None when
        clock-unbound) — a prefix hit is a tier byte-fetch on the shared
        link, not a prefill pass."""
        link = self._pool_link()
        if link is None or not nbytes or not link.bandwidth_Bps:
            return None
        _, tr = link.reserve(self.cursor.now_s,
                             float(nbytes) / link.bandwidth_Bps,
                             nbytes=int(nbytes))
        return tr

    def _charge_wave(self, keys_per_layer: list, fetch=None, step_s=None):
        """Issue one retrieval wave through the store and charge its stall.

        ``keys_per_layer``: one flat packed segment-key array per Engram
        layer (packed on-device by the jitted index fns — the host only
        slices views), so a configured hot-row cache measures real reuse.
        The scheduler computes the per-layer window overshoot, which is
        slept (real point) or accounted (emulated point). Returns the
        per-layer gathered rows when ``fetch`` is given (a per-layer fetch
        list or a fused callable). ``step_s`` overrides the hideable
        window (a scaled prefill wave's compute is longer than one decode
        step, so its retrieval hides inside more)."""
        with TraceAnnotation("repro.store.charge",
                             keys=sum(k.size for k in keys_per_layer)):
            report = self.scheduler.step(
                keys_per_layer,
                self._step_estimate_s() if step_s is None else step_s,
                fetch=fetch)
            self.stats.stall_s += report.stall_s
            if self.emulate_step_s is None:
                self._sleep_stall(report.stall_s)
            else:
                self.stats.emu_time_s += report.stall_s
                # emulated stalls advance the virtual cursor here; real
                # stalls are slept and land in the wave's measured dt
                self.cursor.advance(report.stall_s)
            return report.gather(self.store) if fetch is not None else None

    @staticmethod
    def _sleep_stall(stall_s: float) -> None:
        """Sleep a modelled pool-tier stall into wall time (real point),
        under its own span: the time is the latency model's, not a
        measured transfer."""
        if stall_s > 0:
            with TraceAnnotation("repro.store.stall", ms=stall_s * 1e3):
                time.sleep(stall_s)
