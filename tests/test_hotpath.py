"""Single-sync wave hot path: device-side key packing is bit-identical to
the host reference, decode outputs are unchanged across the packed-key
refactor (greedy + speculate, >=2 Engram layers, batched admission), the
steady-state decode wave costs exactly one device->host sync, and the
scheduler's sort-based per-slot dedup matches the legacy dict path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced

from repro.configs.base import EngramConfig, SpecConfig
from repro.core.hashing import (block_engram_indices, decode_engram_indices,
                                engram_indices, pack_segment_keys)
from repro.models.model import init_params
from repro.pool.scheduler import PrefetchScheduler
from repro.pool.store import (TableFetcher, TierStore, keys_to_gid,
                              make_store, segment_keys)
from repro.serving import Engine
from repro.spec import ConstantProposer, ScriptedProposer


def tiny_cfg():
    cfg = reduced("deepseek-7b")
    return dataclasses.replace(cfg, n_layers=4, layer_types=("attn",) * 4,
                               attn_kinds=("global",) * 4,
                               ffn_types=("dense",) * 4,
                               engram=dataclasses.replace(cfg.engram,
                                                          layers=(1, 2)))


@pytest.fixture(scope="module")
def cfg():
    return tiny_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, 0)


PROMPTS = [[5, 17, 42], [7, 8, 9, 10], [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]]


def run_engine(cfg, params, *, prompts=PROMPTS, max_new=6, max_batch=2,
               **kw):
    eng = Engine(cfg, params=params, max_batch=max_batch, max_len=64,
                 prompt_bucket=8, **kw)
    rids = [eng.submit(list(p), max_new=max_new) for p in prompts]
    stats = eng.run()
    return eng, stats, [eng.done[r].out for r in rids]


# ------------------------------------------------- device-side key packing

def test_pack_segment_keys_matches_host_reference(cfg):
    """The jitted on-device packing is bit-identical to the host
    ``segment_keys`` ground truth, for every layer slot."""
    e = cfg.engram
    rng = np.random.RandomState(0)
    idx = rng.randint(0, e.table_vocab, size=(3, 5, e.n_tables))
    packed = np.asarray(jax.jit(
        lambda i: pack_segment_keys(e, i, 2))(jnp.asarray(idx)))
    for j in range(2):
        ref = segment_keys(e, idx, layer_slot=j)
        assert np.array_equal(packed[:, :, j, :].reshape(-1), ref), j


def test_keys_to_gid_padded_tables(cfg, params):
    """Row-id derivation must honour the table's padded vocab: fetching by
    precomputed gid == fetching by packed keys == the raw table rows."""
    e = cfg.engram
    tab = params["engram"]["layers"][1]["tables"]
    fetcher = TableFetcher(e, tab)
    rng = np.random.RandomState(1)
    idx = rng.randint(0, e.table_vocab, size=(2, 3, e.n_tables))
    keys = segment_keys(e, idx, layer_slot=1)
    gid = fetcher.gid_for(keys)
    assert np.array_equal(gid, keys_to_gid(e, keys, table_rows=fetcher.V))
    by_keys = np.asarray(fetcher(keys))
    by_gid = np.asarray(fetcher(gid=gid))
    # direct reference: table t, row r from the raw (T, V_pad, hd) tables
    t_ids = np.tile(np.arange(e.n_tables), idx.size // e.n_tables)
    ref = np.asarray(tab)[t_ids, idx.reshape(-1), :e.head_dim]
    assert np.array_equal(by_keys, by_gid)
    assert np.allclose(by_keys, ref)
    # the Pallas-kernel impl (interpreted off the TPU) and the XLA-take
    # impl agree bit-for-bit
    kern = TableFetcher(e, tab, impl="kernel", interpret=True)
    assert np.array_equal(np.asarray(kern(gid=gid)), by_gid)


# --------------------------------------------- charged streams bit-for-bit

class RecordingStore:
    """Transparent store proxy recording every prefetched key stream in
    charge order (the cache's-eye view of the wave)."""

    def __init__(self, inner):
        self.inner = inner
        self.streams = []

    def prefetch(self, tokens, fetch=None):
        if not (np.isscalar(tokens) or isinstance(tokens, int)):
            self.streams.append(np.asarray(tokens, np.int64).reshape(-1))
        return self.inner.prefetch(tokens, fetch=fetch)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def test_greedy_charged_keys_bit_identical(cfg, params):
    """Stepwise greedy decode on a pool: every charged per-layer key
    stream equals the pre-refactor host packing (sync idx -> Python
    ``segment_keys``) computed independently from the engine state."""
    e = cfg.engram
    L = len(cfg.engram_layers())
    store = RecordingStore(make_store(e, "CXL"))
    eng = Engine(cfg, params=params, max_batch=1, max_len=64,
                 prompt_bucket=8, pool="CXL", emulate_step_s=5e-5,
                 store=store)
    rt = eng.runtime()
    prompt = [5, 17, 42]
    rt.submit(prompt, max_new=5)

    expected = []
    # admission charge: the prompt's exact-length indices per layer
    idx0 = np.asarray(engram_indices(e, np.asarray([prompt], np.int32)))
    for j in range(L):
        expected.append(segment_keys(e, idx0, layer_slot=j))
    rt.step()                                    # admit + first decode wave
    while eng.busy:
        # pre-compute what the OLD path would charge for the coming wave
        idx = np.asarray(decode_engram_indices(
            e, eng.state["last_tokens"], eng.tokens))
        for j in range(L):
            expected.append(segment_keys(e, idx[:1], layer_slot=j))
        rt.step()
    # the first decode wave's expectation (skipped above) recomputed from
    # the recorded count: waves interleave as [admit L][decode L]...
    n_decode_per_wave = L
    assert len(store.streams) >= len(expected)
    # admission streams first
    for j in range(L):
        assert np.array_equal(store.streams[j], expected[j]), ("admit", j)
    # remaining decode-wave streams, in order (skip the first decode wave
    # whose expectation we didn't capture before stepping)
    got = store.streams[L + n_decode_per_wave:]
    want = expected[L:]
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert np.array_equal(g, w), i


def test_spec_charged_keys_bit_identical(cfg, params):
    """Speculate mode: per-position charged streams equal the old
    per-(position, slot, layer) Python packing for a deterministic block."""
    e = cfg.engram
    L = len(cfg.engram_layers())
    k = 2
    store = RecordingStore(make_store(e, "CXL"))
    eng = Engine(cfg, params=params, max_batch=1, max_len=64,
                 prompt_bucket=8, pool="CXL", emulate_step_s=5e-5,
                 store=store, spec=SpecConfig(max_draft=k),
                 proposer=ConstantProposer(7))
    rt = eng.runtime()
    rt.submit([5, 17, 42], max_new=5)
    rt.step()                      # admit + spec wave 1 (not pre-captured)
    expected = []
    while eng.busy:
        block = np.asarray([[int(eng._tokens_host[0])] + [7] * k], np.int32)
        idx = np.asarray(block_engram_indices(
            e, eng.state["last_tokens"][:1], jnp.asarray(block)))
        for s in range(k + 1):
            for j in range(L):
                expected.append(
                    segment_keys(e, idx[:, s:s + 1], layer_slot=j))
        rt.step()
    per_wave = (k + 1) * L
    got = store.streams[L + per_wave:]           # skip admit + wave 1
    assert len(got) == len(expected)
    for i, (g, w) in enumerate(zip(got, expected)):
        assert np.array_equal(g, w), i


# ------------------------------------------------ output-identical decode

def test_pool_tokens_identical_to_local(cfg, params):
    """Packed-key pool decode (batched admission, mixed prompt buckets)
    emits exactly the LocalStore reference stream."""
    _, _, ref = run_engine(cfg, params, max_batch=3)
    for pool in ("CXL", "RDMA"):
        _, stats, out = run_engine(cfg, params, max_batch=3, pool=pool,
                                   emulate_step_s=5e-5)
        assert out == ref, pool


def test_spec_tokens_identical_on_pool(cfg, params):
    """Speculate mode on the packed-key path stays token-identical to
    greedy, under mixed acceptance across slots."""
    _, _, ref = run_engine(cfg, params, pool="CXL", emulate_step_s=5e-5)
    streams = [p + o for p, o in zip(PROMPTS, ref)]
    for proposer in (ScriptedProposer(streams), ConstantProposer(-1)):
        _, _, out = run_engine(cfg, params, pool="CXL", emulate_step_s=5e-5,
                               spec=SpecConfig(max_draft=3),
                               proposer=proposer)
        assert out == ref, type(proposer).__name__


def test_mixed_acceptance_per_slot_aggregates(cfg, params):
    """Sort-based packed dedup reports the same per-slot accepted/wasted
    split as the legacy dict path on a mixed-acceptance batch: one slot
    replays a scripted stream (full acceptance), the other gets garbage
    drafts (zero acceptance)."""
    _, _, ref = run_engine(cfg, params, prompts=PROMPTS[:2], pool="RDMA",
                           emulate_step_s=5e-5)

    class SplitProposer:
        """Oracle for slot 0, adversarial for slot 1."""
        def __init__(self, streams):
            self.oracle = ScriptedProposer(streams)
        def begin(self, slot, context): pass
        def observe(self, slot, context): pass
        def end(self, slot): pass
        def propose(self, slot, context, k):
            if slot == 0:
                return self.oracle.propose(slot, context, k)
            return [-1] * k

    streams = [p + o for p, o in zip(PROMPTS[:2], ref)]
    eng, stats, out = run_engine(cfg, params, prompts=PROMPTS[:2],
                                 pool="RDMA", emulate_step_s=5e-5,
                                 spec=SpecConfig(max_draft=3),
                                 proposer=SplitProposer(streams))
    assert out == ref
    s = eng.store.stats()
    assert s.spec_waves > 0
    # slot 1 rejected every draft: nearly all its prefetch is waste; slot 0
    # accepted everything (bar the script's padded tail wave), so its waste
    # must be strictly smaller and its accepted share strictly larger
    assert s.slot_wasted.get(1, 0) > s.slot_wasted.get(0, 0)
    assert s.slot_accepted.get(0, 0) > s.slot_accepted.get(1, 0)
    assert s.accepted_segments > 0 and s.wasted_segments > 0


def test_scheduler_packed_matches_dict_path():
    """Unit equivalence: speculative_wave + charge_spec produce identical
    aggregates and per-slot attribution through the packed (sorted) input
    and the legacy per-(position, slot) dict input."""
    ecfg = EngramConfig(layers=(1,), table_vocab=1000)
    m, K = 3, 6
    rng = np.random.RandomState(3)
    slot_ids = [0, 2]
    packed = rng.randint(0, 500, size=(len(slot_ids), m, K)).astype(np.int64)
    keys_by_pos = [[np.concatenate([packed[a, s] for a in range(2)])]
                   for s in range(m)]
    n_keep = {0: 3, 2: 1}

    def charge(**kw):
        sched = PrefetchScheduler(TierStore(ecfg, "CXL"), ecfg,
                                  layers=[1], n_layers=4)
        rep = sched.speculative_wave(keys_by_pos, 1e-3, **kw)
        sched.charge_spec(rep, n_keep=3, n_keep_by_slot=n_keep)
        return sched.store.stats()

    a = charge(slot_keys=packed, slot_ids=slot_ids)
    b = charge(slot_keys_by_pos=[
        {s: [packed[ai, pos]] for ai, s in enumerate(slot_ids)}
        for pos in range(m)])
    assert a.accepted_segments == b.accepted_segments
    assert a.wasted_segments == b.wasted_segments
    assert a.slot_accepted == b.slot_accepted
    assert a.slot_wasted == b.slot_wasted


# ------------------------------------------------------- sync budget

def test_decode_wave_single_sync(cfg, params):
    """Steady-state pool decode = exactly ONE device->host sync, enforced
    by the engine's own counter and (on real accelerators) by the
    transfer guard around the wave."""
    eng = Engine(cfg, params=params, max_batch=2, max_len=64,
                 prompt_bucket=8, pool="CXL", emulate_step_s=5e-5)
    rt = eng.runtime()
    rt.submit([5, 17, 42], max_new=10)
    rt.step()                     # admission wave
    rt.step()                     # post-admission decode (key recompute)
    for _ in range(3):            # steady state
        before = eng.stats.d2h_pulls
        with jax.transfer_guard_device_to_host("disallow"):
            rt.step()
        assert eng.stats.d2h_pulls - before == 1


def test_spec_wave_sync_budget(cfg, params):
    """Speculative wave = two syncs (packed block keys + fused verdict)."""
    eng = Engine(cfg, params=params, max_batch=2, max_len=64,
                 prompt_bucket=8, pool="CXL", emulate_step_s=5e-5,
                 spec=SpecConfig(max_draft=2), proposer=ConstantProposer(3))
    rt = eng.runtime()
    rt.submit([5, 17, 42], max_new=12)
    rt.step()                     # admission + first spec wave
    for _ in range(3):
        before = eng.stats.d2h_pulls
        with jax.transfer_guard_device_to_host("disallow"):
            rt.step()
        assert eng.stats.d2h_pulls - before == 2


def test_batched_admission_one_charge_one_prefill_per_bucket(cfg, params):
    """An admission wave charges the store once (fused prompt stream) and
    runs one multi-slot prefill per prompt bucket, while per-request
    stats (prefills, outputs) are unchanged."""
    e = cfg.engram
    store = RecordingStore(make_store(e, "CXL"))
    eng = Engine(cfg, params=params, max_batch=3, max_len=64,
                 prompt_bucket=8, pool="CXL", emulate_step_s=5e-5,
                 store=store)
    for p in PROMPTS:             # buckets 8, 8, 16 -> two prefill groups
        eng.submit(list(p), max_new=1)    # finish at prefill: admit-only wave
    eng.runtime().step()
    s = store.inner.stats()
    assert s.waves == 1                       # ONE fused admission charge
    assert eng.stats.prefills == 3
    L = len(cfg.engram_layers())
    assert len(store.streams) == L            # one stream per layer
    # the fused stream carries every request's exact-length prompt keys
    total = sum(len(p) for p in PROMPTS) * e.n_tables
    assert store.streams[0].size == total


# ------------------------------------------------- pipelined proposals

def test_pipelined_proposals_widen_window(cfg, params):
    """SpecConfig.pipeline: at full acceptance the next wave's block is
    drafted during the verify pass, its prefetch gains a verify pass of
    window credit, and the measured spec_window_steps widens — with
    token-identical output."""
    _, _, ref = run_engine(cfg, params, prompts=PROMPTS[:2], max_new=12,
                           pool="RDMA", emulate_step_s=5e-5)
    streams = [p + o for p, o in zip(PROMPTS[:2], ref)]

    def spec_run(pipeline):
        eng, stats, out = run_engine(
            cfg, params, prompts=PROMPTS[:2], max_new=12, pool="RDMA",
            emulate_step_s=5e-5,
            spec=SpecConfig(max_draft=3, pipeline=pipeline),
            proposer=ScriptedProposer(streams))
        return eng, stats, out

    eng0, st0, out0 = spec_run(False)
    eng1, st1, out1 = spec_run(True)
    assert out0 == ref and out1 == ref
    assert st0.pipelined_hits == 0
    assert st1.pipelined_hits > 0 and st1.pipelined_misses == 0
    assert st1.pipeline_hit_rate == 1.0
    d0 = eng0.store.stats().spec_window_steps
    d1 = eng1.store.stats().spec_window_steps
    assert d1 > d0 + 1.0          # ~a full verify pass of extra lead time


def test_pipelined_miss_falls_back(cfg, params):
    """A wrong prediction (zero-acceptance proposer) is discarded and the
    wave re-proposes — tokens identical, misses counted."""
    _, _, ref = run_engine(cfg, params, prompts=PROMPTS[:2], pool="CXL",
                           emulate_step_s=5e-5)
    _, stats, out = run_engine(cfg, params, prompts=PROMPTS[:2], pool="CXL",
                               emulate_step_s=5e-5,
                               spec=SpecConfig(max_draft=3, pipeline=True),
                               proposer=ConstantProposer(-1))
    assert out == ref
    assert stats.pipelined_hits == 0
    assert stats.pipelined_misses > 0
