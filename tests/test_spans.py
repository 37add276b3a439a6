"""Program spans and stable program names of the serving path.

A pooled engine served under ``jax.profiler`` on the CPU writes the
``repro.*`` host spans (step, admission, admission group, decode, store
charge, modelled stall, sync) nested as documented and counted as the
engine counts its waves; the modelled-stall span appears only when the
latency model reports a stall; ``EngineStats.queue_wait_s_sum`` counts a
request's wait for a slot; every jitted program of the serving path is
traced under a function name of its own (no ``<lambda>``)."""
import dataclasses

import jax
import pytest
from jax.profiler import ProfileData

from conftest import reduced

from repro.configs.base import SpecConfig, StoreConfig
from repro.pool.store import TierStore
from repro.pool.tiers import TierSpec
from repro.serving import Engine
from repro.spec import ConstantProposer

PROMPTS = [[5, 17, 42], [7, 8, 9, 10], [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]]
# a tier whose 10 ms set-up outlasts any CPU decode step: every charged
# wave reports a modelled stall
SLOW = TierSpec("slow", base_latency_s=0.01, segment_latency_s=1e-6,
                bandwidth_Bps=1e12, concurrency=64)


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str
    args: dict

    def within(self, other) -> bool:
        return other is not self and \
            other.start <= self.start and self.end <= other.end


@pytest.fixture(scope="module")
def cfg():
    c = reduced("deepseek-7b")
    return dataclasses.replace(
        c, n_layers=4, layer_types=("attn",) * 4, attn_kinds=("global",) * 4,
        ffn_types=("dense",) * 4,
        engram=dataclasses.replace(c.engram, layers=(1, 2),
                                   store=StoreConfig(cache_rows=64)))


def serve_traced(tmp_path, eng, prompts=PROMPTS, max_new=5) -> list:
    """Serve ``prompts`` to the end under the profiler; the ``repro.*``
    spans of the host planes, in start order."""
    for p in prompts:
        eng.submit(list(p), max_new=max_new)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [Span(e.start_ns, e.start_ns + e.duration_ns, e.name,
                           dict(e.stats))
                      for e in line.events if e.name.startswith("repro.")]
    return sorted(spans, key=lambda s: (s.start, -s.end))


def parent(span, spans):
    """The innermost span that holds ``span``."""
    outer = [s for s in spans if span.within(s)]
    return min(outer, key=lambda s: s.end - s.start) if outer else None


def named(spans, name):
    return [s for s in spans if s.name == name]


def test_spans_nest_and_count_as_the_waves(cfg, tmp_path):
    eng = Engine(cfg, pool="CXL", max_batch=2, max_len=64, prompt_bucket=8)
    spans = serve_traced(tmp_path, eng)
    st = eng.stats
    assert {s.name for s in spans} >= {
        "repro.step", "repro.admit", "repro.admit.group", "repro.decode",
        "repro.store.charge", "repro.sync"}
    up = {"repro.step": {None}, "repro.admit": {"repro.step"},
          "repro.admit.group": {"repro.admit"},
          "repro.decode": {"repro.step"},
          "repro.store.charge": {"repro.admit", "repro.decode"},
          "repro.store.stall": {"repro.store.charge"},
          "repro.sync": {"repro.admit.group", "repro.decode"}}
    for s in spans:
        p = parent(s, spans)
        assert (p.name if p else None) in up[s.name], (s, p)
    steps = named(spans, "repro.step")
    assert all({"live", "queued"} <= set(s.args) for s in steps)
    assert steps[0].args == {"live": 0, "queued": len(PROMPTS)}
    groups = named(spans, "repro.admit.group")
    assert len(groups) == st.prefill_waves
    assert sum(g.args["n"] for g in groups) == st.prefills == len(PROMPTS)
    for g in groups:
        assert g.args["n_pad"] >= g.args["n"] and g.args["S"] % 8 == 0
        assert len(str(g.args["rids"]).split()) == g.args["n"]
    admits = named(spans, "repro.admit")
    assert sum(a.args["n"] for a in admits) == len(PROMPTS)
    assert len(named(spans, "repro.decode")) == st.decode_steps
    assert all(d.args["live"] >= 1 for d in named(spans, "repro.decode"))
    assert len(named(spans, "repro.sync")) == st.d2h_pulls
    # one charge per admission wave and one per decode wave
    charges = named(spans, "repro.store.charge")
    assert len(charges) == len(admits) + st.decode_steps
    assert all(c.args["keys"] > 0 for c in charges)


@pytest.mark.parametrize("slow", [False, True], ids=["cxl", "slow-tier"])
def test_stall_span_only_around_a_modelled_stall(cfg, tmp_path, slow):
    store = TierStore(cfg.engram, SLOW) if slow else None
    eng = Engine(cfg, pool="CXL", store=store, max_batch=2, max_len=64,
                 prompt_bucket=8)
    spans = serve_traced(tmp_path, eng)
    stalls = named(spans, "repro.store.stall")
    st = eng.stats
    assert (st.stall_s > 0) == slow == bool(stalls)
    assert all(s.args["ms"] > 0 for s in stalls)
    assert sum(s.args["ms"] for s in stalls) == pytest.approx(
        st.stall_s * 1e3)
    for s in stalls:          # the span covers the slept stall
        assert s.end - s.start >= 0.9 * s.args["ms"] * 1e6


@pytest.mark.parametrize("chunk", [None, 8], ids=["groups", "chunked"])
def test_queue_wait_counts_the_wait_for_a_slot(cfg, chunk):
    """Two requests, one slot: the second waits for the first to finish."""
    eng = Engine(cfg, pool="CXL", max_batch=1, max_len=64, prompt_bucket=8,
                 prefill_chunk=chunk)
    a, b = (eng.submit(p, max_new=6) for p in PROMPTS[:2])
    eng.run()
    ra, rb = eng.done[a], eng.done[b]
    st = eng.stats
    assert st.prefills == 2
    # b's admission starts after a finished and before b's first token;
    # a's starts between its submission and its first token
    assert st.queue_wait_s_sum >= ra.done_s - rb.submitted_s > 0
    assert st.queue_wait_s_sum <= (ra.first_token_s - ra.submitted_s) + \
        (rb.first_token_s - rb.submitted_s)


@pytest.mark.parametrize("speculate", [False, True], ids=["greedy", "spec"])
def test_serving_programs_have_names(cfg, speculate):
    traced = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            traced.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        kw = dict(spec=SpecConfig(max_draft=2),
                  proposer=ConstantProposer(3)) if speculate else {}
        eng = Engine(cfg, pool="CXL", max_batch=2, max_len=64,
                     prompt_bucket=8, **kw)
        for p in PROMPTS:
            eng.submit(p, max_new=4)
        eng.run()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    want = {"engram_row_gather", "init_decode_state", "_admit_wave_fn"}
    want |= {"verify_step", "block_keys"} if speculate else \
        {"decode_step", "decode_keys", "_wave_sync_fn"}
    assert want <= set(traced)
    assert "<lambda>" not in traced
