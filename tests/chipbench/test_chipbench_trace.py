"""The trace reduction and the per-layer readers, on a trace recorded on a
TPU v5e (``ds7b-1chip.chat``, 211 ms: five harness steps, one of them an
admission, cut from a ``--trace 1`` run), and the FLOP and byte counters
by hand."""
import chipbench_testpaths  # noqa: F401  (sys.path for chipbench)
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import counters, run
from chipbench import trace as tr

TRACE = Path(__file__).resolve().parent / "data" / \
    "ds7b-chat-steps.xplane.pb.gz"
PEAKS = {"bf16_flops_per_s": 197e12}
DS7B = run.load_cell("ds7b-1chip.chat")[1]


@pytest.fixture(scope="module")
def red():
    return tr.load(TRACE)


def steps():
    """What the harness recorded for the five traced steps: 16 live slots
    decoding; step 147 also admitted one 180-token prompt."""
    return [run.Step(n, [180] if n == 147 else [],
                     [300 + k for k in range(16)]) for n in range(145, 150)]


def test_window_programs_and_spans(red):
    assert red.window_ns == pytest.approx(211.339755e6)
    assert [s.stats["n"] for s in red.spans_named("chipbench.step")] == \
        list(range(145, 150))
    assert len(red.programs_matching(r"jit__admit_wave_fn")) == 1
    assert 0 < red.busy_ns() < red.window_ns
    assert red.busy_ns() / red.window_ns == pytest.approx(0.87706, abs=1e-5)


def test_per_step_device_time(red):
    per = {s.n: ns for s, ns in tr.per_step(red, steps())}
    assert set(per) == set(range(145, 150))
    for n in (145, 146, 148, 149):          # decode waves: ~34.8 ms each
        assert per[n] == pytest.approx(34.77e6, rel=0.01)
    admit = {s.n: ns for s, ns in
             tr.per_step(red, steps(), r"jit__admit_wave_fn")}
    assert admit[147] == pytest.approx(11.472868e6)
    assert sum(admit.values()) == admit[147]


def test_breakdown(red):
    b = tr.breakdown(red)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    top, seconds = b["device_ops"][0]
    assert top.startswith("jit__lambda(") and seconds > 0.15
    assert all(label.startswith("chipbench.") or label.startswith("host:")
               for label, _ in b["idle_gaps"])
    assert sum(s for _, s in b["idle_gaps"]) <= \
        (red.window_ns - red.busy_ns()) * 1e-9 + 1e-12


def _ctx(red):
    return SimpleNamespace(trace=red, steps=steps(), config=DS7B,
                           peaks=PEAKS, counters={})


def test_mfu_decode_reads_pure_decode_steps(red):
    got = run.reader("mfu.decode")(_ctx(red))
    per = {s.n: ns for s, ns in tr.per_step(red, steps())}
    t = sum(per[n] for n in (145, 146, 148, 149)) * 1e-9
    flops = 4 * counters.decode_flops(DS7B, [300 + k for k in range(16)])
    assert got == pytest.approx(100 * flops / t / 197e12)
    assert 0.5 < got < 5


def test_mfu_prefill_reads_admission_programs(red):
    got = run.reader("mfu.prefill")(_ctx(red))
    want = counters.prefill_flops(DS7B, [180]) / 11.472868e-3 / 197e12
    assert got == pytest.approx(100 * want)


def test_idle_share_and_wave_gap(red):
    idle = run.reader("device.idle_share")(_ctx(red))
    assert idle == pytest.approx(100 * (1 - 0.87706), abs=1e-3)
    gap = run.reader("engine.wave_gap_ms")(_ctx(red))
    assert 0 < gap < 5


def test_readers_find_nothing_without_a_trace():
    ctx = SimpleNamespace(trace=None, steps=[], config=DS7B, peaks=PEAKS,
                          counters={})
    for name in ("device.idle_share", "engine.wave_gap_ms", "mfu.decode",
                 "mfu.prefill", "engine.pad_fraction"):
        assert run.reader(name)(ctx) is None


def test_union_and_gaps():
    iv = [(0, 10), (5, 12), (20, 30), (29, 31), (40, 41)]
    assert tr.union_length(iv) == 12 + 11 + 1
    assert tr.idle_gaps(iv, 0, 50) == [(12, 20), (31, 40), (41, 50)]
    assert tr.idle_gaps([], 3, 7) == [(3, 7)]


def test_counters_by_hand():
    c = {"hidden_size": 8, "intermediate_size": 12,
         "num_attention_heads": 2, "num_key_value_heads": 1,
         "num_hidden_layers": 3, "vocab_size": 10,
         "engram": {"layers": [1], "orders": [2, 3], "emb_dim": 4,
                    "n_heads": 2}}
    # per layer: q 8x8, k and v 8x4 each, o 8x8, MLP 3x8x12 = 480;
    # head 8x10; one Engram layer: proj (2x4)x8 + gate 8x8
    assert counters.matmul_params(c) == 3 * 480 + 80 + 128 == 1648
    per_key = 4 * 3 * 2 * 4                  # QK and PV, 3 layers, 2 heads
    assert counters.decode_flops(c, [5, 7]) == \
        2 * (2 * 1648) + per_key * 12
    assert counters.prefill_flops(c, [3]) == 3 * 2 * 1648 + per_key * 6
    assert counters.gather_bytes(c, 10) == 10 * (2 * 2 * 2 + 4)
    # 12 x (4 x 4096^2 + 3 x 4096 x 11008) + 4096 x 102400
    #   + 2 x (2560 x 4096 + 4096^2)
    assert counters.matmul_params(DS7B) == 2_902_458_368
