"""A cell served by replicas over several chips: the trace reduction of
every chip's plane, the correctness sample drawn from every replica, and
a whole run on four CPU devices (the look for a chip skipped), sound,
with one replica's tokens altered and with every replica's decode state
left unchanged."""
import chipbench_testpaths  # noqa: F401  (sys.path for chipbench)
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from chipbench import check, run
from chipbench import trace as tr

ROOT = Path(__file__).resolve().parents[2]


def _ev(mid, start_ns, dur_ns):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _tpu(k, ops):
    evs = " ".join(_ev(1, a, b - a) for a, b in ops)
    progs = " ".join(_ev(2, a, b - a) for a, b in ops)
    return (f'planes {{ id: {k + 2} name: "/device:TPU:{k}" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {evs} }} '
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {progs} }} '
            f'event_metadata {{ key: 1 value {{ id: 1 name: "fusion" }} }} '
            f'event_metadata {{ key: 2 value {{ id: 2 '
            f'name: "jit_decode_step(1)" }} }} }}')


@pytest.fixture(scope="module")
def two_chips():
    """A 10 us window; chip 0 busy 3 + 1 us, chip 1 busy 1 us (its plane
    listed first)."""
    host = ('planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python3" '
            'timestamp_ns: 0 ' + _ev(1, 1000, 10000) + ' } event_metadata { '
            'key: 1 value { id: 1 name: "chipbench.window" } } }')
    return ProfileData.from_text_proto(" ".join([
        host, _tpu(1, [(2000, 3000)]),
        _tpu(0, [(1000, 4000), (5000, 6000)])]))


def test_two_planes_give_each_chips_busy_time(two_chips):
    red = tr.reduce(two_chips, 2)
    assert red.window == (1000, 11000)
    assert red.busy_ns_per_chip() == [4000, 1000]
    assert red.busy_ns() == 4000                      # the first chip's
    idle = run.reader("device.idle_share")(type("C", (), {"trace": red}))
    assert idle == pytest.approx((60 + 90) / 2)
    b = tr.breakdown(red)
    assert b["device_ops"] == [["jit_decode_step(1)", pytest.approx(5e-6)]]
    assert b["idle_gaps"][0] == ["host:outside-spans TPU:1",
                                 pytest.approx(8e-6)]
    assert {label.split()[-1] for label, _ in b["idle_gaps"]} == \
        {"TPU:0", "TPU:1"}


def test_one_chip_cell_reduces_the_first_plane_alone(two_chips):
    red = tr.reduce(two_chips)
    assert red.busy_ns_per_chip() == [4000]
    assert all(" TPU:" not in label
               for label, _ in tr.breakdown(red)["idle_gaps"])
    with pytest.raises(ValueError, match="asks for 3"):
        tr.reduce(two_chips, 3)


def test_sample_takes_every_replica():
    finished = [((1,), [0] * n) for n in (40, 9, 30, 12, 20, 8, 16, 5)]
    replica = [0, 0, 0, 1, 0, 2, 0, 3]
    alone = check.sample_at(finished, 7, 50)
    assert alone[0] == 0 and alone == check.sample_at(finished, 7, 50,
                                                      [0] * 8)
    picked = check.sample_at(finished, 7, 50, replica)
    assert picked[0] == 0
    assert {replica[i] for i in picked} == {0, 1, 2, 3}
    assert set(picked[1:4]) == {3, 5, 7}


RUN = """
import json, sys, time
sys.path[:0] = [{tests!r}, {root!r}, {src!r}]
import jax
import tiny
from chipbench import run
fault = sys.argv[1]
if fault == "replica_token_altered":
    import repro.serving.engine as engine
    wave = engine.Engine._decode_wave
    def altered(self):
        events = wave(self)
        if self.name != "replica3":
            return events
        for k, (req, toks, fin, base) in enumerate(events):
            req.out[-1] = (toks[0] + 1) % self.cfg.vocab_size
            events[k] = (req, [req.out[-1]], fin, base)
        return events
    engine.Engine._decode_wave = altered
elif fault == "state_unchanged":
    import repro.serving.engine as engine
    build = engine.build_decode_step
    def frozen(cfg, flags, external_rows=False):
        step = build(cfg, flags, external_rows=external_rows)
        return lambda params, state, *a: (step(params, state, *a)[0], state)
    engine.build_decode_step = frozen
dep = dict(tiny.CONFIG["deployment"], replicas=4, policy="least_loaded",
           shared_cache=True)
traffic = dict(tiny.TRAFFIC, arrivals=dict(rate_per_s=80.0,
                                           process="poisson"),
               prompt=dict(tiny.TRAFFIC["prompt"], max=8))
out = run.run_cell(dict(tiny.CONFIG, deployment=dep), traffic,
                   seed=2 ** 31 + 3, seconds=1.0, trace=False,
                   peaks={{}}, device=jax.devices()[0],
                   e2e=[{{"name": "tokens_per_s", "unit": "tokens/s"}}],
                   per_layer=[], t_process=time.perf_counter(), chips=4)
print(json.dumps(out))
"""


@pytest.mark.parametrize("fault", ["none", "replica_token_altered",
                                   "state_unchanged"])
def test_four_replicas_on_four_devices(fault):
    code = RUN.format(tests=str(Path(__file__).parent), root=str(ROOT),
                      src=str(ROOT / "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code, fault], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    warm = next(line for line in proc.stdout.splitlines()
                if line.startswith("chipbench shapes_warmed="))
    assert "compiles_in_window=0" in warm
    checks = out["check"]
    assert checks["replicas_not_compared"] == {"value": 0, "limit": 0}
    if fault == "none":
        assert out["correct"] is True and out["attempted"] > 0
        assert checks["max_logit_gap"]["value"] <= 1e-3
    else:
        assert out["correct"] is False
        assert checks["max_logit_gap"]["value"] > 1e-3
