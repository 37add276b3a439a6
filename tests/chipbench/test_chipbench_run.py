"""A whole run of the harness on the CPU at a tiny size (the look for a
chip skipped): the result line, and ``correct`` coming out false when the
timed path is broken underneath."""
import chipbench_testpaths  # noqa: F401  (sys.path for chipbench)
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

import tiny
from chipbench import check, run

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 3
E2E = [{"name": n, "unit": "u"} for n in
       ("tokens_per_s", "ttft_p90_ms", "itl_p95_ms", "peak_hbm_gb",
        "setup_s")]


def run_tiny(seed=SEED, **kw):
    return run.run_cell(tiny.CONFIG, tiny.TRAFFIC, seed=seed,
                        seconds=1.0, trace=False,
                        peaks={"bf16_flops_per_s": 1e12},
                        device=jax.devices()[0], e2e=E2E, per_layer=[],
                        t_process=time.perf_counter(), **kw)


def test_result_line():
    out = run_tiny()
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert out["correct"] is True and out["attempted"] > 0
    assert out["failed"] == 0
    # the CPU has no allocator peak: that metric is left out, not 0
    assert set(out["metrics"]) == {"tokens_per_s", "ttft_p90_ms",
                                   "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    gap = out["check"]["max_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"] == 1e-3
    json.dumps(out)


def _broken(monkeypatch, fault):
    """Break the timed path of the program under test in one way."""
    import repro.serving.engine as engine
    from repro.pool.store import TableFetcher
    if fault == "token_altered":
        wave = engine.Engine._decode_wave
        done = []

        def altered(self):
            events = wave(self)
            if events and len(done) % 4 == 3:
                req, toks, fin, base = events[0]
                req.out[-1] = (toks[0] + 1) % self.cfg.vocab_size
                events[0] = (req, [req.out[-1]], fin, base)
            done.append(1)
            return events

        monkeypatch.setattr(engine.Engine, "_decode_wave", altered)
    elif fault == "state_unchanged":
        build = engine.build_decode_step

        def frozen(cfg, flags, external_rows=False):
            step = build(cfg, flags, external_rows=external_rows)
            return lambda params, state, *a: (step(params, state, *a)[0],
                                              state)

        monkeypatch.setattr(engine, "build_decode_step", frozen)
    elif fault == "engram_row_shifted":
        gid_for = TableFetcher.gid_for
        monkeypatch.setattr(TableFetcher, "gid_for",
                            lambda self, keys: (gid_for(self, keys) + 1)
                            % (self.T * self.V))
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "engram_row_shifted"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    _broken(monkeypatch, fault)
    out = run_tiny()
    gap = out["check"]["max_logit_gap"]
    assert out["correct"] is False, gap
    assert gap["value"] > gap["limit"]


def test_control_fails_where_the_program_passes():
    """bfloat16 serving of the tiny model, driven deterministically: the
    float8 reference in the program's place reads several times the
    program's own widest gap on the same served tokens."""
    from repro.serving import EngramRuntime
    from chipbench import generator, weights
    dep = tiny.BF16["deployment"]
    cfg = run.model_config(tiny.BF16)
    params = weights.program_params(cfg, SEED, jax.devices()[0])
    rt = EngramRuntime(cfg, params=params, pool=dep["pool"],
                       max_batch=dep["max_batch"], max_len=dep["max_len"],
                       prompt_bucket=dep["prompt_bucket"])
    handles = [(r.prompt, rt.submit(list(r.prompt), r.max_new)) for r in
               generator.requests(tiny.TRAFFIC, cfg.vocab_size, SEED, 0.2)]
    rt.drain()
    picked = [(p, h.tokens) for p, h in handles]
    sound = check.widest_gap(tiny.BF16, SEED, picked, dep["max_len"])
    ctrl = check.control_gap(tiny.BF16, SEED, picked, dep["max_len"])
    assert ctrl > 3 * sound and ctrl > 0.05 > sound


def _cli(cwd, *extra):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "ds7b-1chip.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(cwd)})


def test_cli_refuses_a_cpu():
    r = _cli(ROOT)
    assert r.returncode == 2, r.stderr[-2000:]
    assert r.stdout.strip() == "" or not r.stdout.strip().splitlines()[-1] \
        .startswith("{")
    assert "needs a TPU" in r.stderr


def test_cli_refuses_a_tree_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path)
    assert r.returncode == 2 and r.stdout.strip() == ""
