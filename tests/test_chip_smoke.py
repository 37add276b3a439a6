"""chip_smoke.py off the chip: it refuses to run without a TPU or outside
a checkout, and its phases pass on the CPU at the toy config (the take
gather; the kernel through the interpreter), so a chip call only meets
faults the chip can show."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from conftest import reduced

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    return env


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_tpu_or_checkout(tmp_path, where):
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=_env(), capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_one_chip_phases_on_toy_config(capsys):
    chip_smoke.one_chip(jax, reduced("deepseek-7b"), gather="take",
                        interpret=True, max_batch=4, max_len=64)
    out = capsys.readouterr().out
    assert "smoke guarded_wave_d2h_pulls=1" in out
    assert "smoke kernel_rows_bit_equal=2 layers" in out
    assert "smoke logits_max_abs_diff=0.0 (" in out


def test_logits_phase_sees_one_wrong_row(monkeypatch):
    """The pool-vs-local logits check fails when the pooled run's fetcher
    reads one wrong row, as it would for an off-by-one in the key path."""
    cfg = reduced("deepseek-7b")
    from repro.models.model import init_params
    params = init_params(cfg, chip_smoke.SEED)
    real = chip_smoke.first_decode_logits

    def pooled_faulty(*a, pool, fault=False, **kw):
        return real(*a, pool=pool, fault=fault or pool is not None, **kw)

    monkeypatch.setattr(chip_smoke, "first_decode_logits", pooled_faulty)
    with pytest.raises(chip_smoke.SmokeFailure, match="logits differ"):
        chip_smoke.logits_phase(cfg, params, gather="take", max_batch=4,
                                max_len=64)


def test_four_replica_phase_on_fake_devices():
    code = ("import jax, chip_smoke as cs\n"
            "from repro.launch.train import reduced_config\n"
            "cs.four_chips(jax, reduced_config('deepseek-7b'), "
            "gather='take', max_batch=4, max_len=64)\n"
            "print(json.dumps({'done': True}))\n")
    proc = subprocess.run(
        [sys.executable, "-c", "import json\n" + code], cwd=ROOT,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "smoke replica_devices=0,1,2,3" in proc.stdout
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {"done": True}
