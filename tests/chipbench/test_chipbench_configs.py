"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic mix and metric readers by name, and the
configuration files build the program's models."""
import chipbench_testpaths  # noqa: F401  (sys.path for chipbench)
import dataclasses
import json
import re
from pathlib import Path

import pytest

from chipbench import run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    assert (ROOT / BENCH["command"][1]).is_file()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    # a full check of 24 cells (2 + 14 runs each) fits in 12 hours
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_finds_its_files(cell):
    w, config, traffic = run.load_cell(cell)
    assert w["chips"] == 1
    assert (ROOT / "chipbench" / "references"
            / f"{config['reference']}.py").is_file()
    assert set(config["reduced"]) == set(
        next(c for c in BENCH["configs"] if c["name"] == w["config"])
        ["reduced"])
    for kind in ("end_to_end", "per_layer"):
        for m in run.metric_specs(cell, kind):
            assert callable(run.reader(m["name"]))
    assert 0 < traffic["check"]["max_logit_gap"] < 1


def test_per_layer_metrics_name_their_cells_and_moves():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= cells and m["moves"] in e2e


def test_ds7b_config_is_the_registered_preset():
    from repro.configs.base import get_config
    _, config, _ = run.load_cell("ds7b-1chip.chat")
    cfg = run.model_config(config)
    preset = get_config("deepseek-7b-1chip")
    assert cfg.engram.store.cache_rows == 65536
    assert dataclasses.replace(cfg, engram=dataclasses.replace(
        cfg.engram, store=preset.engram.store)) == preset


def test_coder_config_keeps_the_published_widths():
    from repro.configs.base import get_config
    _, config, _ = run.load_cell("coder33b-1chip.complete")
    cfg = run.model_config(config)
    full = get_config("deepseek-coder-33b")
    for k in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "rope_theta"):
        assert getattr(cfg, k) == getattr(full, k), k
    e, fe = cfg.engram, full.engram
    assert (e.orders, e.n_heads, e.emb_dim) == (fe.orders, fe.n_heads,
                                                fe.emb_dim)
    assert cfg.n_layers == 8 and cfg.engram_layers() == (2, 3)
    assert e.table_vocab == 2 ** 17


@pytest.mark.parametrize("cell,shapes", [("ds7b-1chip.chat", 20),
                                         ("coder33b-1chip.complete", 28)])
def test_warm_up_covers_every_admission_shape(cell, shapes):
    _, config, traffic = run.load_cell(cell)
    dep = config["deployment"]
    buckets = run.prompt_buckets(traffic, dep["prompt_bucket"])
    assert len(buckets) * len(run.group_sizes(dep["max_batch"])) == shapes
    assert buckets[-1] >= traffic["prompt"]["max"]
    assert buckets[-1] + traffic["output"]["max"] <= dep["max_len"]
