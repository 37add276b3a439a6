"""Configuration files as the program's ModelConfig: the dense files build
what they built before the loader, a latent-attention and expert file
builds the program's own preset, the check holds the program to every
size the counters read, and unmodelled keys are refused by name."""
import chipbench_testpaths  # noqa: F401  (sys.path for chipbench)
import copy
import dataclasses
import json
import math
import zlib
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import counters, loader, run
from chipbench import weights as W

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 5

# DeepSeek-V3's keys at the sizes of the program's reduced preset
MLA_MOE = {
    "name": "deepseek-v3-671b-reduced", "reference": "mla_moe_engram",
    "model_type": "deepseek_v3", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 4, "vocab_size": 509, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "torch_dtype": "float32", "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 3, "n_shared_experts": 1,
    "moe_intermediate_size": 32, "first_k_dense_replace": 1,
    "moe_layer_freq": 1, "routed_scaling_factor": 1.0,
    "scoring_func": "softmax", "norm_topk_prob": True,
    "engram": {"orders": [2, 3], "n_heads": 4, "emb_dim": 32,
               "table_vocab": 2048, "layers": [1, 3], "strategy": "local",
               "seed": 0x5EED, "pad_token": 0},
    "deployment": {"pool": "CXL", "cache_rows": 0, "max_batch": 4,
                   "max_len": 64, "prompt_bucket": 16},
}


def with_share(c, expert_parallel):
    """``c`` with its expert layers shared by ``expert_parallel`` chips:
    the same experts held here, a router that many times as wide."""
    c = copy.deepcopy(c)
    c["deployment"]["expert_parallel"] = expert_parallel
    return c


# DeepSeek-V3's published routing, YaRN and MTP key at MLA_MOE's sizes,
# its eight experts held of sixteen routed (8 groups of 2, top 4 groups)
V3_ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
           "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
           "type": "yarn"}
V3_KEYED = with_share(dict(
    MLA_MOE, name="deepseek-v3-keyed-reduced", scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=8, topk_group=4, norm_topk_prob=True,
    routed_scaling_factor=2.5, rope_scaling=V3_ROPE,
    num_nextn_predict_layers=0,
    reduced={"num_nextn_predict_layers": [0, 1, "MTP is not served"],
             "n_routed_experts": [8, 16, "held here of 16 routed"]}), 2)


def before(c: dict):
    """The mapping the harness used before the loader (dense files)."""
    from repro.configs.base import EngramConfig, ModelConfig, StoreConfig
    e, dep = c["engram"], c["deployment"]
    eng = EngramConfig(orders=tuple(e["orders"]), n_heads=e["n_heads"],
                       emb_dim=e["emb_dim"], table_vocab=e["table_vocab"],
                       layers=tuple(e["layers"]), strategy=e["strategy"],
                       seed=e["seed"], pad_token=e["pad_token"],
                       store=StoreConfig(cache_rows=dep["cache_rows"]))
    d, H = c["hidden_size"], c["num_attention_heads"]
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=d, vocab_size=c["vocab_size"], n_heads=H,
        n_kv_heads=c["num_key_value_heads"], head_dim=d // H,
        d_ff=c["intermediate_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"],
        engram=eng)


DENSE = ["deepseek-7b-1chip", "deepseek-coder-33b-1chip"]


def dense_file(name: str) -> dict:
    return loader.read(f"chipbench/configs/{name}.json", ROOT)


@pytest.mark.parametrize("name", DENSE)
def test_dense_files_build_what_they_built_before(name):
    c = dense_file(name)
    assert run.model_config(c) == before(c)
    assert loader.mismatches(c, before(c)) == []


def test_replica_file_serves_the_one_chip_model():
    one = dense_file("deepseek-7b-1chip")
    x4 = dense_file("deepseek-7b-x4")
    assert {k: v for k, v in x4.items() if k not in
            ("name", "model", "deployment", "assumed")} == \
        {k: v for k, v in one.items() if k not in
         ("name", "deployment", "assumed")}
    assert x4["deployment"] == dict(one["deployment"], replicas=4,
                                    policy="least_loaded", shared_cache=True)
    assert set(x4["assumed"]) == set(one["assumed"])
    assert dataclasses.replace(run.model_config(x4), name=one["name"]) == \
        run.model_config(one)


def test_replica_mix_is_the_chat_mix_at_its_own_rate():
    def mix(name):
        return json.loads((ROOT / "chipbench" / "traffic"
                           / f"{name}.json").read_text())
    one, x4 = mix("ds7b-1chip.chat"), mix("ds7b-x4.chat")
    assert {k: v for k, v in x4.items() if k not in ("arrivals", "users")} \
        == {k: v for k, v in one.items() if k not in ("arrivals", "users")}
    assert x4["arrivals"]["process"] == one["arrivals"]["process"]
    assert x4["arrivals"]["rate_per_s"] > one["arrivals"]["rate_per_s"]


def test_mla_and_expert_file_builds_the_program_preset():
    from repro.configs.deepseek_v3_671b import reduced
    assert run.model_config(MLA_MOE) == reduced()


@pytest.mark.parametrize("fault,where", [
    (lambda cfg: dataclasses.replace(cfg, mla=dataclasses.replace(
        cfg.mla, q_lora_rank=16)), "wdq"),
    (lambda cfg: dataclasses.replace(cfg, mla=dataclasses.replace(
        cfg.mla, qk_rope_head_dim=4, qk_nope_head_dim=20)), "MLA dims"),
    (lambda cfg: dataclasses.replace(cfg, attn_impl="gqa"), "wq"),
    (lambda cfg: dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_experts=4)), "w_gu"),
    (lambda cfg: dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, top_k=2)), "num_experts_per_tok"),
    (lambda cfg: dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_shared=0)), "shared"),
    (lambda cfg: dataclasses.replace(
        cfg, ffn_types=("dense",) * 2 + ("moe",) * 2), "expert layers"),
])
def test_check_refuses_a_program_config_that_drops_a_size(fault, where):
    cfg = fault(run.model_config(MLA_MOE))
    bad = loader.mismatches(MLA_MOE, cfg)
    assert any(where in line for line in bad), bad
    with pytest.raises(ValueError, match="departs from the file"):
        loader.check(MLA_MOE, cfg)


def test_expert_share_maps_and_the_program_cannot_hold_it():
    """Eight experts held of sixteen routed: the file's sizes and counts
    are read, and the fallback loader's config, whose router is as wide
    as the experts it holds, is refused by it and by the check."""
    shared = with_share(MLA_MOE, 2)
    assert counters.expert_share(shared) == (8, 16)
    assert loader.expected_shapes(shared)["layer1.ffn.router"] == (64, 16)
    assert loader.expected_shapes(shared)["layer1.ffn.w_gu"] == (8, 64, 64)
    today = loader.from_file(MLA_MOE)
    bad = loader.mismatches(shared, today)
    assert any(line.startswith("layer1.ffn.router") for line in bad), bad
    with pytest.raises(ValueError, match="expert_parallel"):
        loader.from_file(shared)
    with pytest.raises(ValueError, match="layer1.ffn.router"):
        loader.check(shared, today)


# keys whose value the check compares with the program's config
CHECKED = {"rope_scaling", "scoring_func", "topk_method", "n_group"}


@pytest.mark.parametrize("key,value", [
    ("sliding_window", 4096), ("num_nextn_predict_layers", 1),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
    ("n_group", 8)])
def test_unmodelled_key_is_refused_unless_listed(key, value):
    """The fallback loader refuses a key its program cannot hold, by
    name, and the check refuses today's program for a compared key; a
    key listed as a departure is neither built nor compared."""
    c = dict(MLA_MOE, **{key: value})
    with pytest.raises(ValueError, match=key):
        loader.from_file(c)
    if key in CHECKED:
        with pytest.raises(ValueError, match=key):
            loader.check(c, loader.from_file(MLA_MOE))
    c["assumed"] = {key: "a departure: not modelled by the program"}
    assert loader.from_file(c) == loader.from_file(MLA_MOE)
    loader.check(c, loader.from_file(c))


def test_v3_keyed_file_is_modelled_and_today_s_program_is_refused():
    """V3's routing, YaRN and share are mapped; the check against today's
    program names each fact, the router's width and its bias; the
    fallback loader refuses the file, naming what it cannot hold."""
    assert loader.unmodelled(V3_KEYED) == []
    want = loader.expected_shapes(V3_KEYED)
    assert want["layer1.ffn.router_bias"] == (16,)
    assert want["layer1.ffn.router"] == (64, 16)
    assert "layer0.ffn.router_bias" not in want        # the dense layer
    assert "layer1.ffn.router_bias" not in loader.expected_shapes(MLA_MOE)
    bad = loader.mismatches(V3_KEYED, loader.from_file(MLA_MOE))
    for fact in ("scoring_func", "topk_method", "n_group", "topk_group",
                 "routed_scaling_factor", "rope_scaling",
                 "layer1.ffn.router_bias", "layer1.ffn.router:"):
        assert any(line.startswith(fact) for line in bad), (fact, bad)
    assert not any(line.startswith("norm_topk_prob") for line in bad)
    with pytest.raises(ValueError) as e:
        loader.from_file(V3_KEYED)
    for key in ("scoring_func", "topk_method", "n_group", "topk_group",
                "rope_scaling", "expert_parallel"):
        assert key in str(e.value), key


def test_check_reads_the_routing_contract_from_the_program_config(
        monkeypatch):
    """A program config that carries V3's routing attributes, its YaRN and
    router bias leaves of the file's width passes; it is refused for a
    routing fact that differs and for a bias not in float32."""
    routing = dict(scoring_func="sigmoid", topk_method="noaux_tc",
                   n_group=4, topk_group=2, norm_topk_prob=True)
    cfg = loader.from_file(MLA_MOE)
    moe = dataclasses.replace(cfg.moe, router_scale=2.5)
    prog = dataclasses.replace(cfg)
    for k, v in routing.items():         # the attributes the contract names
        object.__setattr__(moe, k, v)
    object.__setattr__(prog, "moe", moe)
    object.__setattr__(prog, "rope_scaling", tuple(V3_ROPE.items()))
    leaves = dict(loader.program_leaves(cfg))
    leaves.update({f"layer{i}.ffn.router_bias": ((8,), "float32")
                   for i in (1, 2, 3)})
    monkeypatch.setattr(loader, "program_leaves", lambda _: leaves)
    c = dict(MLA_MOE, routed_scaling_factor=2.5, rope_scaling=V3_ROPE,
             **routing)
    assert loader.mismatches(c, prog) == []
    assert [line for line in loader.mismatches(dict(c, topk_group=3), prog)
            ] == ["topk_group: file 3, program 2"]
    leaves["layer2.ffn.router_bias"] = ((8,), "bfloat16")
    assert loader.mismatches(c, prog) == \
        ["layer2.ffn.router_bias: file float32, program bfloat16"]


def test_coder_file_lists_its_rope_scaling():
    c = dense_file("deepseek-coder-33b-1chip")
    assert "rope_scaling" in c["reduced"] and "rope_scaling" not in c
    assert loader.unmodelled(dict(c, rope_scaling=c["reduced"]
                                  ["rope_scaling"][1])) == []
    assert loader.unmodelled(dict(c, attention_bias=True)) == \
        ["attention_bias"]


def test_counters_of_mla_and_shared_experts_by_hand():
    c = with_share(MLA_MOE, 2)
    # MLA: d q_lora + q_lora H (dn+dr) + d (kv+dr) + kv H (dn+dv) + H dv d
    attn = 64 * 32 + 32 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 4 * 16 * 64
    assert counters.attn_params(c) == attn == 12800
    # router d x 16, one shared expert, 3 of 16 routed experts x 8/16 here
    expert = 64 * 16 + 3 * 64 * 32 + 3 * (8 / 16) * 3 * 64 * 32
    assert counters.expert_ffn_params(c) == expert == 16384
    dense = 3 * 64 * 128
    engram = 2 * (2 * 32 * 64 + 64 * 64)
    want = 4 * attn + dense + 3 * expert + 64 * 509 + engram
    assert counters.matmul_params(c) == want == 173888
    per_key = 4 * (2 * 4 * (16 + 8) + 2 * 4 * 16)
    assert counters._attn_per_key(c) == per_key == 1280
    assert counters.decode_flops(c, [10]) == 2 * want + per_key * 10
    assert counters.prefill_flops(c, [3]) == 3 * 2 * want + per_key * 6
    # without q_lora_rank the query is one d x H (dn+dr) matrix
    assert counters.attn_params(dict(c, q_lora_rank=None)) == \
        64 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 4 * 16 * 64
    # every expert held: the routed experts' whole work
    assert counters.expert_ffn_params(MLA_MOE) == \
        64 * 8 + 3 * 64 * 32 + 3 * 3 * 64 * 32
    assert counters.expert_layers(dict(MLA_MOE, first_k_dense_replace=0,
                                       moe_layer_freq=2)) == [0, 2]


@pytest.mark.parametrize("name,want", [
    ("deepseek-7b-1chip",
     (2902458368, 196608, 11688476672.0, 4046025261056.0)),
    ("deepseek-coder-33b-1chip",
     (4613210112, 229376, 18544590848.0, 6418542821376.0))])
def test_dense_counts_unchanged(name, want):
    c = dense_file(name)
    assert (counters.matmul_params(c), counters._attn_per_key(c),
            counters.decode_flops(c, [100, 300]),
            counters.prefill_flops(c, [180, 512])) == want


def tiny_of(name: str, heads: int, kv: int) -> dict:
    """A dense file cut to a size the CPU holds (its keys and dtype)."""
    c = dense_file(name)
    c.update(hidden_size=64, intermediate_size=160, num_hidden_layers=4,
             vocab_size=521, num_attention_heads=heads,
             num_key_value_heads=kv)
    c["engram"] = dict(c["engram"], emb_dim=32, n_heads=4, table_vocab=2048,
                       layers=[1, 2])
    return c


@pytest.mark.parametrize("name,heads,kv,crc", [
    ("deepseek-7b-1chip", 4, 4, 0xfe74bb01),
    ("deepseek-coder-33b-1chip", 8, 2, 0xdd3b09be)])
def test_dense_weights_unchanged(name, heads, kv, crc):
    """Every value of the program's tree, Engram tables included, as the
    weights were made before expert leaves had a fan-in of their own."""
    cfg = run.model_config(tiny_of(name, heads, kv))
    params = W.program_params(cfg, SEED, jax.devices()[0])
    got = 0
    for leaf in jax.tree.leaves(params):
        got = zlib.crc32(np.asarray(leaf.astype(np.float32)).tobytes(), got)
    assert got == crc


def test_expert_leaves_take_one_experts_fan_in():
    cfg = run.model_config(MLA_MOE)
    params = W.program_params(cfg, SEED, jax.devices()[0])
    stack = params["segments"][1]["stack"][0]["ffn"]       # layers 1, 2
    for leaf, fan_in in (("w_gu", 64), ("w_down", 32)):
        std = float(np.asarray(stack[leaf], np.float64).std())
        assert std == pytest.approx(1 / math.sqrt(fan_in), rel=0.02), leaf
    assert W.std_for("layer3.ffn.w_gu", (8, 64, 64)) == 1 / 8
    assert W.std_for("layer3.ffn.router", (64, 8)) == 1 / 8
    assert W.std_for("engram0.tables", (16, 2048, 8)) == 1 / 4


def test_v3_sizing_file_is_modelled_at_the_cut_s_sizes():
    """The DeepSeek-V3 cut whose reference is timed on the chip: every
    key modelled, and its weights as counted by hand from the
    published widths (bfloat16)."""
    c = loader.read("tests/chipbench/data/deepseek-v3-sizing.json", ROOT)
    assert loader.unmodelled(c) == []
    assert counters.expert_share(c) == (8, 256)
    sh = loader.expected_shapes(c)

    def params(prefix):
        return sum(math.prod(s) for n, s in sh.items()
                   if n.startswith(prefix))
    assert params("layer0.mixer.") == 187105280
    assert params("layer0.ffn.") == 396361728               # dense
    assert params("layer1.ffn.") == 398196992               # 8 of 256
    assert sh["layer1.ffn.router_bias"] == (256,)
    assert sum(params(f"layer{i}.") for i in range(5)) * 2 == 5849352192
    assert (params("embed.") + params("head.")) * 2 == 3706716160


def test_configuration_files_name_only_modelled_keys():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert loader.unmodelled(loader.read(c["file"], ROOT)) == []
