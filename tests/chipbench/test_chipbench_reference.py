"""The plain float32 reference against the engine's prefill and cached
decode logits (``deepseek-7b-reduced``, float32, on the CPU): several
slots live in one wave, and a multi-prompt admission group."""
import chipbench_testpaths  # noqa: F401  (sys.path for chipbench)
import dataclasses

import jax
import numpy as np
import pytest

from chipbench import check, run
from chipbench import weights as W

SEED = 2 ** 31 + 41


def reduced_config() -> dict:
    """deepseek-7b-reduced in the benchmark's configuration format."""
    return {
        "name": "deepseek-7b-reduced", "reference": "dense_engram",
        "hidden_size": 64, "intermediate_size": 160,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "num_hidden_layers": 4, "vocab_size": 521, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-06, "torch_dtype": "float32",
        "engram": {"orders": [2, 3], "n_heads": 4, "emb_dim": 32,
                   "table_vocab": 2048, "layers": [1, 2],
                   "strategy": "local", "seed": 0x5EED, "pad_token": 0},
        "deployment": {"pool": "CXL", "cache_rows": 0, "max_batch": 4,
                       "max_len": 64, "prompt_bucket": 16},
    }


def test_config_format_builds_the_preset():
    from repro.configs.deepseek_7b import reduced
    assert run.model_config(reduced_config()) == reduced()


@pytest.fixture(scope="module")
def served():
    """Logits the engine computed for each served token, by (rid, index)."""
    from repro.configs.deepseek_7b import reduced
    from repro.serving import EngramRuntime
    cfg = reduced()
    params = W.program_params(cfg, SEED, jax.devices()[0])
    rt = EngramRuntime(cfg, params=params, pool="CXL", max_batch=4,
                       max_len=64, prompt_bucket=16)
    eng = rt.engine
    seen = {}
    group = []
    prefill = eng._prefill_fn

    def prefill_spy(p, batch):
        logits, state = prefill(p, batch)
        jax.debug.callback(lambda l: group.append(np.asarray(l)), logits)
        return logits, state

    eng._prefill_fn = prefill_spy
    decode = eng._decode_ext

    def decode_spy(*args):
        live = [(i, r.rid, len(r.out)) for i, r in enumerate(eng.slots)
                if r is not None]
        logits, state = decode(*args)
        arr = np.asarray(logits)
        for i, rid, k in live:
            seen[(rid, k)] = arr[i]
        return logits, state

    eng._decode_ext = decode_spy
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 521, n)) for n in (9, 13, 15, 4)]
    handles = [rt.submit(p, max_new=6) for p in prompts[:3]]
    rt.step()                         # one admission group of three
    handles.append(rt.submit(prompts[3], max_new=5))
    rt.drain()
    assert len(group) == 2            # the group of three, then one
    for r, h in enumerate(handles[:3]):
        seen[(h.rid, 0)] = group[0][r]
    seen[(handles[3].rid, 0)] = group[1][0]
    return [(p, h.tokens, [seen[(h.rid, k)] for k in range(len(h.tokens))])
            for p, h in zip(prompts, handles)]


def test_reference_matches_engine_logits(served):
    ref = check.reference(reduced_config(), SEED)
    picked = [(p, o) for p, o, _ in served]
    h, tokens = check._served_hidden(ref, picked, 64)
    best, at, top = ref.head(h, tokens, 64)
    got = np.concatenate([np.stack(l) for _, _, l in served])
    np.testing.assert_allclose(best, got.max(-1), atol=1e-4)
    np.testing.assert_allclose(at, got[np.arange(len(tokens)), tokens],
                               atol=1e-4)
    np.testing.assert_array_equal(top, tokens)     # greedy, float32
    assert check.widest_gap(reduced_config(), SEED, picked, 64) < 1e-4


def test_reference_sees_a_wrong_token(served):
    p, o, _ = served[0]
    bad = list(o)
    bad[2] = (bad[2] + 1) % 521
    assert check.widest_gap(reduced_config(), SEED, [(p, bad)], 64) > 1e-2
