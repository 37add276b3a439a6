"""A configuration and a traffic mix small enough for the CPU, in the
benchmark's file formats. Served in float32 on the CPU the program agrees
with the float32 reference to rounding, so the limit is tight."""

CONFIG = {
    "name": "tiny-dense-engram",
    "reference": "dense_engram",
    "hidden_size": 64,
    "intermediate_size": 160,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "num_hidden_layers": 4,
    "vocab_size": 521,
    "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06,
    "torch_dtype": "float32",
    "engram": {"orders": [2, 3], "n_heads": 4, "emb_dim": 32,
               "table_vocab": 4096, "layers": [1, 2], "strategy": "local",
               "seed": 24301, "pad_token": 0},
    "deployment": {"pool": "CXL", "cache_rows": 512, "max_batch": 4,
                   "max_len": 64, "prompt_bucket": 8},
}

TRAFFIC = {
    "arrivals": {"process": "poisson", "rate_per_s": 40.0},
    "schedule_seed": 12,
    "lead_in_s": 0.2,
    "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4,
               "max": 24},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2,
               "max": 16},
    "tokens": {"dist": "zipf", "alpha": 1.0},
    "check": {"tokens": 48, "max_logit_gap": 1e-3},
}

# the same model served in bfloat16, for the control (float8 below it)
BF16 = dict(CONFIG, torch_dtype="bfloat16")
