"""Operations and bytes the model's work needs, from shapes alone.

``c`` is a configuration file's dict (``chipbench/configs/*.json``). Counts
are of the work the algorithm needs for the tokens served: matmul FLOPs
(2 per multiply-add) of every projection, the output head and the Engram
fusion, and attention over the real context of each token (QK and PV),
never the padding or the dead slots a program also computes.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Weights one token multiplies through."""
    d, F, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    n = c["num_hidden_layers"] * per_layer + d * V
    e = c.get("engram")
    if e:
        layers = [l for l in e["layers"] if 0 < l < c["num_hidden_layers"]]
        n += len(layers) * (len(e["orders"]) * e["emb_dim"] * d + d * d)
    return n


def _attn_per_key(c: dict) -> int:
    """FLOPs per (query, key) pair over all layers: QK and PV."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    return 4 * c["num_hidden_layers"] * c["num_attention_heads"] * hd


def decode_flops(c: dict, contexts) -> float:
    """One decode token per live slot; ``contexts``: keys each attends."""
    p = 2 * matmul_params(c)
    a = _attn_per_key(c)
    return float(sum(p + a * n for n in contexts))


def prefill_flops(c: dict, lengths) -> float:
    """Causal prefill of prompts of ``lengths`` (useful tokens only)."""
    p = 2 * matmul_params(c)
    a = _attn_per_key(c)
    return float(sum(n * p + a * n * (n + 1) // 2 for n in lengths))


def gather_bytes(c: dict, rows: int) -> float:
    """HBM bytes a row gather needs: each row's valid lanes read and
    written once (bfloat16), plus its int32 row id."""
    e = c["engram"]
    width = e["emb_dim"] // e["n_heads"]
    return float(rows * (2 * width * 2 + 4))
