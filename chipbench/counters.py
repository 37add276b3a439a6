"""Operations and bytes the model's work needs, from shapes alone.

``c`` is a configuration file's dict (``chipbench/configs/*.json``). Counts
are of the work the published model needs for the tokens served, whatever
implements it: matmul FLOPs (2 per multiply-add) of every projection, the
output head and the Engram fusion, and attention over the real context of
each token (QK and PV), never the padding or the dead slots a program also
computes.

Attention is grouped-query (``num_key_value_heads``) or latent (MLA, when
the file has ``kv_lora_rank``); a layer is an expert layer when the file
has ``n_routed_experts``, ``i >= first_k_dense_replace`` and
``i % moe_layer_freq == 0``. ``n_routed_experts`` counts the experts held
on this chip; ``deployment.expert_parallel`` chips share each expert layer,
so the router is ``held * expert_parallel`` wide.
"""
from __future__ import annotations


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def is_mla(c: dict) -> bool:
    return "kv_lora_rank" in c


def expert_layers(c: dict) -> list:
    """Indices of the expert layers."""
    if not c.get("n_routed_experts"):
        return []
    k, f = c.get("first_k_dense_replace", 0), c.get("moe_layer_freq", 1)
    return [i for i in range(c["num_hidden_layers"])
            if i >= k and i % f == 0]


def expert_share(c: dict) -> tuple:
    """(experts held on this chip, experts the router routes over)."""
    held = c.get("n_routed_experts") or 0
    return held, held * c.get("deployment", {}).get("expert_parallel", 1)


def engram_layers(c: dict) -> list:
    e = c.get("engram")
    if not e:
        return []
    return sorted(l for l in e["layers"] if 0 < l < c["num_hidden_layers"])


def attn_params(c: dict) -> int:
    """Projection weights of one attention layer."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    if is_mla(c):
        ql, kv = c.get("q_lora_rank"), c["kv_lora_rank"]
        dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
        q = d * ql + ql * H * (dn + dr) if ql else d * H * (dn + dr)
        return q + d * (kv + dr) + kv * H * (dn + dv) + H * dv * d
    hd, KV = head_dim(c), c["num_key_value_heads"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d


def expert_ffn_params(c: dict):
    """Weights one token multiplies through in an expert layer: the
    router, the shared experts, and its ``num_experts_per_tok`` routed
    experts times the share of them held here (the expected share under
    uniform routing)."""
    d, Fe = c["hidden_size"], c["moe_intermediate_size"]
    held, routed = expert_share(c)
    n = d * routed + (c.get("n_shared_experts") or 0) * 3 * d * Fe
    routed_work = c["num_experts_per_tok"] * 3 * d * Fe
    if held == routed:
        return n + routed_work
    return n + routed_work * held / routed


def matmul_params(c: dict):
    """Weights one token multiplies through."""
    d, F, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    L = c["num_hidden_layers"]
    n_exp = len(expert_layers(c))
    n = L * attn_params(c) + (L - n_exp) * 3 * d * F + d * V
    if n_exp:
        n += n_exp * expert_ffn_params(c)
    e = c.get("engram")
    if e:
        n += len(engram_layers(c)) * (len(e["orders"]) * e["emb_dim"] * d
                                      + d * d)
    return n


def _attn_per_key(c: dict) -> int:
    """FLOPs per (query, key) pair over all layers: QK and PV. Latent
    attention counts its per-head dims, however the program computes it
    (an absorbed decode is counted the same)."""
    H, L = c["num_attention_heads"], c["num_hidden_layers"]
    if is_mla(c):
        dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
        return L * (2 * H * (dn + dr) + 2 * H * dv)
    return 4 * L * H * head_dim(c)


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
