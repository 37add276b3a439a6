"""A configuration file (``chipbench/configs/*.json``) as the program's
``ModelConfig``, and the check that the program builds the model the file
states.

The file holds the model's published keys (as in its ``config.json``),
``engram``, ``deployment``, ``reduced`` and ``assumed``; a file may also
name a model file (``"model": "chipbench/configs/<name>.json"``) and
state only what it changes, such as its deployment.

``from_file`` maps the keys the program models: dense and GQA attention,
latent attention (MLA), expert layers and Engram. A key that changes the
served model and is not modelled here is an error that names it, unless
the file lists it under ``reduced`` or ``assumed`` as a departure. The
harness uses the program's own loader, ``repro.configs.from_file``, where
the program has one, and this mapping otherwise.

``check`` holds the program's config to the file on every size that
``chipbench/counters.py`` reads, from the shapes of
``abstract_params(cfg)``: nothing is made, so it runs before any weight.

Expert routing and YaRN (DeepSeek-V3) are mapped here and modelled by the
reference (``references/mla_moe_engram.py``); ``from_file`` refuses them
by name, since the program it builds routes by softmax top-k and rotates
with one theta. A program's own loader that models them fills this
contract, which ``check`` reads:

- ``cfg.moe.scoring_func`` (``"softmax"`` | ``"sigmoid"``),
  ``cfg.moe.topk_method`` (``"greedy"`` | ``"noaux_tc"``),
  ``cfg.moe.n_group``, ``cfg.moe.topk_group``, ``cfg.moe.norm_topk_prob``
  and ``cfg.moe.router_scale`` (``routed_scaling_factor``), each read as
  today's behaviour (``ROUTING``) where the attribute is missing;
- ``cfg.rope_scaling``: None, or the file's ``rope_scaling`` as a mapping
  or as (key, value) pairs;
- under ``noaux_tc`` each expert layer holds the selection bias
  (``e_score_correction_bias``) as the leaf ``router_bias`` beside
  ``router``, shape (routed,); both leaves are float32;
- a chip of ``deployment.expert_parallel`` holds the logical experts
  ``[0, n_routed_experts)`` of the ``w_gu`` / ``w_down`` stacks and routes
  over all ``n_routed_experts * expert_parallel``.

Routing facts are compared for files with expert layers, ``rope_scaling``
for every file, each unless the file lists the key as a departure.
"""
from __future__ import annotations

import json
from pathlib import Path

from chipbench import counters

# keys that state no shape and no arithmetic of the served forward pass
NOT_SERVED = {
    "name", "source", "paper", "architectures", "model_type", "model",
    "max_position_embeddings", "bos_token_id", "eos_token_id",
    "pad_token_id", "initializer_range", "use_cache", "transformers_version",
    "attention_dropout", "aux_loss_alpha", "seq_aux", "pretraining_tp",
    "reduced", "assumed", "deployment", "reference",
}
# keys mapped onto ModelConfig below
MAPPED = {
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "num_hidden_layers", "vocab_size", "head_dim",
    "rope_theta", "rms_norm_eps", "torch_dtype", "tie_word_embeddings",
    "engram", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
    "num_experts_per_tok", "n_shared_experts", "moe_intermediate_size",
    "first_k_dense_replace", "moe_layer_freq", "routed_scaling_factor",
    "scoring_func", "topk_method", "n_group", "topk_group",
    "norm_topk_prob", "rope_scaling",
}
# the values of mapped keys that are modelled; any other is unmodelled
MAPPED_VALUES = {"scoring_func": ("softmax", "sigmoid"),
                 "topk_method": ("greedy", "noaux_tc")}
# keys modelled at one value only: SiLU gates, no projection biases
ONLY = {
    "hidden_act": "silu", "attention_bias": False, "mlp_bias": False,
    "ep_size": 1,
}
# the routing of a file that states none, and of a program config that
# has no attribute for it: softmax top-k with the top-k weights
# renormalized, in one group
ROUTING = {"scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
           "topk_group": 1, "norm_topk_prob": True,
           "routed_scaling_factor": 1.0}


def read(path, root: Path) -> dict:
    """A configuration file, merged over the model file it names: a key
    the file states replaces the model file's, one level deep in a dict
    (``deployment``, ``assumed``)."""
    c = json.loads(Path(root / path).read_text())
    if "model" not in c:
        return c
    base = read(c["model"], root)
    out = dict(base)
    for k, v in c.items():
        out[k] = {**base[k], **v} if isinstance(v, dict) and \
            isinstance(base.get(k), dict) else v
    return out


def departures(c: dict) -> set:
    return set(c.get("reduced", {})) | set(c.get("assumed", {}))


def is_yarn(rope_scaling) -> bool:
    return isinstance(rope_scaling, dict) and \
        rope_scaling.get("type") == "yarn"


def _modelled(k, v) -> bool:
    if k == "rope_scaling":
        return v is None or is_yarn(v)
    return v in MAPPED_VALUES.get(k, (v,))


def unmodelled(c: dict) -> list:
    """Keys of ``c`` that change the served model, that the mapping below
    does not model, and that the file does not list as departures."""
    listed = departures(c)
    bad = []
    for k, v in c.items():
        if k in NOT_SERVED or k in listed:
            continue
        if k in MAPPED and _modelled(k, v):
            continue
        if k in ONLY and v == ONLY[k]:
            continue
        bad.append(k)
    return bad


def routing(c: dict) -> dict:
    """The file's routing: each key of ``ROUTING`` as the file states it,
    or its default where the file states none (or null) or lists the key
    as a departure."""
    listed = departures(c)
    r = {k: v if c.get(k) is None or k in listed else c[k]
         for k, v in ROUTING.items()}
    r["routed_scaling_factor"] = float(r["routed_scaling_factor"])
    return r


def rope_scaling(c: dict):
    """The file's ``rope_scaling``, None where it states none or lists it
    as a departure."""
    return None if "rope_scaling" in departures(c) \
        else c.get("rope_scaling") or None


def _not_held(c: dict) -> list:
    """What the file states that ``from_file``'s program cannot hold: an
    expert share, routing other than ``ROUTING``, RoPE scaling."""
    bad = [f"{k}={c[k]!r}" for k, v in routing(c).items()
           if k != "routed_scaling_factor" and v != ROUTING[k]]
    if rope_scaling(c) is not None:
        bad.append(f"rope_scaling={c['rope_scaling']!r}")
    held, routed = counters.expert_share(c)
    if held != routed:
        bad.append(f"deployment.expert_parallel="
                   f"{c['deployment']['expert_parallel']} ({held} experts "
                   f"held of {routed} routed)")
    return bad


def from_file(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.configs.base import (EngramConfig, MLAConfig, ModelConfig,
                                    MoEConfig, StoreConfig)
    bad = unmodelled(c)
    if bad:
        raise ValueError(
            f"{c.get('name')}: keys not modelled: "
            + ", ".join(f"{k}={c[k]!r}" for k in bad)
            + "; list each under reduced or assumed as a departure")
    bad = _not_held(c)
    if bad:
        raise ValueError(
            f"{c.get('name')}: the program's config cannot hold "
            + ", ".join(bad) + ": its expert layer holds every expert it "
            "routes over by softmax top-k, and its RoPE takes one theta")
    held, _ = counters.expert_share(c)
    e, dep = c["engram"], c["deployment"]
    eng = EngramConfig(orders=tuple(e["orders"]), n_heads=e["n_heads"],
                       emb_dim=e["emb_dim"], table_vocab=e["table_vocab"],
                       layers=tuple(e["layers"]), strategy=e["strategy"],
                       seed=e["seed"], pad_token=e["pad_token"],
                       store=StoreConfig(cache_rows=dep["cache_rows"]))
    d, H = c["hidden_size"], c["num_attention_heads"]
    kw = {}
    if counters.is_mla(c):
        if c.get("q_lora_rank") is None:
            raise ValueError(f"{c.get('name')}: MLA without q_lora_rank is "
                             f"not modelled")
        kw.update(attn_impl="mla", mla=MLAConfig(
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"]))
    experts = set(counters.expert_layers(c))
    if experts:
        kw.update(moe=MoEConfig(
            n_experts=held, top_k=c["num_experts_per_tok"],
            n_shared=c.get("n_shared_experts") or 0,
            d_ff_expert=c["moe_intermediate_size"],
            router_scale=float(c.get("routed_scaling_factor", 1.0))),
            ffn_types=tuple("moe" if i in experts else "dense"
                            for i in range(c["num_hidden_layers"])))
    if c.get("tie_word_embeddings"):
        kw["tie_embeddings"] = True
    return ModelConfig(
        name=c["name"], family="moe" if experts else "dense",
        n_layers=c["num_hidden_layers"], d_model=d,
        vocab_size=c["vocab_size"], n_heads=H,
        n_kv_heads=c["num_key_value_heads"], head_dim=counters.head_dim(c),
        d_ff=c["intermediate_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"],
        engram=eng, **kw)


# ------------------------------------------------------------------ check

def expected_shapes(c: dict) -> dict:
    """Logical weight name -> shape, for every matrix the file states
    (norm scales left out; Engram tables as (tables, rows, width))."""
    d, V, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    H = c["num_attention_heads"]
    out = {"embed.w": (V, d)}
    if not c.get("tie_word_embeddings"):
        out["head.w"] = (d, V)
    experts = set(counters.expert_layers(c))
    held, routed = counters.expert_share(c)
    for i in range(L):
        m, f = f"layer{i}.mixer.", f"layer{i}.ffn."
        if counters.is_mla(c):
            ql, kv = c["q_lora_rank"], c["kv_lora_rank"]
            dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
            dv = c["v_head_dim"]
            if ql:
                out[m + "wdq"] = (d, ql)
                out[m + "wuq"] = (ql, H * (dn + dr))
            else:
                out[m + "wq"] = (d, H * (dn + dr))
            out.update({m + "wdkv": (d, kv + dr), m + "wuk": (kv, H * dn),
                        m + "wuv": (kv, H * dv), m + "wo": (H * dv, d)})
        else:
            hd, KV = counters.head_dim(c), c["num_key_value_heads"]
            out.update({m + "wq": (d, H * hd), m + "wk": (d, KV * hd),
                        m + "wv": (d, KV * hd), m + "wo": (H * hd, d)})
        if i in experts:
            Fe = c["moe_intermediate_size"]
            out.update({f + "router": (d, routed),
                        f + "w_gu": (held, d, 2 * Fe),
                        f + "w_down": (held, Fe, d)})
            if routing(c)["topk_method"] == "noaux_tc":
                out[f + "router_bias"] = (routed,)
            ns = c.get("n_shared_experts") or 0
            if ns:
                out.update({f + "shared.gate": (d, ns * Fe),
                            f + "shared.up": (d, ns * Fe),
                            f + "shared.down": (ns * Fe, d)})
        else:
            F = c["intermediate_size"]
            out.update({f + "gate": (d, F), f + "up": (d, F),
                        f + "down": (F, d)})
    e = c["engram"]
    for j, _ in enumerate(counters.engram_layers(c)):
        width = e["emb_dim"] // e["n_heads"]
        out.update({
            f"engram{j}.tables": (len(e["orders"]) * e["n_heads"],
                                  e["table_vocab"], width),
            f"engram{j}.proj": (len(e["orders"]) * e["emb_dim"], d),
            f"engram{j}.gate": (d, d)})
    return out


def program_leaves(cfg) -> dict:
    """Logical weight name -> (shape, dtype name) in the program's
    parameter tree."""
    import jax
    from chipbench.weights import _layer_names, _path_parts
    from repro.models.model import abstract_params
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            abstract_params(cfg)):
        names, stacked = _layer_names(cfg, _path_parts(path))
        shape = tuple(int(s) for s in (leaf.shape[1:] if stacked
                                       else leaf.shape))
        for n in names:
            if not n.endswith(".scale"):
                out[n] = shape, str(leaf.dtype)
    return out


def _pairs(x):
    """A ``rope_scaling`` (mapping or (key, value) pairs) as sorted pairs,
    numbers as floats; None for none."""
    if not x:
        return None
    items = x.items() if hasattr(x, "items") else x
    return tuple(sorted(
        (k, float(v) if isinstance(v, (int, float))
         and not isinstance(v, bool) else v) for k, v in items))


def program_routing(cfg) -> dict | None:
    """The program config's routing by ``ROUTING``'s keys (None without
    expert layers), today's behaviour where an attribute is missing."""
    mo = cfg.moe
    if mo is None:
        return None
    r = {k: getattr(mo, k, v) for k, v in ROUTING.items()
         if k != "routed_scaling_factor"}
    r["routed_scaling_factor"] = float(mo.router_scale)
    return r


def mismatches(c: dict, cfg) -> list:
    """Where the program's config ``cfg`` departs from file ``c``: one
    line per weight whose shape differs, is missing or is extra, per
    router leaf not in float32, and per size or fact that the shapes do
    not show apart (heads and head dims, latent dims, experts per token,
    shared experts, routing, RoPE scaling, Engram layers)."""
    leaves = program_leaves(cfg)
    want = expected_shapes(c)
    got = {n: sh for n, (sh, _) in leaves.items()}
    bad = [f"{n}: file float32, program {dt}" for n, (_, dt) in
           sorted(leaves.items())
           if n.endswith((".ffn.router", ".ffn.router_bias"))
           and dt != "float32"]
    for n in sorted(set(want) | set(got)):
        w, g = want.get(n), got.get(n)
        if n.endswith(".tables") and w and g:
            # lane- and row-padded in the program
            ok = g[0] == w[0] and g[1] >= w[1] and g[2] >= w[2]
        else:
            ok = w == g
        if not ok:
            bad.append(f"{n}: file {w}, program {g}")
    facts = [("num_hidden_layers", cfg.n_layers, c["num_hidden_layers"]),
             ("num_attention_heads", cfg.n_heads,
              c["num_attention_heads"]),
             ("rope_theta", cfg.rope_theta, float(c["rope_theta"])),
             ("rms_norm_eps", cfg.norm_eps, float(c["rms_norm_eps"])),
             ("torch_dtype", cfg.dtype, c["torch_dtype"])]
    if counters.is_mla(c):
        m = cfg.mla
        got_mla = None if m is None or cfg.attn_impl != "mla" else (
            m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim)
        facts.append(("MLA dims", got_mla, (
            c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"])))
    else:
        facts += [("num_key_value_heads", cfg.n_kv_heads,
                   c["num_key_value_heads"]),
                  ("head_dim", cfg.head_dim, counters.head_dim(c)),
                  ("attention", cfg.attn_impl, "gqa")]
    experts = counters.expert_layers(c)
    facts.append(("expert layers", [i for i, t in enumerate(cfg.ffn_types)
                                    if t == "moe"], experts))
    listed = departures(c)
    if experts:
        mo = cfg.moe
        facts += [("num_experts_per_tok", mo and mo.top_k,
                   c["num_experts_per_tok"]),
                  ("n_shared_experts", mo and mo.n_shared,
                   c.get("n_shared_experts") or 0)]
        got_r, want_r = program_routing(cfg), routing(c)
        facts += [(k, got_r and got_r[k], want_r[k]) for k in ROUTING
                  if k not in listed]
    if "rope_scaling" not in listed:
        facts.append(("rope_scaling",
                      _pairs(getattr(cfg, "rope_scaling", None)),
                      _pairs(c.get("rope_scaling"))))
    e, fe = cfg.engram, c["engram"]
    facts += [("engram layers", cfg.engram_layers(),
               tuple(counters.engram_layers(c))),
              ("engram", e and (tuple(e.orders), e.n_heads, e.emb_dim,
                                e.table_vocab),
               (tuple(fe["orders"]), fe["n_heads"], fe["emb_dim"],
                fe["table_vocab"]))]
    bad += [f"{k}: file {w!r}, program {g!r}" for k, g, w in facts if g != w]
    return bad


def check(c: dict, cfg) -> None:
    """Raise when the program's config is not the model the file states."""
    bad = mismatches(c, cfg)
    if bad:
        raise ValueError(f"{c.get('name')}: the program's config departs "
                         f"from the file: " + "; ".join(bad))
