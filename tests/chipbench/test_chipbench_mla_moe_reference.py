"""The plain float32 reference of latent attention and expert layers
(``chipbench/references/mla_moe_engram.py``) on the CPU: against the
program's full forward pass on ``MLA_MOE``, its expert shares against the
uncut layer, DeepSeek-V3's ``noaux_tc`` routing and YaRN by hand, and a
V3-keyed file through the reference and its timing."""
import chipbench_testpaths  # noqa: F401  (sys.path for chipbench)
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, run
from chipbench import weights as W
from chipbench.references import mla_moe_engram as R
from test_chipbench_loader import MLA_MOE, V3_KEYED, V3_ROPE, with_share

SEED = 2 ** 33 + 17
# float32 on both sides: the reference and the program agree to ~2e-6 on
# logits of magnitude ~4 (summation order); the bfloat16 control departs
# by 0.02-0.14 over three seeds, so 1e-4 holds the one and fails the other
TOL = 1e-4
PROMPTS = (13, 29)


def _swiglu_rows(params, rows, group_sizes, act):
    """The program's grouped expert MLP with the up projection applied:
    ``silu(x W_g) * (x W_u)``, then ``W_down``, as the program's dense
    ``mlp`` computes a SwiGLU. The program's ``_expert_mlp_rows`` drops
    ``x W_u`` (PERF.md, Open questions), so the comparison stands this
    one line in for it."""
    f = params["w_down"].shape[-2]
    h = jax.lax.ragged_dot(rows, params["w_gu"], group_sizes)
    a = jax.nn.silu(h[..., :f]) * h[..., f:]
    return jax.lax.ragged_dot(a, params["w_down"], group_sizes)


@pytest.fixture(scope="module")
def program_logits():
    """The program's full-forward logits for two prompts of different
    lengths, on ``W.program_params`` weights, float32: one batch padded at
    the end, which causal attention, the n-gram hash and dropless expert
    routing keep out of the real positions."""
    import repro.models.moe as moe
    from repro.models.model import forward
    from repro.models.transformer import RunFlags
    cfg = run.model_config(MLA_MOE)
    params = W.program_params(cfg, SEED, jax.devices()[0])
    rng = np.random.RandomState(5)
    seqs = [list(rng.randint(1, MLA_MOE["vocab_size"], n)) for n in PROMPTS]
    tokens = np.zeros((len(seqs), 32), np.int32)
    for b, s in enumerate(seqs):
        tokens[b, :len(s)] = s
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "_expert_mlp_rows", _swiglu_rows)
        h, _, _ = forward(cfg, RunFlags(), params,
                          {"tokens": jnp.asarray(tokens)}, "train")
    logits = np.asarray(h @ params["head"]["w"])
    return seqs, [logits[b, :len(s)] for b, s in enumerate(seqs)]


def _worst_gap(precision, seqs, logits):
    """Widest gap between the reference's (best, target) logits and the
    program's, over every position of every prompt."""
    ref = check.reference(MLA_MOE, SEED, precision)
    gaps = []
    for h, lg, s in zip(ref.final_hidden(seqs, 32), logits, seqs):
        targets = np.arange(len(s)) * 7 % MLA_MOE["vocab_size"]
        best, at, top = ref.head(h, targets, 32)
        gaps += [np.abs(best - lg.max(-1)).max(),
                 np.abs(at - lg[np.arange(len(s)), targets]).max()]
        if precision == "float32":
            np.testing.assert_array_equal(top, lg.argmax(-1))
    return float(max(gaps))


def test_reference_matches_program_logits(program_logits):
    assert _worst_gap("float32", *program_logits) < TOL


def test_bfloat16_control_falls_outside_the_tolerance(program_logits):
    assert _worst_gap("bfloat16", *program_logits) > TOL


def test_expert_layer_matches_program_route_and_mlp_per_expert():
    """A second witness of the expert layer: the program's own router
    (``_route``) and its dense SwiGLU ``mlp`` on each chosen expert's
    slice of ``w_gu`` / ``w_down``, summed with the router's weights."""
    from repro.models.layers import mlp
    from repro.models.moe import _route
    cfg = run.model_config(MLA_MOE)
    ref = check.reference(MLA_MOE, SEED)
    ws = ref.layer_weights(1)
    x = jax.random.normal(jax.random.PRNGKey(1), (11, 64))
    F = MLA_MOE["moe_intermediate_size"]
    eids, w, _ = _route(cfg.moe, {"router": ws["router"]}, x)
    want = mlp({"gate": ws["shared.gate"], "up": ws["shared.up"],
                "down": ws["shared.down"]}, x)
    for t in range(x.shape[0]):
        for j in range(cfg.moe.top_k):
            e = int(eids[t, j])
            want = want.at[t].add(w[t, j] * mlp(
                {"gate": ws["w_gu"][e, :, :F], "up": ws["w_gu"][e, :, F:],
                 "down": ws["w_down"][e]}, x[t]))
    got = R.expert_layer(x, ws, ref.routing, ref.top_k)
    np.testing.assert_allclose(got, want, atol=1e-5)


# --------------------------------------------------------------- shares

def _layer(c):
    ref = check.reference(c, SEED)
    return ref, ref.layer_weights(1)


@pytest.mark.parametrize("routing", ["softmax_greedy", "sigmoid_noaux_tc"])
@pytest.mark.parametrize("expert_parallel", [2, 4])
def test_expert_shares_add_up_to_the_uncut_layer(expert_parallel, routing):
    """Each chip of the group computes its experts' part for the same
    routing; with the shared expert counted once the parts add up to the
    layer that holds all eight."""
    c = MLA_MOE if routing == "softmax_greedy" else dict(
        MLA_MOE, scoring_func="sigmoid", topk_method="noaux_tc", n_group=4,
        topk_group=2, routed_scaling_factor=2.5)
    ref, ws = _layer(c)
    x = jax.random.normal(jax.random.PRNGKey(2), (23, 64))
    whole = R.expert_layer(x, ws, ref.routing, ref.top_k)
    n = 8 // expert_parallel
    total = 0.0
    for k in range(expert_parallel):
        share = {key: v for key, v in ws.items()
                 if k == 0 or not key.startswith("shared.")}
        share["w_gu"] = ws["w_gu"][k * n:(k + 1) * n]
        share["w_down"] = ws["w_down"][k * n:(k + 1) * n]
        part = R.expert_layer(x, share, ref.routing, ref.top_k, first=k * n)
        assert float(jnp.abs(part).max()) > 0
        total = total + part
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_held_experts_do_not_depend_on_the_share():
    """Chip 0 of a group of two holds logical experts 0..7 of the stack,
    the values the uncut layer holds there; only the router widens."""
    _, whole = _layer(MLA_MOE)
    ref, part = _layer(with_share(MLA_MOE, 2))
    assert (ref.held, ref.routed) == (8, 16)
    for k in ("w_gu", "w_down", "shared.gate"):
        np.testing.assert_array_equal(part[k], whole[k])
    assert part["router"].shape == (64, 16)


# ------------------------------------------------------------- noaux_tc

NOAUX = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 4,
         "topk_group": 2, "norm_topk_prob": True,
         "routed_scaling_factor": 2.5}


def _route(scores, bias, r, top_k):
    """``R.route`` on sigmoid scores given directly (one token)."""
    s = np.asarray(scores, np.float64)
    ids, w = R.route(jnp.asarray([np.log(s / (1 - s))], jnp.float32),
                     jnp.asarray(bias, jnp.float32), r, top_k)
    return [int(i) for i in ids[0]], np.asarray(w[0], np.float64)


def test_group_limit_changes_the_chosen_set():
    # groups (0,1) (2,3) (4,5) (6,7) sum their two best to 1.0, 1.22, 1.14
    # and 0.4: groups 1 and 2 are kept, so expert 0 (0.9) cannot be chosen
    s = [0.9, 0.1, 0.62, 0.6, 0.58, 0.56, 0.2, 0.2]
    ids, w = _route(s, np.zeros(8), NOAUX, 2)
    assert sorted(ids) == [2, 3]
    np.testing.assert_allclose(sorted(w), sorted(
        [2.5 * 0.62 / 1.22, 2.5 * 0.6 / 1.22]), rtol=1e-5)
    plain, _ = _route(s, np.zeros(8), dict(NOAUX, topk_method="greedy"), 2)
    assert sorted(plain) == [0, 2]


def test_bias_changes_the_choice_but_not_the_weights():
    s = [0.7, 0.6, 0.5, 0.4, 0.3, 0.2, 0.1, 0.05]
    r = dict(NOAUX, topk_group=4)                     # no group limit
    ids, _ = _route(s, np.zeros(8), r, 2)
    assert sorted(ids) == [0, 1]
    bias = np.zeros(8)
    bias[5] = 0.6                                     # chosen by 0.8
    ids, w = _route(s, bias, r, 2)
    assert sorted(ids) == [0, 5]
    got = dict(zip(ids, w))
    np.testing.assert_allclose([got[0], got[5]],
                               [2.5 * 0.7 / 0.9, 2.5 * 0.2 / 0.9],
                               rtol=1e-5)


@pytest.mark.parametrize("norm", [True, False])
def test_normalisation_and_scaling(norm):
    s = [0.7, 0.6, 0.5, 0.4, 0.3, 0.2, 0.1, 0.05]
    r = dict(NOAUX, topk_group=4, norm_topk_prob=norm)
    ids, w = _route(s, np.zeros(8), r, 3)
    assert sorted(ids) == [0, 1, 2]
    want = np.array([0.7, 0.6, 0.5]) / (1.8 if norm else 1.0) * 2.5
    np.testing.assert_allclose(sorted(w, reverse=True), want, rtol=1e-5)


# ------------------------------------------------------------------ YaRN

def test_yarn_at_v3_settings():
    c = {"rope_theta": 10000, "rope_scaling": V3_ROPE}
    inv, cos_mult, soft = R.rope_table(c, 64)
    base, one, unit = R.rope_table({"rope_theta": 10000}, 64)
    assert (one, unit) == (1.0, 1.0)
    assert R.mscale(40, 1) == pytest.approx(1.3689, abs=1e-4)
    assert soft == pytest.approx(R.mscale(40, 1) ** 2) == \
        pytest.approx(1.8739, abs=1e-4)
    assert cos_mult == 1.0                  # mscale == mscale_all_dim
    assert inv[0] == base[0]                # highest frequency kept
    assert inv[-1] == pytest.approx(base[-1] / 40, rel=1e-6)
    # DeepSeek's correction range at these settings: dims [10, 23)
    np.testing.assert_array_equal(inv[:11], base[:11])
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    assert np.all((inv[11:23] < base[11:23]) & (inv[11:23] > base[11:23] / 40))
    listed = dict(c, reduced={"rope_scaling": [None, V3_ROPE, "dropped"]})
    np.testing.assert_array_equal(R.rope_table(listed, 64)[0], base)


def test_softmax_scale_takes_yarn_s_mscale():
    ref = check.reference(dict(MLA_MOE, rope_scaling=V3_ROPE), SEED)
    assert ref.scale == pytest.approx(R.mscale(40, 1) ** 2 / math.sqrt(24))
    assert check.reference(MLA_MOE, SEED).scale == \
        pytest.approx(1 / math.sqrt(24))


# ------------------------------------------------------ a V3-keyed file

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_v3_keyed_file_runs_through_the_reference(dtype):
    """Routing, YaRN and a share of 8 of 16 experts, through
    ``final_hidden`` and ``head`` as a run's check calls them, in float32
    and in the control below the stated dtype."""
    from chipbench.reference_time import time_reference
    c = dict(V3_KEYED, torch_dtype=dtype,
             deployment=dict(V3_KEYED["deployment"], max_len=32))
    out = time_reference(c, SEED, requests=2, prompt=12, output=8)
    assert out["tokens_compared"] == 16
    for prec in ("float32", check.CONTROL[dtype]):
        assert len(out[f"reference_s.{prec}"]) == 2
