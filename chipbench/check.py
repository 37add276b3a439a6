"""Whether what the timed path served is correct: served tokens against
the plain float32 reference.

A sample of the requests the run finished, drawn from the seed with the
longest among them and, where several replicas served, at least one of
each, is run through the reference once, each request as
its prompt followed by its served tokens. At every served position the
number read is the gap by which the served token's reference logit lies
below the reference's best logit there; the run compares the widest gap
with the cell's limit. A greedy server that computed what the
configuration states serves tokens whose gaps are rounding-sized; one that
computes something else serves tokens the reference ranks lower.
"""
from __future__ import annotations

import importlib

import numpy as np


def sample_at(finished: list, seed: int, tokens: int,
              replica: list = None) -> list:
    """Indices into the finished ``(prompt, out)`` pairs: the one with the
    most served tokens, then others in a seeded order until ``tokens`` are
    covered. ``replica``: the replica that served each pair; the first of
    each replica in that order then comes right after the longest, so that
    every replica's tokens are compared."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -len(finished[i][1]))
    first, rest = order[0], order[1:]
    rng = np.random.default_rng(seed)
    rest = [rest[i] for i in rng.permutation(len(rest))]
    lead = []
    if replica is not None:
        seen = {replica[first]}
        for i in rest:
            if replica[i] not in seen:
                seen.add(replica[i])
                lead.append(i)
        rest = lead + [i for i in rest if i not in set(lead)]
    out, n = [], 0
    for k, i in enumerate([first] + rest):
        if n >= tokens and k > len(lead):
            break
        out.append(i)
        n += len(finished[i][1])
    return out


def sample(finished: list, seed: int, tokens: int,
           replica: list = None) -> list:
    """The pairs that ``sample_at`` picks."""
    return [finished[i] for i in sample_at(finished, seed, tokens, replica)]


def reference(config: dict, seed: int, precision: str = "float32"):
    mod = importlib.import_module(f"chipbench.references.{config['reference']}")
    return mod.Model(config, seed, precision)


def _served_hidden(model, picked: list, length: int):
    """Final hidden states at the positions that produced each served
    token, and those tokens."""
    seqs = [list(p) + list(o[:-1]) for p, o in picked]
    hs = model.final_hidden(seqs, length)
    import jax.numpy as jnp
    h = jnp.concatenate([hh[len(p) - 1:len(p) - 1 + len(o)]
                         for hh, (p, o) in zip(hs, picked)])
    return h, np.concatenate([np.asarray(o, np.int64) for _, o in picked])


def widest_gap(config: dict, seed: int, picked: list, length: int) -> float:
    """Widest reference-logit gap of the served tokens of ``picked``."""
    ref = reference(config, seed)
    h, served = _served_hidden(ref, picked, length)
    best, at, _ = ref.head(h, served, length)
    return float(np.max(best - at))


# the precision a control computes in: the next below the configuration's
CONTROL = {"bfloat16": "fp8", "float32": "bfloat16"}


def control_gap(config: dict, seed: int, picked: list, length: int) -> float:
    """The same number for the control: at the same positions, the token
    that the reference computed one precision lower puts first."""
    ctrl = reference(config, seed, CONTROL[config["torch_dtype"]])
    hc, served = _served_hidden(ctrl, picked, length)
    _, _, tops = ctrl.head(hc, served, length)
    del hc
    ref = reference(config, seed)
    h, _ = _served_hidden(ref, picked, length)
    best, at, _ = ref.head(h, tops, length)
    return float(np.max(best - at))
