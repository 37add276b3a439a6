"""Open-loop traffic from a mix file (``chipbench/traffic/<cell>.json``) and a seed.

Every seed gets the same work: request lengths come from a fixed quantile
grid of the mix's distributions and inter-arrival gaps from a fixed
quantile grid of the exponential, put in one order by the mix's own
``schedule_seed``. The run's seed draws the prompt tokens only. At four
fifths of a cell's capacity the tail of the time to first token follows
the bursts of the arrival order, so a seed that reordered arrivals would
change the load; with one schedule, runs on different seeds spread as
little as repeated runs of one seed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    arrival_s: float          # scheduled arrival, from the start of traffic
    prompt: tuple
    max_new: int


def _lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of a clipped lognormal."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def _gaps(spec: dict, n: int, span_s: float) -> np.ndarray:
    """n inter-arrival gaps at the mid-quantiles of the exponential, scaled
    so that they fill ``span_s``."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (span_s / g.sum())


def zipf_probs(vocab: int, alpha: float) -> np.ndarray:
    """Finite Zipf law over ranks 1..vocab, P(r) ~ r^-alpha (the law of
    ``repro.pool.cache.zipf_keys`` for alpha <= 1)."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -alpha
    return w / w.sum()


def requests(traffic: dict, vocab: int, seed: int,
             seconds: float) -> list[Request]:
    """The requests of one run: traffic starts ``lead_in_s`` before the
    measured window of ``seconds`` and runs to its close."""
    span = float(traffic["lead_in_s"]) + float(seconds)
    n = max(1, int(round(traffic["arrivals"]["rate_per_s"] * span)))
    order = np.random.default_rng(traffic["schedule_seed"])
    gaps = order.permutation(_gaps(traffic["arrivals"], n, span))
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompt_lens = order.permutation(_lengths(traffic["prompt"], n))
    out_lens = order.permutation(_lengths(traffic["output"], n))
    rng = np.random.default_rng(seed)
    tok = traffic["tokens"]
    if tok["dist"] != "zipf":
        raise ValueError(f"unknown token distribution {tok['dist']!r}")
    rank_to_id = rng.permutation(vocab)
    cdf = np.cumsum(zipf_probs(vocab, tok["alpha"]))
    draws = np.searchsorted(cdf, rng.random(int(prompt_lens.sum())),
                            side="right")
    ids = rank_to_id[np.minimum(draws, vocab - 1)]
    out, at = [], 0
    for t, p, m in zip(arrivals, prompt_lens, out_lens):
        out.append(Request(float(t), tuple(int(x) for x in ids[at:at + p]),
                           int(m)))
        at += p
    return out
