"""The traffic generator: deterministic in the seed, inside its clips, and
the same work for every seed."""
import chipbench_testpaths  # noqa: F401  (sys.path for chipbench)
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import generator

ROOT = Path(__file__).resolve().parents[2]
MIXES = sorted((ROOT / "chipbench" / "traffic").glob("*.json"))
BIG_SEED = 2 ** 31 + 987_654_321


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    mix = json.loads(path.read_text())
    a = generator.requests(mix, 32_256, BIG_SEED, 30)
    b = generator.requests(mix, 32_256, BIG_SEED, 30)
    assert a == b
    assert a != generator.requests(mix, 32_256, BIG_SEED + 1, 30)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_requests_stay_inside_the_mix(path):
    mix = json.loads(path.read_text())
    span = mix["lead_in_s"] + 30
    reqs = generator.requests(mix, 32_256, 7, 30)
    assert len(reqs) == round(mix["arrivals"]["rate_per_s"] * span)
    arrivals = [r.arrival_s for r in reqs]
    assert arrivals == sorted(arrivals) and arrivals[0] == 0.0
    assert arrivals[-1] < span
    for r in reqs:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert all(0 <= t < 32_256 for t in r.prompt)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_gets_the_same_work(path):
    """One schedule of arrivals and lengths per mix; the seed draws the
    prompt tokens only."""
    mix = json.loads(path.read_text())
    a = generator.requests(mix, 32_256, 1, 30)
    b = generator.requests(mix, 32_256, BIG_SEED, 30)
    assert [(r.arrival_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.arrival_s, len(r.prompt), r.max_new) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    other = dict(mix, schedule_seed=mix["schedule_seed"] + 1)
    c = generator.requests(other, 32_256, 1, 30)
    assert sorted(r.max_new for r in c) == sorted(r.max_new for r in a)
    assert [r.max_new for r in c] != [r.max_new for r in a]


def test_lengths_follow_the_lognormal_median():
    mix = {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32,
           "max": 512}
    x = generator._lengths(mix, 1001)
    assert x[500] == 192 and x.min() >= 32 and x.max() == 512


def test_zipf_tokens_are_skewed():
    mix = json.loads(MIXES[0].read_text())
    reqs = generator.requests(mix, 1000, 3, 30)
    toks = np.concatenate([r.prompt for r in reqs])
    counts = np.sort(np.bincount(toks, minlength=1000))[::-1]
    # Zipf(1) over 1000 ranks: the top rank takes ~13% of draws
    assert 0.09 < counts[0] / counts.sum() < 0.18
