"""The chat generator: the same seed gives the same requests, every seed
puts the same sizes into the window in another order, and the sizes and
arrivals follow the mix's stated distributions."""
import json
import os
import statistics
import sys

import numpy as np
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

import traffic  # noqa: E402

with open(os.path.join(CHIP, "traffic", "chat.json")) as f:
    CHAT = json.load(f)
GEN = CHAT["generator"]
RATE, WARM, WINDOW, VOCAB = 1.28, 15.0, 51.0, 100352


def make(seed, rate=RATE, gen=GEN):
    return traffic.generate(gen, rate=rate, seed=seed, vocab=VOCAB,
                            warm_s=WARM, window_s=WINDOW)


def window(reqs):
    return [r for r in reqs if 0 <= r.due < WINDOW]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_same_seed_same_requests(seed):
    a, b = make(seed), make(seed)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


def test_seeds_differ_in_order_and_content_not_in_work():
    a, b = window(make(1)), window(make(2))
    assert len(a) == len(b) == round(RATE * WINDOW)
    assert sorted(r.prompt.shape[1] for r in a) == \
        sorted(r.prompt.shape[1] for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    assert [r.prompt.shape[1] for r in a] != [r.prompt.shape[1] for r in b]
    assert not np.array_equal(a[0].prompt[:, :8], b[0].prompt[:, :8])


def test_lengths_follow_the_mix():
    reqs = window(make(3, rate=20.0))            # ~1000 requests
    p = [r.prompt.shape[1] for r in reqs]
    o = [r.max_new_tokens for r in reqs]
    lo, hi = GEN["prompt"]["min"], GEN["prompt"]["max"]
    assert min(p) >= lo and max(p) <= hi
    assert min(o) >= GEN["output"]["min"] and max(o) <= GEN["output"]["max"]
    # stratified lognormal: the median sits at the stated median
    assert abs(statistics.median(p) - GEN["prompt"]["median"]) <= 2
    assert abs(statistics.median(o) - GEN["output"]["median"]) <= 1
    # the log-lengths' spread matches sigma where the clip does not bite
    z = np.log(np.array(p, float) / GEN["prompt"]["median"])
    q1, q3 = np.percentile(z, [25, 75])
    assert abs((q3 - q1) / 1.349 - GEN["prompt"]["sigma"]) < 0.05
    assert all(r.greedy for r in reqs)
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < VOCAB
               for r in reqs)


def test_arrivals_fill_warm_in_and_window_at_the_rate():
    reqs = make(5, rate=4.0)
    warm = [r for r in reqs if r.due < 0]
    assert len(warm) == round(4.0 * WARM)
    assert len(window(reqs)) == round(4.0 * WINDOW)
    assert min(r.due for r in reqs) == -WARM
    assert max(r.due for r in reqs) < WINDOW
    dues = [r.due for r in window(reqs)]
    gaps = np.diff(dues)
    assert dues == sorted(dues)
    # exponential gaps: mean 1/rate, coefficient of variation about 1
    assert abs(gaps.mean() - 0.25) < 0.02
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_bursts_keep_the_mean_rate():
    gen = dict(GEN, arrivals={"process": "poisson_quantiles", "bursts": {
        "period_s": 10.0, "on_share": 0.25, "peak_factor": 3.0}})
    reqs = window(make(9, rate=6.0, gen=gen))
    assert len(reqs) == round(6.0 * WINDOW)
    phase = np.array([r.due % 10.0 for r in reqs])
    on = np.mean(phase < 2.5)
    assert 0.6 < on < 0.9              # 3 x 0.25 = 75% of requests in "on"


def test_shared_prefixes():
    gen = dict(GEN, shared_prefix={"groups": 2, "share": 0.5, "length": {
        "dist": "fixed", "value": 64}})
    reqs = window(make(4, rate=4.0, gen=gen))
    heads = {tuple(r.prompt[0, :64]) for r in reqs
             if r.prompt.shape[1] > 64}
    counts = sorted(sum(1 for r in reqs if tuple(r.prompt[0, :64]) == h)
                    for h in heads)
    assert sum(counts[-2:]) >= 0.5 * len(reqs) - 2


@pytest.mark.parametrize("seed", [6, 2**40 + 5])
def test_gaps_are_the_exponential_quantiles_in_a_seeded_order(seed):
    a, b = window(make(seed, rate=20.0)), window(make(seed + 1, rate=20.0))
    ga, gb = np.diff([r.due for r in a]), np.diff([r.due for r in b])
    # the same set of gaps in another order (the last one drawn falls
    # past the window's end)
    shared = np.intersect1d(np.round(ga, 9), np.round(gb, 9))
    assert len(ga) == len(gb) and len(shared) >= len(ga) - 1
    assert not np.allclose(ga, gb)
    # in a uniform order, arrivals per second spread as a Poisson
    # process's do: variance about the mean
    counts = np.bincount(np.floor([r.due for r in a]).astype(int))
    assert 0.6 < counts.var() / counts.mean() < 1.6


def test_unknown_arrival_process_is_refused():
    with pytest.raises(ValueError, match="arrival process"):
        make(1, gen=dict(GEN, arrivals={"process": "poisson"}))
