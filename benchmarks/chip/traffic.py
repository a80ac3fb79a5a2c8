"""One general open-loop traffic generator, driven by a mix file.

A mix (``traffic/<name>.json``) gives its parameters under ``"generator"``:

    arrivals      {"process": "poisson_quantiles"} with optional
                  "bursts": {"period_s", "on_share", "peak_factor"} — on/off
                  modulation at the same mean rate (BurstGPT-style)
    prompt        length distribution: {"dist": "lognormal", "median",
                  "sigma", "min", "max"} | {"dist": "uniform", "min", "max"}
                  | {"dist": "fixed", "value"}
    output        the same, for the number of tokens asked for
    shared_prefix optional {"groups": k, "share": f, "length": <dist>}: a
                  share f of requests starts with one of k prefixes drawn
                  once per run (system prompts, documents asked about again)
    greedy_share  share of requests decoded greedily (the rest sample at
                  ``temperature`` with ``top_k``)

Every run of a cell is the same amount of work: the warm-in and the window
are drawn apart, each with ``round(rate * length)`` requests whose lengths
and gaps between arrivals are the quantiles of their distributions at
``(i + 0.5) / n``. The seed draws their order, a uniform permutation of
each, and the token ids. A uniformly ordered set of exponential gaps is a
Poisson process whose gaps are pinned to the exponential's quantiles
(``poisson_quantiles``): it keeps a Poisson process's bursts, and two seeds
differ in order and in content, not in how much they ask for.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    due: float                  # seconds from the window's opening
    prompt: np.ndarray          # [1, S] int32
    max_new_tokens: int
    greedy: bool = True
    temperature: float = 1.0
    top_k: int = 0


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """n stratified draws (the quantiles at (i + 0.5) / n), as ints."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        vals = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        vals = dist["min"] + u * (dist["max"] + 1 - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        vals = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", int(1e9))
    return np.clip(np.floor(vals), lo, hi).astype(np.int64)


def _gaps(rate: float, n: int) -> np.ndarray:
    """Stratified exponential inter-arrival gaps with mean 1 / rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate



def _warp(t: np.ndarray, bursts: Optional[dict]) -> np.ndarray:
    """Map Poisson times at the mean rate onto on/off bursts of the same
    mean: a period spends ``on_share`` of its time at ``peak_factor`` times
    the mean rate and the rest at what keeps the mean."""
    if not bursts:
        return t
    period, on = bursts["period_s"], bursts["on_share"]
    peak = bursts["peak_factor"]
    low = (1.0 - on * peak) / (1.0 - on)
    if low < 0:
        raise ValueError("bursts: on_share * peak_factor must be <= 1")
    work_on = on * period * peak            # mean-rate seconds of work in on
    k, r = np.divmod(t, period)
    out = np.where(r < work_on, r / peak,
                   on * period + (r - work_on) / max(low, 1e-9))
    return k * period + out


def _segment(gen: dict, rng, *, rate: float, start: float, span: float,
             vocab: int, prefixes: list, idx0: int) -> List[Request]:
    """The requests due in [start, start + span): ``round(rate * span)`` of
    them, with stratified gaps scaled to fill the span exactly."""
    n = int(round(rate * span))
    if n <= 0:
        return []
    gaps = rng.permutation(_gaps(rate, n))
    ends = np.cumsum(gaps)
    bursts = gen["arrivals"].get("bursts")
    pos = _warp(np.concatenate([[0.0], ends[:-1]]), bursts)
    due = start + span * pos / _warp(ends[-1:], bursts)[0]
    prompt_lens = rng.permutation(_quantiles(gen["prompt"], n))
    out_lens = rng.permutation(_quantiles(gen["output"], n))
    shared = gen.get("shared_prefix")
    use_prefix = np.zeros(n, bool)
    if shared:
        use_prefix[rng.permutation(n)[:int(round(shared["share"] * n))]] = True
    greedy = np.zeros(n, bool)
    greedy[rng.permutation(n)[:int(round(gen.get("greedy_share", 1.0)
                                         * n))]] = True
    reqs = []
    for i in range(n):
        toks = rng.integers(0, vocab, int(prompt_lens[i]), dtype=np.int32)
        if use_prefix[i]:
            pre = prefixes[int(rng.integers(0, len(prefixes)))]
            toks = np.concatenate([pre, toks])[:max(len(toks), len(pre) + 1)]
        reqs.append(Request(idx0 + i, float(due[i]), toks[None],
                            int(out_lens[i]), greedy=bool(greedy[i]),
                            temperature=gen.get("temperature", 1.0),
                            top_k=gen.get("top_k", 0)))
    return reqs


def generate(gen: dict, *, rate: float, seed: int, vocab: int,
             warm_s: float, window_s: float) -> List[Request]:
    """Requests due from ``-warm_s`` to ``window_s`` (seconds from the
    window's opening), sorted by due time. The warm-in and the window are
    drawn apart, so every seed puts the same sizes into the window."""
    if gen["arrivals"]["process"] != "poisson_quantiles":
        raise ValueError(f"unknown arrival process "
                         f"{gen['arrivals']['process']!r}")
    rng = np.random.default_rng(abs(int(seed)))
    prefixes = []
    shared = gen.get("shared_prefix")
    if shared:
        plens = _quantiles(shared["length"], shared["groups"])
        prefixes = [rng.integers(0, vocab, int(p), dtype=np.int32)
                    for p in rng.permutation(plens)]
    warm = _segment(gen, rng, rate=rate, start=-warm_s, span=warm_s,
                    vocab=vocab, prefixes=prefixes, idx0=0)
    window = _segment(gen, rng, rate=rate, start=0.0, span=window_s,
                      vocab=vocab, prefixes=prefixes, idx0=len(warm))
    return warm + window
