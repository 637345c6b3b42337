"""The one traffic generator: a mix's parameter file in, requests out.

A mix (`bench/traffic/<name>.json`) gives the loop, the rate or the number
of clients, the length distributions, the sampling and the engine's slot
pool; the generator turns it and a seed into requests. Every seed gets the
same work in another order: lengths and inter-arrival gaps are the
mid-point quantiles of their distributions (the i-th of n is the
(i + 1/2)/n quantile), permuted by the seed, and only the order, the token
ids and which requests are greedy follow from the seed. So two seeds differ
in content and arrival order, not in how much there is to do.

Open loop: arrivals at `rate_rps` from `-ramp_s` (a ramp that fills the
slots before the window) to the end of the window; each request is due at
its arrival time. The ramp and the window are drawn apart, each with its
own quantiles, so the window holds the same number of requests of the same
sizes under every seed. Closed loop: `clients` independent callers, each
with a queue of requests it sends one after another.

Greedy requests: one in `greedy_every` is greedy whatever the mix samples,
so that the served tokens of those can be checked against the reference.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the traffic plans it."""
    idx: int
    due_s: float                   # open loop: arrival, relative to window
    prompt: np.ndarray             # (T,) int32
    max_new_tokens: int
    temperature: float
    top_p: float
    client: int = -1               # closed loop: which caller sends it


def quantiles(dist: dict, n: int) -> np.ndarray:
    """Mid-point quantiles of a length distribution, clipped, as ints."""
    p = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(q)) for q in p])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + p * (dist["max"] + 1 - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), dist["min"], dist["max"]).astype(np.int64)


def length_bounds(dist: dict) -> tuple:
    return int(dist["min"]), int(dist["max"])


def _phase(mix: dict, rng, n: int, rate: float, start: float) -> list:
    """n open-loop requests from `start`: the mid-point quantiles of the
    inter-arrival gaps and of the lengths, each permuted by the seed."""
    gaps = rng.permutation(-np.log(1.0 - (np.arange(n) + 0.5) / n) / rate)
    due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return _requests(mix, rng, due, np.full(n, -1))


def _requests(mix: dict, rng, due, clients) -> list:
    n = len(due)
    plens = rng.permutation(quantiles(mix["prompt"], n))
    outs = rng.permutation(quantiles(mix["output"], n))
    greedy = rng.permutation(np.arange(n) % mix.get("greedy_every", 1) == 0)
    temp = mix["sampling"].get("temperature", 0.0)
    top_p = mix["sampling"].get("top_p", 1.0)
    out = []
    for i in range(n):
        prompt = rng.integers(0, mix["vocab"], size=int(plens[i])).astype(
            np.int32)
        g = bool(greedy[i]) or temp <= 0.0
        out.append(Planned(i, float(due[i]), prompt, int(outs[i]),
                           0.0 if g else temp, 1.0 if g else top_p,
                           int(clients[i])))
    return out


def generate(mix: dict, seed: int, vocab: int, seconds: float,
             rate_rps: Optional[float] = None) -> List[Planned]:
    """The mix's requests for one run of `seconds`. `rate_rps` overrides
    the mix's rate (the knee sweep)."""
    rng = np.random.default_rng(int(seed))
    mix = dict(mix, vocab=vocab)
    if mix["loop"] == "open":
        rate = rate_rps or mix["rate_rps"]
        ramp = _phase(mix, rng, int(math.ceil(rate * mix["ramp_s"])), rate,
                      -mix["ramp_s"])
        window = _phase(mix, rng, int(math.ceil(rate * seconds)), rate, 0.0)
        out = ramp + window
        for i, p in enumerate(out):
            p.idx = i
        return out
    if mix["loop"] == "closed":
        n = mix["clients"] * mix["requests_per_client"]
        return _requests(mix, rng, np.zeros(n), np.arange(n) % mix["clients"])
    raise ValueError(f"unknown loop {mix['loop']!r}")
