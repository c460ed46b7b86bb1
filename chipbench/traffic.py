"""Open-loop arrival schedules from a traffic file.

The schedule is cut into blocks of ``block`` requests, each block spans
exactly ``block / rate`` seconds, and inside a block the gaps are the
exponential distribution's quantiles and the lengths a stratified share
of the log-normal's quantiles, both shuffled.  So the offered load of any
block is the file's rate and the lengths follow the file's distribution,
while the arrivals inside a block stay Poisson-like.

The shuffle is one fixed draw, the same for every run seed: the seed
picks the weights and the prompts' tokens, never the arrival times or
lengths.  At four fifths of the knee a 90th-percentile TTFT over some
sixty requests moves by a third or more between two orders of the same
arrivals, so a schedule that changed with the seed would make the seed,
not the program, set the tail.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Arrival:
    index: int
    at: float            # scheduled arrival, seconds from the run's start
    prompt_len: int
    new_tokens: int


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """Sorted ``max(1, min(int(X), max))`` at the n mid-quantiles of a
    log-normal with the given median and log-sigma."""
    z = np.array([NormalDist().inv_cdf((j + 0.5) / n) for j in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(x.astype(np.int64), 1, spec["max"])


def block_size(traffic: dict) -> int:
    return max(8, round(traffic["rate_per_s"] * traffic["block_s"]))


def schedule(traffic: dict, horizon: float) -> list:
    """Arrivals from t=0 until at least ``horizon`` seconds."""
    rate = traffic["rate_per_s"]
    k = block_size(traffic)
    n_blocks = math.ceil(horizon * rate / k) + 1
    n = n_blocks * k
    rng = np.random.default_rng(0x7AFF1C)
    q = (np.arange(k) + 0.5) / k
    gaps = -np.log1p(-q)
    gaps *= (k / rate) / gaps.sum()
    # stratified: block b takes quantile ranks b, b + n_blocks, ...
    prompts = _lognormal_quantiles(traffic["prompt_tokens"], n)
    outputs = _lognormal_quantiles(traffic["output_tokens"], n)
    out, t = [], 0.0
    for b in range(n_blocks):
        p = rng.permutation(prompts[b::n_blocks])
        o = rng.permutation(outputs[b::n_blocks])
        for j, g in enumerate(rng.permutation(gaps)):
            t += g
            out.append(Arrival(len(out), t, int(p[j]), int(o[j])))
    return out


def max_lengths(traffic: dict) -> tuple:
    return traffic["prompt_tokens"]["max"], traffic["output_tokens"]["max"]
