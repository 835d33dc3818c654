"""Request lengths and the request record the arrival generators share.

A block of ``n`` draws takes the distribution's quantiles at
(i + 0.5) / n, shuffled. In an open loop every seed draws the same
multiset of lengths and of gaps between arrivals in its own order; the
closed loop draws one sequence of lengths for every seed
(``arrivals/closed.py``).

A length spec in a traffic file is one of
``{"dist": "uniform", "lo": a, "hi": b}`` (integers a..b) or
``{"dist": "lognormal", "median": m, "sigma": s, "lo": a, "hi": b}``
(clipped to a..b).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Req:
    """One request as the generator schedules it. ``due_t`` is seconds
    after the window opens (negative for warm-up requests); ``idx`` is
    the generator's running count, which fixes the prompt's tokens."""
    idx: int
    due_t: float
    prompt_len: int
    max_tokens: int
    client: int = -1


def quantile(spec: dict, u: float) -> int:
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if spec["dist"] == "uniform":
        return min(hi, lo + int(u * (hi - lo + 1)))
    if spec["dist"] == "lognormal":
        z = NormalDist().inv_cdf(u)
        x = math.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return int(min(hi, max(lo, round(x))))
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def stratified(spec: dict, n: int, rng: np.random.Generator) -> list[int]:
    """``n`` lengths at the spec's quantiles (i + 0.5) / n, shuffled."""
    xs = [quantile(spec, (i + 0.5) / n) for i in range(n)]
    return [xs[i] for i in rng.permutation(n)]


def stratified_gaps(rate: float, n: int, span: float,
                    rng: np.random.Generator) -> list[float]:
    """``n`` gaps of a Poisson process at ``rate`` per second: the
    exponential's quantiles, scaled to add up to ``span`` seconds and
    shuffled."""
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])
    gaps *= span / gaps.sum()
    return [float(g) for g in gaps[rng.permutation(n)]]


def max_total(mix: dict) -> int:
    """Longest prompt plus longest answer the mix can draw."""
    return int(mix["prompt"]["hi"]) + int(mix["output"]["hi"])
