"""Draws that give every seed the same work. A run's sizes and arrival
gaps are the evenly spaced quantiles of their distribution in one fixed
mixed order; the seed turns that cycle to another starting point and fills
in the tokens. So two seeds differ in order and content and never in the
amount of work, and a window one cycle long holds every member once.
"""
from __future__ import annotations

import math
import statistics
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> List[int]:
    """``n`` whole numbers: the (i + 1/2)/n quantiles of a lognormal with
    this median and sigma, clipped to ``[lo, hi]``."""
    out = []
    for i in range(n):
        x = math.exp(math.log(median) + sigma * _NORMAL.inv_cdf((i + .5) / n))
        out.append(int(min(max(round(x), lo), hi)))
    return out


def exponential_gaps(n: int, total_s: float) -> List[float]:
    """``n`` gaps between Poisson arrivals: the (i + 1/2)/n quantiles of
    the exponential distribution, scaled to sum to ``total_s``."""
    raw = [-math.log(1.0 - (i + .5) / n) for i in range(n)]
    scale = total_s / sum(raw)
    return [g * scale for g in raw]


def fixed_order(values: list, order_seed: int) -> list:
    """``values`` in a mixed order that depends on ``order_seed`` alone
    (a number in the traffic file, the same for every run)."""
    perm = np.random.default_rng(order_seed).permutation(len(values))
    return [values[i] for i in perm]


def turned(values: list, by: int) -> list:
    by %= max(len(values), 1)
    return values[by:] + values[:by]


def tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, n, dtype=np.int32)
