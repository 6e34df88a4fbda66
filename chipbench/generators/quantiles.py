"""Fixed sets of sizes: every seed gets the same set, in another order, so
that the seed never changes the amount of work in a run."""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_set(n: int, median: float, sigma: float, lo: int, hi: int) -> np.ndarray:
    """The n mid-quantiles of a log-normal, clipped to [lo, hi], as whole numbers."""
    nd = NormalDist()
    q = [math.exp(math.log(median) + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(q), lo, hi).astype(np.int64)


def uniform_set(n: int, lo: int, hi: int) -> np.ndarray:
    return np.rint(lo + (hi - lo) * (np.arange(n) + 0.5) / n).astype(np.int64)


def exponential_set(n: int, rate: float) -> np.ndarray:
    """The n mid-quantiles of the gaps between Poisson arrivals at ``rate``."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate
