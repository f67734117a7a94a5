"""Open-loop Poisson arrivals: independent users at the cell's (or the
sweep's) ``rate_per_s``. A run offers the requests due in its window; the
gaps are the exponential distribution's quantiles at (i + 1/2) / n, in the
mix's fixed order."""

import numpy as np

from bench.traffic.generate import quantiles

OPEN_LOOP = True


def count(mix, cell, seconds, rate_per_s):
    if not rate_per_s or rate_per_s <= 0:
        raise ValueError("poisson arrivals need a positive rate_per_s")
    return max(1, int(round(rate_per_s * seconds)))


def due_s(mix, n, rate_per_s, rng):
    return np.cumsum(rng.permutation(-np.log1p(-quantiles(n)) / rate_per_s))
