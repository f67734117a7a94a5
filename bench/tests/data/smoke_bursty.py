"""Bursts of ``burst`` requests due at once, the bursts open-loop Poisson
at ``rate_per_s / burst``: an arrival kind that a test adds as a file."""

import numpy as np

from bench.traffic.generate import quantiles

OPEN_LOOP = True


def count(mix, cell, seconds, rate_per_s):
    return max(1, int(round(rate_per_s * seconds)))


def due_s(mix, n, rate_per_s, rng):
    b = int(mix["burst"])
    k = -(-n // b)
    gaps = rng.permutation(-np.log1p(-quantiles(k)) * b / rate_per_s)
    return np.repeat(np.cumsum(gaps), b)[:n]
