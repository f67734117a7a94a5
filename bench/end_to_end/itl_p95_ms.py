"""95th percentile of the gap between consecutive output tokens, over every
gap that ends inside the window (host clock)."""

from bench.stats import itls_s, percentile


def read(ctx):
    v = percentile(itls_s(ctx), 95)
    return None if v is None else v * 1e3
