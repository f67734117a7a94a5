"""Median first-token time from each request's due time, over every
request due in the window (host clock). The chat cell offers about 25
requests in its window: the median is the highest percentile with ten
requests beyond it."""

from bench.stats import percentile, ttfts_s


def read(ctx):
    v = percentile(ttfts_s(ctx), 50)
    return None if v is None else v * 1e3
