"""99th percentile of the gap between consecutive output tokens, over
every gap that ends inside the window (host clock). A step gives every
live slot the same gap, so in the chat cell (one step in ten carries a
prompt chunk) this is about the third-longest chunk step of the window,
and a host pause on any chunk step lifts it to the next one up."""

from bench.stats import itls_s, percentile


def read(ctx):
    v = percentile(itls_s(ctx), 99)
    return None if v is None else v * 1e3
