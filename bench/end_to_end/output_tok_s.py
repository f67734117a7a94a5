"""Output tokens stamped inside the window over the window's seconds
(host clock)."""

from bench.stats import window_tokens


def read(ctx):
    return window_tokens(ctx) / ctx.window_s
