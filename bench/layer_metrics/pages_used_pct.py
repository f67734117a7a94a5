"""Mean share of the pool's pages in use over the window's steps (the
engine's ``pool.pages_used`` gauge over its usable pages)."""

from bench.stats import mean


def read(ctx):
    v = mean(s["pages_used"] for s in ctx.window_steps())
    return None if v is None else 100.0 * v / ctx.pool_pages
