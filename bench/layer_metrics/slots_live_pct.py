"""Mean share of the decode slots live in the window's steps (the engine's
``step_log`` live decode count over its slots)."""

from bench.stats import mean


def read(ctx):
    v = mean(s["live"] for s in ctx.window_steps())
    return None if v is None else 100.0 * v / ctx.slots
