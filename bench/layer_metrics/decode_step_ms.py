"""Mean time of the window's decode-only steps (the engine's
``engine.step_s.decode``: pack, dispatch and the host read that ends the
step)."""

from bench.stats import mean


def read(ctx):
    v = mean(s["step_s"] for s in ctx.window_steps() if s["kind"] == "decode")
    return None if v is None else v * 1e3
