"""Mean time of the window's steps that carry a prompt chunk (the engine's
``engine.step_s.chunk+decode`` and ``engine.step_s.chunk``)."""

from bench.stats import mean


def read(ctx):
    v = mean(s["step_s"] for s in ctx.window_steps()
             if s["kind"] in ("chunk", "chunk+decode"))
    return None if v is None else v * 1e3
