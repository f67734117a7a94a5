"""Admission queue wait (admission stamp minus due time) of the requests
due in the window, at the percentile of the cell's first-token metric:
``ttft_p<N>_ms`` among its end-to-end metrics in ``BENCHMARK.json``, so
that one entry there sets both. Nothing to read in a cell without one.
Host clock, read from the engine's ``admit_walltime_s`` stamps; a request
never admitted counts as infinite."""

import math
import re

from bench.stats import percentile


def read(ctx):
    tails = [int(m.group(1)) for name in ctx.end_to_end
             if (m := re.fullmatch(r"ttft_p(\d+)_ms", name))]
    if not tails:
        return None
    waits = [(r["admit"] - r["due"]) if r["admit"] is not None else math.inf
             for r in ctx.requests if ctx.in_window(r["due"])]
    v = percentile(waits, tails[0])
    return None if v is None else v * 1e3
