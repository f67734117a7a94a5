"""Arithmetic the metric readers share."""

from __future__ import annotations

import math


def percentile(values, q: float):
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it (the program's ``obs.metrics`` rule). None when
    there are no values."""
    s = sorted(values)
    if not s:
        return None
    return s[min(len(s) - 1, max(0, math.ceil(q / 100 * len(s)) - 1))]


def mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def ttfts_s(ctx) -> list[float]:
    """First-token time from the due time, for every request due in the
    window; a request that never got its first token counts as infinite."""
    out = []
    for r in ctx.requests:
        if ctx.in_window(r["due"]):
            out.append(r["stamps"][0] - r["due"] if r["stamps"]
                       else math.inf)
    return out


def itls_s(ctx) -> list[float]:
    """Every gap between consecutive output tokens of one request whose
    later token was stamped inside the window."""
    out = []
    for r in ctx.requests:
        st = r["stamps"]
        out.extend(b - a for a, b in zip(st, st[1:]) if ctx.in_window(b))
    return out


def window_tokens(ctx) -> int:
    return sum(ctx.in_window(t) for r in ctx.requests for t in r["stamps"])


def decode_kv_lens(ctx) -> list[int]:
    """Context length of every decode-step token stamped inside the window.
    A request's token i >= 1 comes out of the decode step that attends
    over prompt_len + i keys; token 0 comes out of its last prefill
    chunk."""
    out = []
    for r in ctx.requests:
        out.extend(r["prompt_len"] + i for i, t in enumerate(r["stamps"])
                   if i >= 1 and ctx.in_window(t))
    return out


def prefill_chunks(ctx) -> list[tuple[int, int]]:
    """(first row, live rows) of every prompt chunk of the requests whose
    prefill ended inside the window (first token stamped there): the
    engine cuts a prompt into ``chunk_size`` rows from position 0."""
    out = []
    for r in ctx.requests:
        if r["stamps"] and ctx.in_window(r["stamps"][0]):
            n, cs = r["prompt_len"], ctx.chunk_size
            out.extend((q0, min(cs, n - q0)) for q0 in range(0, n, cs))
    return out
