"""Paged prefill attention's share of its roofline: the least time the chip
needs for the live rows' causal operations and bytes
(``kernels/paged_prefill``, every chunk of the prompts whose prefill ended
in the traced window, every layer), over the kernel's device time."""

from bench.kernels import paged_prefill
from bench.stats import prefill_chunks

TRACE_NAME = "paged_prefill_attention"


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = ctx.trace.kernel_s(TRACE_NAME)
    chunks = prefill_chunks(ctx)
    if kernel_s <= 0 or not chunks:
        return None
    layers = ctx.config["num_hidden_layers"]
    least = 0.0
    for q0, rows in chunks:
        flops, nbytes = paged_prefill.cost(ctx.config, q0, rows,
                                           ctx.page_size)
        least += max(flops * layers / ctx.peaks["bf16_flops_per_s"],
                     nbytes * layers / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
