"""Paged decode attention's share of its roofline: the least time the chip
needs for the live pages' operations and bytes (``kernels/paged_decode``,
every decode token stamped in the traced window, every layer), over the
kernel's device time in the trace."""

from bench.kernels import paged_decode
from bench.stats import decode_kv_lens

TRACE_NAME = "paged_decode_attention"


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = ctx.trace.kernel_s(TRACE_NAME)
    kv_lens = decode_kv_lens(ctx)
    if kernel_s <= 0 or not kv_lens:
        return None
    flops, nbytes = paged_decode.cost(ctx.config, kv_lens, ctx.page_size)
    layers = ctx.config["num_hidden_layers"]
    least = max(flops * layers / ctx.peaks["bf16_flops_per_s"],
                nbytes * layers / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
