"""The held experts' grouped matmuls' share of their roofline: the least
time the chip needs for their operations and bytes
(``kernels/moe_experts``: the window's decode tokens and live prompt rows,
each step program's weight reads), over the device time of the grouped
matmul kernel family in the trace: ``lax.ragged_dot``'s Mosaic kernel,
``ragged-dot-none``."""

from bench.kernels import moe_experts
from bench.stats import decode_kv_lens, prefill_chunks

TRACE_NAME = "ragged-dot-none"
# model passes of a step program: a chunk+decode step runs the prompt
# chunk and the decode batch through the layers one after the other
PASSES = {"decode": 1, "chunk": 1, "chunk+decode": 2}


def read(ctx):
    if ctx.trace is None or "n_routed_experts_published" not in ctx.config:
        return None
    kernel_s = ctx.trace.kernel_s(TRACE_NAME)
    tokens = len(decode_kv_lens(ctx)) + sum(
        rows for _, rows in prefill_chunks(ctx))
    passes = sum(PASSES[s["kind"]] for s in ctx.window_steps())
    if kernel_s <= 0 or not tokens or not passes:
        return None
    flops, nbytes = moe_experts.cost(ctx.config, tokens, passes)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
