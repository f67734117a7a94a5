"""The whole step's share of the chip's bf16 peak: model operations of the
tokens processed in the traced window (decode tokens and live prompt
rows, ``kernels/model_step``) over window seconds times the peak."""

from bench.kernels import model_step
from bench.stats import decode_kv_lens, prefill_chunks


def read(ctx):
    if ctx.trace is None:
        return None
    cfg = ctx.config
    dense, head = model_step.matmul_flops_per_token(cfg), \
        model_step.head_flops(cfg)
    flops = 0
    for n in decode_kv_lens(ctx):
        flops += dense + head + model_step.attention_flops(cfg, n)
    for q0, rows in prefill_chunks(ctx):
        keys = rows * q0 + rows * (rows + 1) // 2
        flops += rows * dense + head + model_step.attention_flops(cfg, keys)
    if flops == 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
