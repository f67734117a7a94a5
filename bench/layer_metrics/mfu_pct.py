"""The whole step's share of the chip's bf16 peak: model operations of the
tokens processed in the traced window (decode tokens and live prompt
rows, counted by the cell's architecture module, ``bench/archs/``) over
window seconds times the peak."""

from bench.stats import decode_kv_lens, prefill_chunks


def read(ctx):
    if ctx.trace is None:
        return None
    cfg, arch = ctx.config, ctx.arch
    per_token, head = arch.matmul_flops_per_token(cfg), arch.head_flops(cfg)
    flops = 0
    for n in decode_kv_lens(ctx):
        flops += per_token + head + arch.attention_flops(cfg, n)
    for q0, rows in prefill_chunks(ctx):
        keys = rows * q0 + rows * (rows + 1) // 2
        flops += rows * per_token + head + arch.attention_flops(cfg, keys)
    if flops == 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
