"""Operations and bytes latent (MLA) decode attention needs (per call of
the kernel, one layer, summed over its live sequences).

Counted from the work in the absorbed form, not from the kernel's grid:
for each live sequence with ``kv_len`` keys, every query head scores the
latent row (``kv_lora_rank + qk_rope_head_dim`` values) and weights its
first ``kv_lora_rank`` values, 2 * heads * (latent + rank) operations a
key; each live page's latent rows are read once (whole pages, at their
published 576 values; the pool's zero padding to 640 is not work), with
the query row (heads x latent) and the output row (heads x rank).
"""

from __future__ import annotations


def cost(config: dict, kv_lens, page_size: int, itemsize: int = 2):
    """(operations, bytes) of one layer's latent decode attention over
    sequences with ``kv_lens`` keys each."""
    h = config["num_attention_heads"]
    r = config["kv_lora_rank"]
    lat = r + config["qk_rope_head_dim"]
    flops = 0
    nbytes = 0
    for n in kv_lens:
        pages = -(-int(n) // page_size)
        flops += 2 * h * (lat + r) * int(n)
        nbytes += (pages * page_size * lat + h * lat + h * r) * itemsize
    return flops, nbytes
