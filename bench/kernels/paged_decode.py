"""Operations and bytes the paged decode attention needs (per call of the
kernel, summed over its live sequences).

Counted from the work, not from the kernel's grid: for each live sequence
with ``kv_len`` keys, every query head multiplies against each key and
value once (4 * Hq * E * kv_len operations), and K and V are read once per
KV head for each live page (whole pages), with the query and output rows.
So the count stays fixed when a change alters how the kernel walks the
pool.
"""

from __future__ import annotations


def cost(config: dict, kv_lens, page_size: int, itemsize: int = 2):
    """(operations, bytes) of one layer's decode attention over sequences
    with ``kv_lens`` keys each."""
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    e = config["head_dim"]
    flops = 0
    nbytes = 0
    for n in kv_lens:
        pages = -(-int(n) // page_size)
        flops += 4 * hq * e * int(n)
        nbytes += (2 * pages * page_size * hkv * e + 2 * hq * e) * itemsize
    return flops, nbytes
