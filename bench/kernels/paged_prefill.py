"""Operations and bytes the paged prefill attention needs for one chunk.

A chunk of ``rows`` live query rows starting at absolute position ``q0``
attends causally: row i sees q0 + i + 1 keys, so the chunk needs
4 * Hq * E * (rows * q0 + rows * (rows + 1) / 2) operations. It reads K
and V once per KV head for each live page up to q0 + rows (whole pages),
and the chunk's live query and output rows. Padded rows of the chunk and
pages past the causal bound are not counted.
"""

from __future__ import annotations


def cost(config: dict, q0: int, rows: int, page_size: int,
         itemsize: int = 2):
    """(operations, bytes) of one layer's prefill attention for a chunk."""
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    e = config["head_dim"]
    keys = rows * q0 + rows * (rows + 1) // 2
    flops = 4 * hq * e * keys
    pages = -(-(q0 + rows) // page_size)
    nbytes = (2 * pages * page_size * hkv * e + 2 * rows * hq * e) * itemsize
    return flops, nbytes
