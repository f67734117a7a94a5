"""Paged single-token decode attention over a block-table KV cache.

Decode-time analogue of the paper's memory-aware tiling: the KV cache
lives in fixed-size pages scattered through a global pool, and a
per-sequence page table maps logical KV block ``j`` to its physical
page. The page table and per-sequence lengths ride the
``PrefetchScalarGridSpec`` scalar-prefetch path into SMEM, and the
kernel gathers exactly the pages each sequence owns with its own DMAs
— no dense copy of the cache.

Grid = (B,): one grid step per slot, every KV head at once. The pools
stay in HBM (``memory_space=pl.ANY``); the step walks its slot's live
pages in blocks of ``pages_per_block`` pages, each page copied for all
heads (``pool[:, page_id]``) into a double-buffered VMEM block. The
next block's copies start before the current block is computed, and a
slot's last block starts the first block of the next slot with live
keys, so the copies run behind the compute across slots too. Pages past
a slot's last live page are neither copied nor computed, and slots with
``kv_len == 0`` cost one empty grid step. The scores are one batched
``dot_general`` over the head axis, (Hkv, G, E) x (Hkv, T, E), and the
online-softmax state is (Hkv, G, 1) / (Hkv, G, E) fp32 scratch.
``pages_per_block`` comes from the shapes
(``decode_pages_per_block``): the most pages whose double-buffered K
and V blocks fit ``DECODE_VMEM_BUDGET``, capped at ``max_pages``.

Arithmetic: fp32 scores, running max and sum, and accumulator. QK^T
feeds the MXU the operands as stored when q and the pool share a dtype
(bf16 x bf16 products are exact in the fp32 accumulator); otherwise
both go to fp32. P stays fp32 for P.V. A block's columns past
``kv_len`` are masked (``mask_kv_tail``), and its V rows there are
zeroed, since the buffer holds whatever an earlier block left.

Quantized pools (DESIGN.md §5): when ``k_scales``/``v_scales`` are
given, the pools are int8 and each physical page carries one fp32
symmetric-absmax scale per kv head. The scale tables are *scalar
prefetch* operands too — one scalar per page, read from SMEM through
the page table — so the page DMA moves 1/2–1/4 the bytes and the
dequant lands on the VEC stream as a per-page-column multiply of the
(Hkv, G, T) score tile (K) and of P (V).

q pre-grouped to (B, Hkv, G, E) by ops.py; pools are (Hkv, P, page, E);
scale tables are (Hkv, P) fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, mask_kv_tail

# Scoped VMEM for the double-buffered K and V blocks: half of v5e's
# 16 MiB default, leaving the rest to the score, P and fp32 K/V values.
# Compiled for v5e at the benchmark's shapes the kernel needs at most
# 10 MiB (bf16 pools, 16-page blocks) or 11 MiB (int8, 32-page blocks).
DECODE_VMEM_BUDGET = 8 * 2**20


def decode_pages_per_block(hkv: int, page_size: int, e: int, itemsize: int,
                           max_pages: int) -> int:
    """Pages per block: the most whose K and V blocks, double-buffered,
    fit ``DECODE_VMEM_BUDGET``, at least 1 and at most ``max_pages``."""
    page_bytes = hkv * page_size * e * itemsize  # one page, every head
    return max(1, min(max_pages, DECODE_VMEM_BUDGET // (4 * page_bytes)))


def _paged_decode_kernel(
    kvlens_ref, table_ref, *refs, page_size, pages_per_block, sm_scale,
    quantized,
):
    if quantized:
        ks_ref, vs_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_ref, l_ref,
     acc_ref, buf_ref) = refs
    n_slots, max_pages = table_ref.shape
    hkv = k_buf.shape[1]
    rows = pages_per_block * page_size
    b = pl.program_id(0)

    def live_pages(s):
        return (kvlens_ref[s] + page_size - 1) // page_size

    def next_live_slot(s):
        """The first slot after ``s`` with live keys, or ``n_slots``."""
        return jax.lax.while_loop(
            lambda c: (c < n_slots)
            & (kvlens_ref[jnp.minimum(c, n_slots - 1)] == 0),
            lambda c: c + 1, s + 1)

    def block_copies(s, blk, buf, act):
        """Start (``act="start"``) or wait for the copies of block
        ``blk`` of slot ``s`` into buffer ``buf``: one K and one V copy
        per live page, every head."""
        first, n_live = blk * pages_per_block, live_pages(s)
        for i in range(pages_per_block):
            @pl.when(first + i < n_live)
            def _copy():
                page = table_ref[s, first + i]
                dst = pl.ds(i * page_size, page_size)
                for which, (hbm, vbuf) in enumerate(((k_hbm, k_buf),
                                                     (v_hbm, v_buf))):
                    cp = pltpu.make_async_copy(hbm.at[:, page],
                                               vbuf.at[buf, :, dst],
                                               sems.at[which, buf])
                    cp.start() if act == "start" else cp.wait()

    def column_scales(sc_ref, blk):
        """(Hkv, 1, rows) per-page scales of block ``blk``'s columns."""
        page_of_col = jax.lax.broadcasted_iota(
            jnp.int32, (1, rows), 1) // page_size
        pages = [table_ref[b, jnp.minimum(blk * pages_per_block + i,
                                          max_pages - 1)]
                 for i in range(pages_per_block)]
        per_head = []
        for h in range(hkv):
            col = jnp.zeros((1, rows), jnp.float32)
            for i, page in enumerate(pages):
                col = jnp.where(page_of_col == i, sc_ref[h, page], col)
            per_head.append(col)
        return jnp.stack(per_head)

    @pl.when(b == 0)
    def _first_block():
        buf_ref[0] = 0
        s0 = next_live_slot(-1)

        @pl.when(s0 < n_slots)
        def _start():
            block_copies(s0, 0, 0, "start")

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    kv_len = kvlens_ref[b]
    n_blocks = (live_pages(b) + pages_per_block - 1) // pages_per_block

    def block(blk, carry):
        buf = buf_ref[0]
        nxt = 1 - buf

        # prefetch: this slot's next block, else the next live slot's first
        @pl.when(blk + 1 < n_blocks)
        def _same_slot():
            block_copies(b, blk + 1, nxt, "start")

        @pl.when(blk + 1 == n_blocks)
        def _next_slot():
            s = next_live_slot(b)

            @pl.when(s < n_slots)
            def _start():
                block_copies(s, 0, nxt, "start")

        block_copies(b, blk, buf, "wait")
        col0 = blk * rows
        q = q_ref[0]                                   # (Hkv, G, E)
        k = k_buf[buf]                                 # (Hkv, rows, E)
        if k.dtype != q.dtype:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                   # (Hkv, G, rows)
        if quantized:
            s = s * column_scales(ks_ref, blk)
        s = mask_kv_tail(s, col0, kv_len)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        if quantized:
            p = p * column_scales(vs_ref, blk)
        # rows past kv_len hold an earlier block's pages: zero them so
        # that P's zeros there cannot meet a non-finite value
        v_rows = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1) + col0
        v = jnp.where(v_rows < kv_len, v_buf[buf].astype(jnp.float32), 0.0)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        buf_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention_flat(
    q: jax.Array,           # (B, Hkv, G, E) — G = padded GQA group
    k_pages: jax.Array,     # (Hkv, P, page, E) — global page pool
    v_pages: jax.Array,     # (Hkv, P, page, E)
    page_table: jax.Array,  # (B, max_pages) int32 physical page ids
    kv_lens: jax.Array,     # (B,) int32 live tokens per sequence
    *,
    pages_per_block: int,
    sm_scale: float | None = None,
    k_scales: jax.Array | None = None,  # (Hkv, P) fp32 per-page scales
    v_scales: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, hkv, g, e = q.shape
    _, _, page_size, _ = k_pages.shape
    quantized = k_scales is not None
    assert (v_scales is None) == (k_scales is None)
    scale = (e**-0.5) if sm_scale is None else sm_scale

    kernel = functools.partial(
        _paged_decode_kernel, page_size=page_size,
        pages_per_block=pages_per_block, sm_scale=scale,
        quantized=quantized,
    )
    scalars = [jnp.asarray(kv_lens, jnp.int32),
               jnp.asarray(page_table, jnp.int32)]
    if quantized:
        scalars += [jnp.asarray(k_scales, jnp.float32),
                    jnp.asarray(v_scales, jnp.float32)]
    rows = pages_per_block * page_size
    q_spec = pl.BlockSpec((1, hkv, g, e), lambda b_, *_: (b_, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b,),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, hkv, rows, e), k_pages.dtype),
            pltpu.VMEM((2, hkv, rows, e), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),   # (K/V, buffer)
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, e), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),       # buffer of the next block
        ],
    )
    kwargs = {}
    if not interpret:
        # A slot's last block prefetches the next slot's first: the grid
        # runs in order.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        )
    return pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, e), q.dtype),
        interpret=interpret,
        **kwargs,
    )(*scalars, q, k_pages, v_pages)
