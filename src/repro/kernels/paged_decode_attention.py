"""Paged single-token decode attention over a block-table KV cache.

Decode-time analogue of the paper's memory-aware tiling: the KV cache
lives in fixed-size pages scattered through a global pool, and a
per-sequence page table maps logical KV block ``j`` to its physical
page. The page table and per-sequence lengths ride the
``PrefetchScalarGridSpec`` scalar-prefetch path (the same mechanism
``decode_attention.py`` uses for ``kv_len``): index maps read them
*before* the kernel body runs, so the grid pipeline DMAs exactly the
pages each sequence owns — a gather expressed entirely through block
index maps, with no dense copy of the cache.

Quantized pools (DESIGN.md §5): when ``k_scales``/``v_scales`` are
given, the pools are int8 and each physical page carries one fp32
symmetric-absmax scale per kv head. The scale tables are *scalar
prefetch* operands too — one scalar per page, read from SMEM through
the same ``table_ref`` indirection the index maps use — so the page DMA
moves 1/2–1/4 the bytes and the dequant lands on the VEC stream as a
scalar multiply of the (G, page) score tile (K) and of P (V).

Grid = (B, Hkv, max_pages); the page dimension is innermost so the
online max/sum combine accumulates in scratch across pages. Dead pages
(``j`` past a sequence's last live page) clamp their index map to the
last live page, so consecutive dead steps revisit the same block and
issue no DMA (mirrors the causal clamping of DESIGN.md §3).

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


def _paged_decode_kernel(
    kvlens_ref, table_ref, *refs, page_size, n_pages, sm_scale, quantized
):
    if quantized:
        (ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kvlens_ref[b]
    col0 = j * page_size

    @pl.when(col0 < kv_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # (G, E)
        k_page = k_ref[0, 0].astype(jnp.float32)  # (page, E)
        s = jax.lax.dot_general(
            q, k_page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if quantized:
            # per-page scales from SMEM, through the same page-table
            # indirection the index maps use (scalar-prefetch path)
            s = s * ks_ref[h, table_ref[b, j]]
        s = mask_kv_tail(s, col0, kv_len)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * vs_ref[h, table_ref[b, j]]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _writeback():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention_flat(
    q: jax.Array,           # (B, Hkv, G, E) — G = padded GQA group
    k_pages: jax.Array,     # (Hkv, P, page, E) — global page pool
    v_pages: jax.Array,     # (Hkv, P, page, E)
    page_table: jax.Array,  # (B, max_pages) int32 physical page ids
    kv_lens: jax.Array,     # (B,) int32 live tokens per sequence
    *,
    sm_scale: float | None = None,
    k_scales: jax.Array | None = None,  # (Hkv, P) fp32 per-page scales
    v_scales: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, hkv, g, e = q.shape
    _, _, page_size, _ = k_pages.shape
    n_pages = page_table.shape[1]
    quantized = k_scales is not None
    assert (v_scales is None) == (k_scales is None)
    scale = (e**-0.5) if sm_scale is None else sm_scale

    def kv_index(b_, h, j, kvlens_ref, table_ref, *_):
        # Clamp dead pages to the last live one: repeated block indices
        # issue no DMA. Sequences with kv_len == 0 read table slot 0
        # (the pool's reserved scratch page) and compute nothing.
        last = jnp.maximum(kvlens_ref[b_] - 1, 0) // page_size
        return (h, table_ref[b_, jnp.minimum(j, last)], 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, page_size=page_size, n_pages=n_pages,
        sm_scale=scale, quantized=quantized,
    )
    scalars = [jnp.asarray(kv_lens, jnp.int32),
               jnp.asarray(page_table, jnp.int32)]
    if quantized:
        scalars += [jnp.asarray(k_scales, jnp.float32),
                    jnp.asarray(v_scales, jnp.float32)]
    grid = (b, hkv, n_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, e), lambda b_, h, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, e), kv_index),
            pl.BlockSpec((1, 1, page_size, e), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, g, e), lambda b_, h, j, *_: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, e), jnp.float32),
        ],
    )
    kwargs = {}
    if not interpret:
        # Batch and kv-head cells are independent; only the page
        # dimension carries the online-softmax accumulation in scratch.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    return pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, e), q.dtype),
        interpret=interpret,
        **kwargs,
    )(*scalars, q, k_pages, v_pages)
