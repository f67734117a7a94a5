"""Chunked prefill attention over a block-table paged KV cache.

Prefill-time analogue of ``paged_decode_attention.py`` (DESIGN.md §6):
one fixed-size chunk of prompt Q rows attends to ALL earlier context —
including the chunk's own keys, written into the page pool just before
this kernel runs — read directly from the global pool through the
page-table scalar-prefetch gather. The dense batch-1 prefill cache and
the copy-on-admit scatter disappear: every chunk of every prompt lowers
to this ONE compile shape.

The chunk starts at absolute position ``q_offset`` (a *traced* scalar on
the prefetch path, so chunk index never re-specializes the kernel) and
``kv_len = q_offset + live chunk rows`` bounds the visible context.
Causality reuses the §3 three-band classification with pages as KV
tiles:

* pages ``[0, n_full)``  — fully visible to every chunk row (the last
  key position ``<= q_offset``): computed with NO in-tile mask;
* pages ``[n_full, n_needed)`` — straddle the chunk's causal diagonal
  or the ``kv_len`` tail: one fused ``cols <= rows & cols < kv_len``
  select;
* pages ``[n_needed, max_pages)`` — dead: ``pl.when`` skips compute and
  the index map clamps to the last live page, so consecutive dead steps
  revisit the same block and issue no DMA.

Ragged last chunks pad their Q rows; pad rows (absolute position
``>= kv_len``) see only live keys (their scores past ``kv_len`` are
masked), produce garbage the caller discards, and their K/V rows are
zeroed by the caller before the page write.

Quantized pools ride the same per-page fp32 scale side-tables as the
decode kernel, read from SMEM through the ``table_ref`` indirection
(K scales multiply the (chunk, page) score tile, V scales fold into P).

Grid = (Hq, max_pages), page dimension innermost (online max/sum
combine in scratch); the q-head dimension is ``"parallel"``.
q: (Hq, chunk, E) — one sequence per call; pools: (Hkv, P, page, E).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, three_band_select


def _paged_prefill_kernel(
    qoff_ref, kvlen_ref, table_ref, *refs,
    chunk, page_size, n_pages, group, sm_scale, quantized
):
    if quantized:
        (ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    h = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0 = qoff_ref[0]
    kv_len = kvlen_ref[0]
    col0 = j * page_size
    # §3 three-band classification with pages as KV tiles (q_offset is
    # traced, so the bands are computed in-kernel, not at trace time).
    n_full = (q0 + 1) // page_size

    @pl.when(col0 < kv_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)          # (chunk, E)
        k_page = k_ref[0, 0].astype(jnp.float32)  # (page, E)
        s = jax.lax.dot_general(
            q, k_page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if quantized:
            # per-page scales from SMEM, through the same page-table
            # indirection the index maps use (scalar-prefetch path)
            s = s * ks_ref[h // group, table_ref[j]]

        # Fully-visible pages skip the mask computation entirely; only
        # diagonal-straddling / kv_len-tail pages pay the VEC select.
        s = jax.lax.cond(
            j >= n_full,
            lambda s: three_band_select(s, q0, col0, kv_len),
            lambda s: s, s)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * vs_ref[h // group, table_ref[j]]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _writeback():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_prefill_attention_flat(
    q: jax.Array,           # (Hq, chunk, E) — one sequence's prompt chunk
    k_pages: jax.Array,     # (Hkv, P, page, E) — global page pool
    v_pages: jax.Array,     # (Hkv, P, page, E)
    page_table: jax.Array,  # (max_pages,) int32 physical page ids
    q_offset: jax.Array,    # () int32 absolute position of chunk row 0
    kv_len: jax.Array,      # () int32 == q_offset + live chunk rows
    *,
    sm_scale: float | None = None,
    k_scales: jax.Array | None = None,  # (Hkv, P) fp32 per-page scales
    v_scales: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    hq, chunk, e = q.shape
    hkv, _, page_size, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    n_pages = page_table.shape[0]
    quantized = k_scales is not None
    assert (v_scales is None) == (k_scales is None)
    scale = (e**-0.5) if sm_scale is None else sm_scale

    def kv_index(h, j, qoff_ref, kvlen_ref, table_ref, *_):
        # Clamp dead pages to the last live one so the grid pipeline
        # issues no DMA for them (§3 treatment, same as paged decode).
        last = jnp.maximum(kvlen_ref[0] - 1, 0) // page_size
        return (h // group, table_ref[jnp.minimum(j, last)], 0, 0)

    kernel = functools.partial(
        _paged_prefill_kernel, chunk=chunk, page_size=page_size,
        n_pages=n_pages, group=group, sm_scale=scale, quantized=quantized,
    )
    scalars = [jnp.asarray(q_offset, jnp.int32).reshape(1),
               jnp.asarray(kv_len, jnp.int32).reshape(1),
               jnp.asarray(page_table, jnp.int32)]
    if quantized:
        scalars += [jnp.asarray(k_scales, jnp.float32),
                    jnp.asarray(v_scales, jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(hq, n_pages),
        in_specs=[
            pl.BlockSpec((1, chunk, e), lambda h, j, *_: (h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, e), kv_index),
            pl.BlockSpec((1, 1, page_size, e), kv_index),
        ],
        out_specs=pl.BlockSpec((1, chunk, e), lambda h, j, *_: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((chunk, 1), jnp.float32),
            pltpu.VMEM((chunk, 1), jnp.float32),
            pltpu.VMEM((chunk, e), jnp.float32),
        ],
    )
    kwargs = {}
    if not interpret:
        # Only the page dimension carries the online-softmax combine;
        # q heads are independent.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
    return pl.pallas_call(
        kernel,
        name="paged_prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hq, chunk, e), q.dtype),
        interpret=interpret,
        **kwargs,
    )(*scalars, q, k_pages, v_pages)
