"""Multi-token speculative-verify attention over a paged KV cache.

Speculative decoding's verify step (DESIGN.md §9): each live slot has
already written k candidate K/V rows (the last emitted token plus k-1
drafted ones) into its pages, and now attends a short Q block of those
k positions against ALL prior context in one pass. Decode is DMA-bound
on KV page traffic, so reading each page once for k query positions —
instead of once per position as k serial decode steps would — amortizes
the dominant cost k-fold while the argmax over each position's logits
lets the host accept exactly the greedy-matching draft prefix.

Structurally this kernel is batched over slots like the paged decode
kernel, on a grid (B, Hkv, max_pages) whose index maps gather pages
through the scalar-prefetched page table (clamped dead pages, online
softmax in scratch), with the prefill kernel's §3 three-band causal
banding folded in, the k-block playing the diagonal tile:

* the Q block row ``i`` holds query-head ``i % G`` of speculative
  position ``i // G`` (position-major (k·G, E) layout, G = padded GQA
  group), sitting at absolute position ``q0 + i // G`` where ``q0`` is
  the slot's entry in the ``q_starts`` prefetch vector; ``kv_lens``
  counts the candidate rows actually written (``q_starts + n_rows``),
  which may stop short of k for slots near their token budget — the
  surplus Q rows then sit past ``kv_len``, attend the full live
  context, and are discarded by the host;
* pages ``[0, n_full)`` with ``n_full = (q0 + 1) // page_size`` are
  fully visible to every row: no in-tile mask;
* later live pages straddle the k-block's diagonal or the ``kv_len``
  tail: one fused ``three_band_select`` with ``rows_per_pos = G``;
* dead pages clamp their index map to the last live page and skip
  compute, so they issue no DMA.

``k == 1`` degenerates exactly to the paged decode kernel's math (q0 is
the last position, every live page is either full or the kv-tail page).

Quantized pools ride the identical per-page fp32 scale side-tables as
decode (K scales multiply the (k·G, page) score tile, V scales fold
into P before the PV matmul).

q pre-arranged to (B, Hkv, k·G, E) by ops.py; pools (Hkv, P, page, E).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, three_band_select


def _paged_verify_kernel(
    kvlens_ref, qstarts_ref, table_ref, *refs,
    page_size, n_pages, group, sm_scale, quantized
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
    # §3 three-band classification with the k-block as the diagonal
    # tile: the earliest speculative position is the slot's q_start.
    q0 = qstarts_ref[b]
    n_full = (q0 + 1) // page_size

    @pl.when(col0 < kv_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # (k*G, E)
        k_page = k_ref[0, 0].astype(jnp.float32)  # (page, E)
        s = jax.lax.dot_general(
            q, k_page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if quantized:
            # per-page scales from SMEM, through the same page-table
            # indirection the index maps use (scalar-prefetch path)
            s = s * ks_ref[h, table_ref[b, j]]

        # Fully-visible pages skip the mask entirely; straddling /
        # kv-tail pages pay one fused select (row i // G = position).
        s = jax.lax.cond(
            j >= n_full,
            lambda s: three_band_select(s, q0, col0, kv_len,
                                        rows_per_pos=group),
            lambda s: s, s)

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


def paged_verify_attention_flat(
    q: jax.Array,           # (B, Hkv, k*G, E) — position-major rows
    k_pages: jax.Array,     # (Hkv, P, page, E) — global page pool
    v_pages: jax.Array,     # (Hkv, P, page, E)
    page_table: jax.Array,  # (B, max_pages) int32 physical page ids
    kv_lens: jax.Array,     # (B,) int32 live tokens INCL. written rows
    q_starts: jax.Array,    # (B,) int32 position of speculative row 0
    *,
    spec: int,              # k — speculative positions per slot
    sm_scale: float | None = None,
    k_scales: jax.Array | None = None,  # (Hkv, P) fp32 per-page scales
    v_scales: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, hkv, rows, e = q.shape
    assert rows % spec == 0
    group = rows // spec
    _, _, page_size, _ = k_pages.shape
    n_pages = page_table.shape[1]
    quantized = k_scales is not None
    assert (v_scales is None) == (k_scales is None)
    scale = (e**-0.5) if sm_scale is None else sm_scale

    def kv_index(b_, h, j, kvlens_ref, qstarts_ref, table_ref, *_):
        # Clamp dead pages to the last live one: repeated block indices
        # issue no DMA (same §3 treatment as the decode kernel).
        last = jnp.maximum(kvlens_ref[b_] - 1, 0) // page_size
        return (h, table_ref[b_, jnp.minimum(j, last)], 0, 0)

    kernel = functools.partial(
        _paged_verify_kernel, page_size=page_size,
        n_pages=n_pages, group=group, sm_scale=scale, quantized=quantized,
    )
    scalars = [jnp.asarray(kv_lens, jnp.int32),
               jnp.asarray(q_starts, jnp.int32),
               jnp.asarray(page_table, jnp.int32)]
    if quantized:
        scalars += [jnp.asarray(k_scales, jnp.float32),
                    jnp.asarray(v_scales, jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, hkv, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rows, e), lambda b_, h, j, *_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, e), kv_index),
            pl.BlockSpec((1, 1, page_size, e), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, e),
                               lambda b_, h, j, *_: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, e), jnp.float32),
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
        name="paged_verify_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, e), q.dtype),
        interpret=interpret,
        **kwargs,
    )(*scalars, q, k_pages, v_pages)
