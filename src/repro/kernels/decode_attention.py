"""Split-KV single-token decode attention (flash-decode style).

For the ``decode_*`` / ``long_*`` serving shapes: one new query token per
sequence attends to a KV cache of length S, masked at ``kv_len``. The MXU
row dimension is the GQA *group* (query heads sharing one kv head), padded
to the sublane minimum; the KV cache is swept in ``blk_kv`` tiles with the
usual online max/sum combine. Grid = (B*Hkv, n_kv_blocks).

Quantized caches (DESIGN.md §5): when ``k_scale``/``v_scale`` are given,
K/V are int8 and each cache *row* carries one fp32 scale. The DMA then
moves 1/2–1/4 the bytes and dequantization happens inside the kernel on
the VEC stream, after the copy: the K scales multiply the (G, blk_kv)
score tile columns (cheaper than scaling the (blk_kv, E) K tile) and the
V scales fold into P before the PV MatMul.

Inputs pre-grouped to q: (B*Hkv, G, E), caches: (B*Hkv, S, E) by ops.py;
scales: (B*Hkv, S) fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, mask_kv_tail


def _decode_kernel(
    kvlen_ref, q_ref, k_ref, v_ref, *refs,
    blk_kv, n_kv_blocks, sm_scale, quantized
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kvlen_ref[0]
    col0 = j * blk_kv

    @pl.when(col0 < kv_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # (G, E)
        k_tile = k_ref[0].astype(jnp.float32)  # (blk_kv, E)
        s = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if quantized:
            # per-row K scales dequantize the score *columns* (VEC pass
            # over (G, blk_kv) — smaller than the (blk_kv, E) K tile)
            s = s * ks_ref[0][None, :]
        s = mask_kv_tail(s, col0, kv_len)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            # per-row V scales fold into P ahead of the PV MatMul
            p = p * vs_ref[0][None, :]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == n_kv_blocks - 1)
    def _writeback():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_flat(
    q: jax.Array,  # (B*Hkv, G, E) — G = padded GQA group
    k: jax.Array,  # (B*Hkv, S, E) — compute dtype, or int8 when quantized
    v: jax.Array,  # (B*Hkv, S, E)
    kv_len: jax.Array,  # () int32
    *,
    blk_kv: int,
    sm_scale: float | None = None,
    k_scale: jax.Array | None = None,  # (B*Hkv, S) fp32 per-row scales
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    bh, g, e = q.shape
    _, s_len, _ = k.shape
    assert s_len % blk_kv == 0
    quantized = k_scale is not None
    assert (v_scale is None) == (k_scale is None)
    scale = (e**-0.5) if sm_scale is None else sm_scale
    n_kv_blocks = s_len // blk_kv

    kernel = functools.partial(
        _decode_kernel, blk_kv=blk_kv, n_kv_blocks=n_kv_blocks,
        sm_scale=scale, quantized=quantized,
    )

    def kv_index(bh_, j, kvlen_ref):
        # Tiles past kv_len are skipped by `pl.when` in the body, but an
        # unclamped index map would still DMA them. Clamp to the last
        # live tile so dead steps revisit the same block and the grid
        # pipeline issues no copy (DESIGN.md §3 flash/MAS treatment).
        last = jnp.maximum(kvlen_ref[0] - 1, 0) // blk_kv
        return (bh_, jnp.minimum(j, last), 0)

    def scale_index(bh_, j, kvlen_ref):
        last = jnp.maximum(kvlen_ref[0] - 1, 0) // blk_kv
        return (bh_, jnp.minimum(j, last))

    in_specs = [
        pl.BlockSpec((1, g, e), lambda bh_, j, *_: (bh_, 0, 0)),
        pl.BlockSpec((1, blk_kv, e), kv_index),
        pl.BlockSpec((1, blk_kv, e), kv_index),
    ]
    operands = [q, k, v]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, blk_kv), scale_index),
            pl.BlockSpec((1, blk_kv), scale_index),
        ]
        operands += [jnp.asarray(k_scale, jnp.float32),
                     jnp.asarray(v_scale, jnp.float32)]

    grid = (bh, n_kv_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, e), lambda bh_, j, *_: (bh_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, e), jnp.float32),
        ],
    )
    kwargs = {}
    if not interpret:
        # B*Hkv cells are independent; only the KV-block dimension
        # carries the online-softmax accumulation in scratch.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
    return pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, g, e), q.dtype),
        interpret=interpret,
        **kwargs,
    )(jnp.asarray(kv_len, jnp.int32).reshape(1), *operands)
