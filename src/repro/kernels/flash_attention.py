"""Online-softmax streaming attention — the beyond-paper TPU kernel.

The paper keeps full (blk_q, N) score rows in VMEM (its §5.6 limitation:
max sequence halves vs FLAT). On TPU the same two-stream MXU/VPU overlap is
achievable with an online softmax (FlashAttention-style rescaling), which
shrinks the VMEM working set to (blk_q, blk_kv) and removes the second
V pass. This kernel is our optimized variant: identical outputs, strictly
smaller memory term, plus causal/sliding-window block skipping.

Inputs pre-flattened to (B*H, N, E) by ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, causal_tile_mask


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, blk_q, blk_kv,
    n_kv_blocks, sm_scale, causal, window, q_offset, kv_len
):
    iq = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    row0 = iq * blk_q + q_offset
    col0 = j * blk_kv
    # Whole-block skip: strictly above the causal diagonal, or entirely
    # outside the sliding window.
    should_run = True
    if causal or window is not None:
        should_run = col0 <= row0 + blk_q - 1
    if window is not None:
        # newest row attends back `window` positions; block ends at
        # col0+blk_kv-1 — skip if even the OLDEST in-window key is newer.
        should_run = jnp.logical_and(
            should_run, col0 + blk_kv - 1 > row0 - window
        )

    @pl.when(should_run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k_tile = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if causal or window is not None or kv_len is not None:
            # Interior tiles (strictly below the diagonal, inside the
            # window, below kv_len) skip the mask computation entirely.
            need_mask = False
            if causal or window is not None:
                need_mask = col0 + blk_kv - 1 > row0
            if window is not None:
                need_mask = jnp.logical_or(
                    need_mask, col0 <= row0 + blk_q - 1 - window
                )
            if kv_len is not None:
                need_mask = jnp.logical_or(need_mask, col0 + blk_kv > kv_len)

            def _masked(s):
                # One fused select: all active conditions AND into a
                # single mask before the where.
                mask = None
                if causal or window is not None:
                    mask = causal_tile_mask(blk_q, blk_kv, row0, col0)
                if window is not None:
                    rows = jax.lax.broadcasted_iota(
                        jnp.int32, (blk_q, blk_kv), 0) + row0
                    cols = jax.lax.broadcasted_iota(
                        jnp.int32, (blk_q, blk_kv), 1) + col0
                    mask = jnp.logical_and(mask, cols > rows - window)
                if kv_len is not None:
                    cols = jax.lax.broadcasted_iota(
                        jnp.int32, (blk_q, blk_kv), 1) + col0
                    live = cols < kv_len
                    mask = live if mask is None else jnp.logical_and(
                        mask, live)
                return jnp.where(mask, s, NEG_INF)

            s = jax.lax.cond(need_mask, _masked, lambda s: s, s)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == n_kv_blocks - 1)
    def _writeback():
        # Guard against fully-masked rows (all-skip => l == 0).
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_flat(
    q: jax.Array,  # (BHq, Nq, E)
    k: jax.Array,  # (BHkv, Nkv, E)
    v: jax.Array,  # (BHkv, Nkv, E)
    *,
    blk_q: int,
    blk_kv: int,
    causal: bool = False,
    window: int | None = None,
    sm_scale: float | None = None,
    q_offset: int = 0,
    kv_len: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    bhq, nq, e = q.shape
    bhkv, nkv_len, _ = k.shape
    assert bhq % bhkv == 0
    group = bhq // bhkv
    assert nq % blk_q == 0 and nkv_len % blk_kv == 0
    scale = (e**-0.5) if sm_scale is None else sm_scale
    n_q_blocks = nq // blk_q
    n_kv_blocks = nkv_len // blk_kv
    if kv_len is not None and kv_len >= nkv_len:
        kv_len = None

    kernel = functools.partial(
        _flash_kernel,
        blk_q=blk_q, blk_kv=blk_kv, n_kv_blocks=n_kv_blocks, sm_scale=scale,
        causal=causal, window=window, q_offset=q_offset, kv_len=kv_len,
    )
    grid = (bhq, n_q_blocks, n_kv_blocks)
    last = n_kv_blocks - 1

    def _kv_index(bh, iq, j):
        # Clamp the block index into the live causal/window band so the
        # pipeline never DMAs a tile the kernel will skip.
        if causal or window is not None:
            row0 = iq * blk_q + q_offset
            j = jnp.minimum(j, jnp.minimum((row0 + blk_q - 1) // blk_kv, last))
            if window is not None:
                # lower clamp must stay in range too: windowed Q rows
                # (incl. blk_q padding) may extend past the KV length
                jmin = jnp.maximum((row0 - window + 1) // blk_kv, 0)
                j = jnp.maximum(j, jnp.minimum(jmin, last))
        return (bh // group, j, 0)

    in_specs = [
        pl.BlockSpec((1, blk_q, e), lambda bh, iq, j: (bh, iq, 0)),
        pl.BlockSpec((1, blk_kv, e), _kv_index),
        pl.BlockSpec((1, blk_kv, e), _kv_index),
    ]
    o_spec = pl.BlockSpec((1, blk_q, e), lambda bh, iq, j: (bh, iq, 0))
    scratch = [
        pltpu.VMEM((blk_q, 1), jnp.float32),
        pltpu.VMEM((blk_q, 1), jnp.float32),
        pltpu.VMEM((blk_q, e), jnp.float32),
    ]
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        )
    return pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((bhq, nq, e), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(q, k, v)
