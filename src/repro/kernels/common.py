"""Constants and mask helpers shared by the attention kernels.

Every kernel (and the XLA twins in ``models/attention.py``) builds its
masks from the same two primitives so the causal/padding semantics are
defined exactly once:

* ``causal_tile_mask`` — the begin-aligned in-tile causal mask
  (``cols <= rows``) for a (blk_q, blk_kv) tile at (row0, col0);
* ``mask_kv_tail`` — the padded-cache mask: score columns at absolute
  kv position >= ``kv_len`` are forced to ``NEG_INF``.

``causal_tile_bounds`` is the three-band tile classification of
DESIGN.md §3 (fully-visible / diagonal-straddling / fully-masked) that
both MAS variants, the flash kernel's index-map clamps, and the cost
models key off.

``three_band_select`` is the *in-kernel* form of the straddling-band
mask shared by the paged prefill and verify kernels (DESIGN.md §6, §9):
the row-0 query position is a traced scalar there (chunk offset /
``kv_len - k``), so the fused ``cols <= rows & cols < kv_len`` select
is built from traced values inside the kernel body rather than at
trace time; ``rows_per_pos`` collapses grouped query-head rows onto one
absolute position (the verify kernel's (k·G, page) tiles).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Finite stand-in for -inf: exp(NEG_INF - m) underflows to exactly 0 in
# fp32 without producing NaNs when a whole row is masked.
NEG_INF = -1e30


def causal_tile_mask(blk_q: int, blk_kv: int, row0, col0):
    """Begin-aligned causal mask for one (blk_q, blk_kv) score tile."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_kv), 0) + row0
    cols = jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_kv), 1) + col0
    return cols <= rows


def causal_tile_bounds(iq, blk_q: int, blk_kv: int, nkv: int):
    """(n_full, n_needed) KV-tile counts for Q row block ``iq``.

    Tiles [0, n_full) lie strictly below the causal diagonal (every
    element visible — no in-tile mask needed); tiles [n_full, n_needed)
    straddle the diagonal (in-tile mask); tiles [n_needed, nkv) are fully
    masked and are never computed, fetched, or accumulated (DESIGN.md §3).
    """
    row0 = iq * blk_q
    n_full = jnp.minimum((row0 + 1) // blk_kv, nkv)
    n_needed = jnp.minimum((row0 + blk_q - 1) // blk_kv + 1, nkv)
    return n_full, n_needed


# ---------------------------------------------------------------------------
# int8 symmetric-absmax quantization (DESIGN.md §5)
# ---------------------------------------------------------------------------

Q8_LEVELS = 127.0


def quantize_q8(x, axes):
    """Symmetric absmax int8 quantization of ``x`` over ``axes``.

    Returns ``(values int8, scales fp32)``; the scales drop the reduced
    axes (one fp32 scalar per quantization group). All-zero groups get
    scale 0 and quantize to 0 — ``dequantize_q8`` round-trips them to
    exact zeros.
    """
    xf = x.astype(jnp.float32)
    scales = jnp.max(jnp.abs(xf), axis=axes) / Q8_LEVELS
    denom = jnp.where(scales == 0.0, 1.0, scales)
    q = jnp.clip(
        jnp.round(xf / jnp.expand_dims(denom, axes)),
        -Q8_LEVELS, Q8_LEVELS,
    ).astype(jnp.int8)
    return q, scales


def dequantize_q8(values, scales, axes):
    """Inverse of ``quantize_q8`` (up to the rounding error)."""
    return values.astype(jnp.float32) * jnp.expand_dims(scales, axes)


def three_band_select(s, q0, col0, kv_len, *, rows_per_pos: int = 1):
    """Fused straddling-band select for one paged score tile.

    ``s`` is a (blk_q, blk_kv) score tile whose row ``i`` sits at
    absolute query position ``q0 + i // rows_per_pos`` (grouped query
    heads share one position when ``rows_per_pos`` is the GQA group)
    and whose first column sits at absolute kv position ``col0``; ``q0``
    and ``kv_len`` may be traced scalars. Applies the DESIGN.md §3
    diagonal + kv-tail mask in ONE select: callers gate it behind the
    ``j >= n_full`` band test so fully-visible pages never pay it.
    """
    blk_q, blk_kv = s.shape
    rows = jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_kv), 0) // rows_per_pos + q0
    cols = jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_kv), 1) + col0
    keep = jnp.logical_and(cols <= rows, cols < kv_len)
    return jnp.where(keep, s, NEG_INF)


def mask_kv_tail(s, col0, kv_len):
    """Mask score columns whose absolute kv position is >= ``kv_len``.

    ``s`` is a (..., rows, blk_kv) score tile whose first column sits at
    absolute kv position ``col0``; positions past the live cache length
    are forced to NEG_INF so they contribute exp(.) == 0 downstream.
    """
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1) + col0
    return jnp.where(cols < kv_len, s, NEG_INF)
