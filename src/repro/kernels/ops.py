"""jit'd public wrappers around the Pallas kernels.

Handles layout flattening (B, H, N, E) -> (B*H, N, E), GQA grouping,
padding to block multiples (masked via static kv_len), interpret-mode
defaulting on CPU, and method dispatch through the §4.3 policy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.policy import (
    DEFAULT_VMEM_BUDGET,
    TilingConfig,
    choose_attention_method,
    sublane_rows,
)
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_flat
from repro.kernels.flash_attention import flash_attention_flat
from repro.kernels.mas_attention import mas_attention_flat
from repro.kernels.paged_decode_attention import (
    decode_pages_per_block,
    paged_decode_attention_flat,
)
from repro.kernels.paged_prefill_attention import paged_prefill_attention_flat
from repro.kernels.paged_verify_attention import paged_verify_attention_flat


def _default_interpret(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _sublane_multiple(dtype) -> int:
    return sublane_rows(jnp.dtype(dtype).itemsize)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "sm_scale", "method", "blk_q", "blk_kv",
        "kv_resident", "interpret", "vmem_budget",
    ),
)
def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
    sm_scale: float | None = None,
    method: str = "auto",  # auto | mas | mas_resident | mas_streamed | flash | ref
    blk_q: int = 128,
    blk_kv: int = 512,
    kv_resident: bool | None = None,
    interpret: bool | None = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> jax.Array:
    """Exact attention. q: (B, Hq, Nq, E); k, v: (B, Hkv, Nkv, E)."""
    if method == "ref":
        return ref.attention(
            q, k, v, causal=causal, window=window, sm_scale=sm_scale
        )
    b, hq, nq, e = q.shape
    _, hkv, nkv, _ = k.shape
    interp = _default_interpret(interpret)

    # Resolve method through the policy (§4.3 analogue).
    if method == "auto" or method == "mas":
        decision = choose_attention_method(
            n_kv=nkv, e=e, itemsize=q.dtype.itemsize,
            tiling=TilingConfig(blk_q, blk_kv, True),
            vmem_budget=vmem_budget,
            prefer="mas" if method == "mas" else "auto",
            causal=causal,
        )
        method = decision.method
        blk_q, blk_kv = decision.tiling.blk_q, decision.tiling.blk_kv
        if kv_resident is None:
            kv_resident = decision.tiling.kv_resident
    elif method == "mas_resident":
        method, kv_resident = "mas_resident", True
    elif method == "mas_streamed":
        method, kv_resident = "mas_streamed", False

    if window is not None and method.startswith("mas"):
        # Sliding window needs per-block skip bookkeeping the paper's
        # dataflow doesn't define; served by the flash kernel.
        method = "flash"

    # Pad to aligned blocks; padded KV masked via static kv_len.
    sub = _sublane_multiple(q.dtype)
    blk_q = -(-min(blk_q, nq) // sub) * sub  # round up to sublane multiple
    blk_kv = -(-min(blk_kv, nkv) // 128) * 128  # round up to lane multiple
    qf = q.reshape(b * hq, nq, e)
    kf = k.reshape(b * hkv, nkv, e)
    vf = v.reshape(b * hkv, nkv, e)
    qf = _pad_to(qf, 1, blk_q)
    kf = _pad_to(kf, 1, blk_kv)
    vf = _pad_to(vf, 1, blk_kv)
    kv_len = nkv if kf.shape[1] != nkv else None

    common = dict(
        blk_q=blk_q, blk_kv=blk_kv, causal=causal, sm_scale=sm_scale,
        kv_len=kv_len, interpret=interp,
    )
    if method in ("mas_resident", "mas_streamed"):
        of = mas_attention_flat(
            qf, kf, vf, kv_resident=(method == "mas_resident"),
            vmem_limit_bytes=vmem_budget, **common
        )
    elif method == "flash":
        of = flash_attention_flat(qf, kf, vf, window=window, **common)
    else:
        raise ValueError(f"unknown method {method!r}")
    return of[:, :nq].reshape(b, hq, nq, e)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "blk_kv", "interpret")
)
def decode_attention(
    q: jax.Array,  # (B, Hq, E)
    k_cache: jax.Array,  # (B, Hkv, S, E) — compute dtype, or int8
    v_cache: jax.Array,  # (B, Hkv, S, E)
    kv_len: jax.Array | int,
    *,
    sm_scale: float | None = None,
    blk_kv: int = 512,
    k_scale: jax.Array | None = None,  # (B, Hkv, S) fp32 per-row scales
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-token decode against a (partially filled) KV cache."""
    b, hq, e = q.shape
    _, hkv, s_len, _ = k_cache.shape
    assert hq % hkv == 0
    group = hq // hkv
    interp = _default_interpret(interpret)

    sub = _sublane_multiple(q.dtype)
    g_pad = max(group, sub)
    # (B, Hkv, G, E): query heads grouped under their kv head.
    qg = q.reshape(b, hkv, group, e)
    qg = _pad_to(qg, 2, g_pad).reshape(b * hkv, g_pad, e)
    kf = k_cache.reshape(b * hkv, s_len, e)
    vf = v_cache.reshape(b * hkv, s_len, e)
    # The K/V tile's sublane dim is blk rows of the *cache* dtype: int8
    # needs 32-row multiples (handled by the 128 lane round-up below).
    blk = -(-min(blk_kv, s_len) // 128) * 128
    kf = _pad_to(kf, 1, blk)
    vf = _pad_to(vf, 1, blk)
    ks = vs = None
    if k_scale is not None:
        ks = _pad_to(k_scale.reshape(b * hkv, s_len), 1, blk)
        vs = _pad_to(v_scale.reshape(b * hkv, s_len), 1, blk)

    of = decode_attention_flat(
        qg, kf, vf, kv_len, blk_kv=blk, sm_scale=sm_scale,
        k_scale=ks, v_scale=vs, interpret=interp,
    )
    return of[:, :group].reshape(b, hq, e)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "interpret", "v_dim"))
def paged_decode_attention(
    q: jax.Array,           # (B, Hq, E)
    k_pages: jax.Array,     # (Hkv, P, page, E) — global page pool
    v_pages: jax.Array | None,  # (Hkv, P, page, E); None in latent mode
    page_table: jax.Array,  # (B, max_pages) int32
    kv_lens: jax.Array,     # (B,) int32
    *,
    sm_scale: float | None = None,
    k_scales: jax.Array | None = None,  # (Hkv, P) fp32 per-page scales
    v_scales: jax.Array | None = None,
    interpret: bool | None = None,
    v_dim: int | None = None,
) -> jax.Array:
    """Single-token decode against a block-table paged KV cache.

    Latent mode (``v_dim`` given, ``v_pages`` None; DESIGN.md §12): V is
    the first ``v_dim`` columns of each K row, so each live page is copied
    once, and the output is (B, Hq, v_dim)."""
    b, hq, e = q.shape
    hkv, _, page_size, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    interp = _default_interpret(interpret)

    if not interp:
        # Page rows are the K/V block's sublane dim: the tile constraint
        # follows the *pool* dtype (int8 -> 32). Interpret mode has no
        # tiling, so small CPU test pages stay allowed.
        sub_kv = _sublane_multiple(k_pages.dtype)
        assert page_size % sub_kv == 0, (
            f"page_size {page_size} must be a multiple of the {sub_kv}-row "
            f"sublane tile for {k_pages.dtype}"
        )
    g_pad = max(group, _sublane_multiple(q.dtype))
    qg = _pad_to(q.reshape(b, hkv, group, e), 2, g_pad)

    of = paged_decode_attention_flat(
        qg, k_pages, v_pages, page_table, kv_lens,
        pages_per_block=decode_pages_per_block(
            hkv, page_size, e, k_pages.dtype.itemsize, page_table.shape[1]),
        sm_scale=sm_scale, k_scales=k_scales, v_scales=v_scales,
        interpret=interp, v_dim=v_dim,
    )
    return of[:, :, :group].reshape(b, hq, of.shape[-1])


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def paged_verify_attention(
    q: jax.Array,           # (B, k, Hq, E) — k speculative positions/slot
    k_pages: jax.Array,     # (Hkv, P, page, E) — global page pool
    v_pages: jax.Array,     # (Hkv, P, page, E)
    page_table: jax.Array,  # (B, max_pages) int32
    kv_lens: jax.Array,     # (B,) int32 — INCL. the written candidate rows
    q_starts: jax.Array,    # (B,) int32 — position of candidate row 0
    *,
    sm_scale: float | None = None,
    k_scales: jax.Array | None = None,  # (Hkv, P) fp32 per-page scales
    v_scales: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """k-token speculative verify against a block-table paged KV cache.

    The candidate K/V rows per slot must already be written to the pool
    (the model layer writes before it attends, DESIGN.md §9); position
    i of slot b sits at absolute position ``q_starts[b] + i``, and rows
    at or past ``kv_lens[b]`` (slots verifying fewer than k rows) come
    back as full-context garbage the host discards. Returns
    (B, k, Hq, E) attention outputs for every candidate position.
    """
    b, spec, hq, e = q.shape
    hkv, _, page_size, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    interp = _default_interpret(interpret)

    if not interp:
        sub_kv = _sublane_multiple(k_pages.dtype)
        assert page_size % sub_kv == 0, (
            f"page_size {page_size} must be a multiple of the {sub_kv}-row "
            f"sublane tile for {k_pages.dtype}"
        )
    # Position-major (k*G, E) Q rows: row i = query head i % G of
    # speculative position i // G. Padding the group (not the whole
    # block) keeps every pad row mapped to a valid position, so the
    # in-kernel three-band mask needs no pad special-case.
    g_pad = max(group, _sublane_multiple(q.dtype))
    qg = q.reshape(b, spec, hkv, group, e).transpose(0, 2, 1, 3, 4)
    qg = _pad_to(qg, 3, g_pad).reshape(b, hkv, spec * g_pad, e)

    of = paged_verify_attention_flat(
        qg, k_pages, v_pages, page_table, kv_lens, q_starts, spec=spec,
        sm_scale=sm_scale, k_scales=k_scales, v_scales=v_scales,
        interpret=interp,
    )
    of = of.reshape(b, hkv, spec, g_pad, e)[:, :, :, :group]
    return of.transpose(0, 2, 1, 3, 4).reshape(b, spec, hq, e)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def paged_prefill_attention(
    q: jax.Array,           # (Hq, chunk, E) — one sequence's prompt chunk
    k_pages: jax.Array,     # (Hkv, P, page, E) — global page pool
    v_pages: jax.Array,     # (Hkv, P, page, E)
    page_table: jax.Array,  # (max_pages,) int32
    q_offset: jax.Array,    # () int32 absolute position of chunk row 0
    kv_len: jax.Array,      # () int32 visible context length
    *,
    sm_scale: float | None = None,
    k_scales: jax.Array | None = None,  # (Hkv, P) fp32 per-page scales
    v_scales: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """One prompt chunk attending to all prior context in a paged cache.

    The chunk's own K/V must already be written to its pages (the model
    layer writes before it attends, DESIGN.md §6). Pad rows past
    ``kv_len - q_offset`` return garbage the caller slices off.
    """
    hq, chunk, e = q.shape
    hkv, _, page_size, _ = k_pages.shape
    assert hq % hkv == 0
    interp = _default_interpret(interpret)

    if not interp:
        sub_kv = _sublane_multiple(k_pages.dtype)
        assert page_size % sub_kv == 0, (
            f"page_size {page_size} must be a multiple of the {sub_kv}-row "
            f"sublane tile for {k_pages.dtype}"
        )
    qf = _pad_to(q, 1, _sublane_multiple(q.dtype))

    of = paged_prefill_attention_flat(
        qf, k_pages, v_pages, page_table, q_offset, kv_len,
        sm_scale=sm_scale, k_scales=k_scales, v_scales=v_scales,
        interpret=interp,
    )
    return of[:, :chunk]
