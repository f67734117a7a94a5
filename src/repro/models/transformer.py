"""The composable LM: dense / GQA / MoE / hybrid / SSM / enc-dec stacks.

Layers are organized as ``num_units`` repetitions of a ``unit_pattern``
(e.g. ("rec","rec","attn") for RecurrentGemma) plus a short tail, so the
whole decoder lowers as ONE ``lax.scan`` over stacked unit parameters —
compile time stays flat in depth, which the 512-device dry-run depends on.

Pure functional: ``init(rng, cfg) -> params`` and explicit forward
functions. Caches are pytrees with the same unit structure.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.common import quantize_q8
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models.common import (
    ArchConfig,
    apply_rope,
    dense_init,
    rms_norm,
    sinusoidal_positions,
    split_keys,
)
from repro.models.moe import init_moe, init_routed, moe_ffn, routed_experts
from repro.models.rglru import init_rglru_block, rglru_block
from repro.models.ssm import init_ssd_block, ssd_block


# ---------------------------------------------------------------------------
# pattern bookkeeping
# ---------------------------------------------------------------------------


def unit_layout(cfg: ArchConfig) -> tuple[tuple[str, ...], int, tuple[str, ...]]:
    """(unit_pattern, num_units, tail)."""
    if cfg.family == "ssm":
        pattern: tuple[str, ...] = ("ssd",)
    elif cfg.mla is not None:
        pattern = cfg.layer_kinds[-1:]
    elif cfg.block_pattern is not None:
        pattern = cfg.block_pattern
    else:
        pattern = ("attn",)
    tail = cfg.pattern_tail
    body = cfg.num_layers - len(tail) - len(lead_kinds(cfg))
    assert body % len(pattern) == 0, (cfg.name, body, pattern)
    return pattern, body // len(pattern), tail


def lead_kinds(cfg: ArchConfig) -> tuple[str, ...]:
    """Kinds of the layers before the scanned units: a latent-attention
    stack's leading dense layers (``params["lead"]``, stacked)."""
    return cfg.layer_kinds[:cfg.dense_lead] if cfg.mla is not None else ()


def _stacks(cfg: ArchConfig) -> tuple[tuple[str, str], ...]:
    """(tree key, kind) of the stacked layer groups, in order: the lead
    layers, then the units (one-block pattern only)."""
    lead = lead_kinds(cfg)
    pattern, _, _ = unit_layout(cfg)
    return ((("lead", lead[0]),) if lead else ()) + (("units", pattern[0]),)


def _layer(tree, i):
    return jax.tree.map(lambda t: t[i], tree)


# ---------------------------------------------------------------------------
# block init / apply
# ---------------------------------------------------------------------------


def init_attn(key, cfg: ArchConfig, cross: bool = False):
    d, e = cfg.d_model, cfg.hd
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    ks = split_keys(key, ["q", "k", "v", "o"])
    p = {
        "norm": jnp.zeros((d,), cfg.param_dtype),
        "wq": dense_init(ks["q"], (d, hq * e), dtype=cfg.param_dtype),
        "wk": dense_init(ks["k"], (d, hkv * e), dtype=cfg.param_dtype),
        "wv": dense_init(ks["v"], (d, hkv * e), dtype=cfg.param_dtype),
        "wo": dense_init(ks["o"], (hq * e, d), dtype=cfg.param_dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((e,), cfg.param_dtype)
        p["k_norm"] = jnp.zeros((e,), cfg.param_dtype)
    return p


def init_mlp(key, cfg: ArchConfig):
    d, f = cfg.d_model, cfg.d_ff
    ks = split_keys(key, ["gate", "up", "down"])
    p = {
        "norm": jnp.zeros((d,), cfg.param_dtype),
        "w_up": dense_init(ks["up"], (d, f), dtype=cfg.param_dtype),
        "w_down": dense_init(ks["down"], (f, d), dtype=cfg.param_dtype),
    }
    if cfg.mlp == "swiglu":
        p["w_gate"] = dense_init(ks["gate"], (d, f), dtype=cfg.param_dtype)
    return p


def mlp(params, x, cfg: ArchConfig):
    from repro.distributed import ctx

    dt = x.dtype
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    up = h @ params["w_up"].astype(dt)
    if cfg.mlp == "swiglu":
        up = up * jax.nn.silu(h @ params["w_gate"].astype(dt))
    else:
        up = jax.nn.gelu(up)
    # §Perf iter 4: keep the (B, S, F) intermediate sequence-sharded so
    # XLA gathers the (smaller) weights instead of the activations and
    # the down-projection needs no cross-shard reduction.
    up = ctx.seq_sharded_activations(up)
    return up @ params["w_down"].astype(dt)


def _split_heads(x, n_heads, e):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, e).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, e = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * e)


def _qkv(params, x, cfg, positions, *, rope=True):
    dt = x.dtype
    e = cfg.hd
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    q = _split_heads(h @ params["wq"].astype(dt), cfg.num_heads, e)
    k = _split_heads(h @ params["wk"].astype(dt), cfg.num_kv_heads, e)
    v = _split_heads(h @ params["wv"].astype(dt), cfg.num_kv_heads, e)
    if cfg.qk_norm and "q_norm" in params:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if rope and cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_block(params, x, cfg: ArchConfig, *, positions, window=None,
               causal=True):
    """Full-sequence self-attention (train / encoder / prefill-compute)."""
    q, k, v = _qkv(params, x, cfg, positions)
    o = attn_mod.attention(
        q, k, v, impl=cfg.attn_impl, causal=causal, window=window,
        chunk=cfg.attn_chunk, remat=cfg.remat,
    )
    return _merge_heads(o) @ params["wo"].astype(x.dtype), (k, v)


def attn_decode(params, x, cfg: ArchConfig, *, cache_k, cache_v, pos,
                window=None, k_scale=None, v_scale=None):
    """One-token self-attention against a (ring) cache.

    x: (B, 1, D); cache_[kv]: (B, Hkv, C, E); pos: scalar absolute
    position. An int8 cache carries per-row (B, Hkv, C) fp32
    ``k_scale``/``v_scale``: the new token's row is quantized with its
    own absmax scale at append time (rows are written once, so no
    requantization is ever needed on this layout). Returns
    (out, cache updates dict).
    """
    c = cache_k.shape[2]
    q, k, v = _qkv(params, x, cfg, positions=pos + jnp.zeros((1,), jnp.int32))
    slot = pos % c if window is not None else pos
    quantized = cache_k.dtype == jnp.int8
    if quantized:
        k, ks = quantize_q8(k, -1)
        v, vs = quantize_q8(v, -1)
        k_scale = jax.lax.dynamic_update_slice_in_dim(k_scale, ks, slot,
                                                      axis=2)
        v_scale = jax.lax.dynamic_update_slice_in_dim(v_scale, vs, slot,
                                                      axis=2)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot, axis=2)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot, axis=2)
    kv_len = jnp.minimum(pos + 1, c)
    o = attn_mod.decode_attention(
        q[:, :, 0], cache_k, cache_v, kv_len,
        impl="pallas" if cfg.attn_impl == "pallas" else "xla",
        k_scale=k_scale, v_scale=v_scale,
    )
    updates = {"k": cache_k, "v": cache_v}
    if quantized:
        updates.update(k_scale=k_scale, v_scale=v_scale)
    return (o.reshape(x.shape[0], 1, -1) @ params["wo"].astype(x.dtype),
            updates)


def _paged_append_requant(pages, scales, page_ids, slots, row):
    """Append one quantized token row per sequence (DESIGN.md §5).

    pages: (Hkv, P, page, E) int8; scales: (Hkv, P) fp32; page_ids /
    slots: (B,); row: (Hkv, B, E) at compute precision. The touched
    page's *live* rows ([0, slot)) are dequantized, the new row is
    inserted, and the page is requantized under a fresh symmetric
    absmax — so the per-page scale always reflects exactly the rows
    written so far. Stale rows (>= slot: reused pages keep their old
    bytes until overwritten) are masked out of both the absmax and the
    rewrite, which is what makes freed-page reuse safe without any
    scale reset. While the scale is unchanged the dequant/requant
    round-trip is exact (round(v*s/s) == v), so old rows only pay one
    rounding error per scale growth.
    """
    hkv, _, page, e = pages.shape
    bsz = page_ids.shape[0]
    sc = scales[:, page_ids]                                   # (Hkv, B)
    pg = pages[:, page_ids].astype(jnp.float32) * sc[:, :, None, None]
    live = jnp.arange(page)[None, :] < slots[:, None]          # (B, page)
    pg = jnp.where(live[None, :, :, None], pg, 0.0)
    pg = pg.at[:, jnp.arange(bsz), slots].set(row.astype(jnp.float32))
    q, new_sc = quantize_q8(pg, (-2, -1))
    return pages.at[:, page_ids].set(q), scales.at[:, page_ids].set(new_sc)


def _paged_append_n(pages, scales, table, positions, rows, n_valid, *, spec):
    """Append up to ``spec`` candidate rows per sequence in ONE pass (§9).

    pages: (Hkv, P, page, E); scales: (Hkv, P) fp32 or None (fp32 pool);
    table: (B, max_pages); positions: (B,) absolute position of each
    sequence's FIRST candidate row; rows: (Hkv, B, k, E) at compute
    precision; n_valid: (B,) rows actually landing per sequence (slots
    near their token budget, or idle with 0, verify fewer than k — the
    surplus candidate rows are zeroed out of the write so they touch no
    page past the allocation point). The valid window may straddle a
    page boundary, so the touched span (at most ``t_max`` pages, all
    pre-allocated by the engine's ``ensure_capacity``) is gathered
    whole, the candidates inserted at their in-window offsets, and —
    for int8 pools — every touched page requantized under ONE fresh
    symmetric absmax: the §5 requant invariant (live rows only; stale
    bytes masked out of absmax and rewrite) generalized from one row to
    k. Inactive window slots (a window shorter than t_max pages) park
    on the pool's reserved scratch page 0, whose bytes are never read
    live.
    """
    hkv, _, page, e = pages.shape
    bsz = rows.shape[1]
    t_max = (page - 1 + spec - 1) // page + 1
    p0 = positions // page
    p_last = (positions + n_valid - 1) // page       # -1 when n_valid == 0
    off0 = positions % page
    lp = p0[:, None] + jnp.arange(t_max)[None, :]          # (B, t_max)
    active = lp <= p_last[:, None]
    ids = jnp.where(
        active,
        jnp.take_along_axis(table,
                            jnp.clip(lp, 0, table.shape[1] - 1), axis=1),
        0,
    )
    quantized = scales is not None
    win = pages[:, ids].astype(jnp.float32)          # (Hkv, B, t_max, pg, E)
    if quantized:
        win = win * scales[:, ids][..., None, None]
    win = win.reshape(hkv, bsz, t_max * page, e)
    flat = jnp.arange(t_max * page)[None, :]
    live = flat < off0[:, None]                      # pre-window live rows
    win = jnp.where(live[None, :, :, None], win, 0.0)
    idx = off0[:, None] + jnp.arange(spec)[None, :]  # (B, k) window offsets
    win = win.at[:, jnp.arange(bsz)[:, None], idx].set(
        rows.astype(jnp.float32))
    keep = flat < (off0 + n_valid)[:, None]          # drop surplus rows
    win = jnp.where(keep[None, :, :, None], win, 0.0)
    win = win.reshape(hkv, bsz, t_max, page, e)
    if not quantized:
        return pages.at[:, ids].set(win.astype(pages.dtype)), None
    qv, new_sc = quantize_q8(win, (-2, -1))
    return pages.at[:, ids].set(qv), scales.at[:, ids].set(new_sc)


def attn_paged_decode(params, x, cfg: ArchConfig, *, k_pages, v_pages,
                      page_table, positions, k_scales=None, v_scales=None):
    """One-token self-attention against a paged (block-table) cache.

    x: (B, 1, D); pools: (Hkv, P, page, E); page_table: (B, max_pages);
    positions: (B,) per-sequence absolute positions — unlike the dense
    path there is no shared scalar `pos`, which is what lets the
    continuous-batching engine decode sequences of different ages in
    one batch. Int8 pools carry per-page (Hkv, P) fp32 scale tables and
    append through ``_paged_append_requant``. Returns
    (out, pool updates dict).
    """
    b = x.shape[0]
    page = k_pages.shape[2]
    q, k, v = _qkv(params, x, cfg, positions=positions[:, None, None])
    page_ids = page_table[jnp.arange(b), positions // page]
    slots = positions % page
    k_row = k[:, :, 0].transpose(1, 0, 2)   # (Hkv, B, E)
    v_row = v[:, :, 0].transpose(1, 0, 2)
    quantized = k_pages.dtype == jnp.int8
    if quantized:
        k_pages, k_scales = _paged_append_requant(k_pages, k_scales,
                                                  page_ids, slots, k_row)
        v_pages, v_scales = _paged_append_requant(v_pages, v_scales,
                                                  page_ids, slots, v_row)
    else:
        k_pages = k_pages.at[:, page_ids, slots].set(k_row)
        v_pages = v_pages.at[:, page_ids, slots].set(v_row)
    o = attn_mod.paged_decode_attention(
        q[:, :, 0], k_pages, v_pages, page_table, positions + 1,
        impl="pallas" if cfg.attn_impl == "pallas" else "xla",
        k_scales=k_scales, v_scales=v_scales,
    )
    updates = {"k": k_pages, "v": v_pages}
    if quantized:
        updates.update(k_scale=k_scales, v_scale=v_scales)
    return (o.reshape(b, 1, -1) @ params["wo"].astype(x.dtype),
            updates)


def attn_paged_verify(params, x, cfg: ArchConfig, *, k_pages, v_pages,
                      page_table, positions, n_rows, k_scales=None,
                      v_scales=None):
    """k-token speculative-verify self-attention on a paged cache (§9).

    x: (B, k, D) — the last emitted token plus up to k-1 drafted ones
    per slot, rows at absolute positions ``positions[b] + i``; pools:
    (Hkv, P, page, E); page_table: (B, max_pages); n_rows: (B,) valid
    candidate rows per slot (< k for slots near their token budget; 0
    for idle slots). The valid candidate K/V rows are written first
    (one batched, requant-safe pass — the pages were pre-allocated by
    the scheduler), then the k-row Q block attends through the
    page-table gather with ``kv_len = positions + n_rows``; Q rows past
    ``n_rows`` return garbage the engine discards. Rows of rejected
    candidates stay in the pool as stale bytes: future kv_lens stop
    before them and the §5 requant live-masks skip them, exactly like
    reused-page garbage. Returns (out (B, k, D), pool updates dict).
    """
    b, k = x.shape[0], x.shape[1]
    pos_bk = positions[:, None] + jnp.arange(k)[None, :]
    q, kk, vv = _qkv(params, x, cfg, positions=pos_bk[:, None, :])
    k_rows = kk.transpose(1, 0, 2, 3)   # (Hkv, B, k, E)
    v_rows = vv.transpose(1, 0, 2, 3)
    quantized = k_pages.dtype == jnp.int8
    k_pages, k_scales = _paged_append_n(k_pages, k_scales, page_table,
                                        positions, k_rows, n_rows, spec=k)
    v_pages, v_scales = _paged_append_n(v_pages, v_scales, page_table,
                                        positions, v_rows, n_rows, spec=k)
    o = attn_mod.paged_verify_attention(
        q.transpose(0, 2, 1, 3), k_pages, v_pages, page_table,
        positions + n_rows, positions,
        impl="pallas" if cfg.attn_impl == "pallas" else "xla",
        k_scales=k_scales, v_scales=v_scales,
    )
    updates = {"k": k_pages, "v": v_pages}
    if quantized:
        updates.update(k_scale=k_scales, v_scale=v_scales)
    return (o.reshape(b, k, -1) @ params["wo"].astype(x.dtype), updates)


def attn_paged_prefill(params, x, cfg: ArchConfig, *, k_pages, v_pages,
                       page_table, chunk_page_ids, q_offset, kv_len,
                       k_scales=None, v_scales=None):
    """One prompt chunk of self-attention against a paged cache (§6).

    x: (1, chunk, D) — one sequence's chunk, rows at absolute positions
    ``q_offset + i``; pools: (Hkv, P, page, E); page_table: (max_pages,)
    for THE sequence; chunk_page_ids: (chunk // page,) physical pages of
    the chunk's span (entries past the allocation point at the scratch
    page); ``kv_len`` = q_offset + live rows (ragged last chunks pad).

    The chunk's K/V rows are written into their pages FIRST — rows past
    ``kv_len`` zeroed, so the ragged tail matches the zero-initialized
    dense cache of the monolithic path and never enters a per-page
    absmax — then the chunk's Q attends through the page-table gather,
    which sees prior context and the chunk's own keys alike. Whole
    pages are quantized at write time exactly like ``write_prefill_pages``
    (the §5 per-page invariant: a reused physical page is overwritten
    values-and-scale together, so no scale reset is ever needed).
    Returns (out, pool updates dict).
    """
    chunk = x.shape[1]
    hkv, _, page, e = k_pages.shape
    positions = q_offset + jnp.arange(chunk)
    q, k, v = _qkv(params, x, cfg, positions=positions)
    live = (positions < kv_len)[None, :, None]
    n_cp = chunk // page
    quantized = k_pages.dtype == jnp.int8

    def write(pages, scales, rows):
        ch = jnp.where(live, rows, 0).reshape(hkv, n_cp, page, e)
        if quantized:
            qv, sc = quantize_q8(ch, (-2, -1))
            return (pages.at[:, chunk_page_ids].set(qv),
                    scales.at[:, chunk_page_ids].set(sc))
        return pages.at[:, chunk_page_ids].set(ch.astype(pages.dtype)), None

    k_pages, k_scales_new = write(k_pages, k_scales, k[0])
    v_pages, v_scales_new = write(v_pages, v_scales, v[0])
    if quantized:
        k_scales, v_scales = k_scales_new, v_scales_new
    o = attn_mod.paged_prefill_attention(
        q[0], k_pages, v_pages, page_table, q_offset, kv_len,
        impl="pallas" if cfg.attn_impl == "pallas" else "xla",
        k_scales=k_scales, v_scales=v_scales,
    )
    updates = {"k": k_pages, "v": v_pages}
    if quantized:
        updates.update(k_scale=k_scales, v_scale=v_scales)
    return (_merge_heads(o[None]) @ params["wo"].astype(x.dtype), updates)


def cross_attn_block(params, x, cfg: ArchConfig, *, mem_k, mem_v):
    """Decoder cross-attention against precomputed encoder K/V."""
    dt = x.dtype
    e = cfg.hd
    h = rms_norm(x, params["norm"], cfg.norm_eps)
    q = _split_heads(h @ params["wq"].astype(dt), cfg.num_heads, e)
    o = attn_mod.attention(q, mem_k, mem_v, impl=cfg.attn_impl, causal=False,
                           chunk=cfg.attn_chunk, remat=cfg.remat)
    return _merge_heads(o) @ params["wo"].astype(dt)


# ---------------------------------------------------------------------------
# decoder block (kind dispatch)
# ---------------------------------------------------------------------------


def init_block(key, kind: str, cfg: ArchConfig, *, with_cross=False):
    ks = split_keys(key, ["main", "ffn", "cross"])
    if kind == "ssd":
        return {"ssd": init_ssd_block(ks["main"], cfg)}
    if kind == "rec":
        return {"rec": init_rglru_block(ks["main"], cfg),
                "ffn": init_mlp(ks["ffn"], cfg)}
    if kind in ("mla", "mla_moe"):
        return {"attn": mla_mod.init_mla(ks["main"], cfg),
                "ffn": (init_routed if kind == "mla_moe" else init_mlp)(
                    ks["ffn"], cfg)}
    assert kind == "attn"
    p = {"attn": init_attn(ks["main"], cfg)}
    if with_cross:
        p["cross"] = init_attn(ks["cross"], cfg, cross=True)
    if cfg.moe is not None:
        p["ffn"] = init_moe(ks["ffn"], cfg)
    else:
        p["ffn"] = init_mlp(ks["ffn"], cfg)
    return p


def make_cache_block(kind: str, cfg: ArchConfig, batch: int, max_len: int,
                     dtype, *, with_cross=False, mem_len: int = 0,
                     kv_dtype=None):
    """Zero-initialized cache pytree for one block.

    ``kv_dtype=jnp.int8`` stores the self-attention K/V quantized with
    per-row fp32 scale side-tables (DESIGN.md §5); cross-attention
    memories stay at the compute dtype (written once, read every step).
    """
    e = cfg.hd
    if kind in ("mla", "mla_moe"):
        if kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8:
            raise NotImplementedError("int8 latent cache (MLA)")
        return {"lat": jnp.zeros((batch, max_len, cfg.mla.latent_dim),
                                 dtype)}
    if kind == "attn":
        c = min(max_len, cfg.window) if cfg.window else max_len
        kv_dt = kv_dtype or dtype
        blk: dict[str, Any] = {
            "k": jnp.zeros((batch, cfg.num_kv_heads, c, e), kv_dt),
            "v": jnp.zeros((batch, cfg.num_kv_heads, c, e), kv_dt),
        }
        if jnp.dtype(kv_dt) == jnp.int8:
            zs = jnp.zeros((batch, cfg.num_kv_heads, c), jnp.float32)
            blk["k_scale"] = zs
            blk["v_scale"] = zs
        if with_cross:
            blk["mem_k"] = jnp.zeros((batch, cfg.num_kv_heads, mem_len, e),
                                     dtype)
            blk["mem_v"] = jnp.zeros((batch, cfg.num_kv_heads, mem_len, e),
                                     dtype)
        return blk
    if kind == "rec":
        w = cfg.lru_width or cfg.d_model
        return {"conv": jnp.zeros((batch, 3, w), dtype),
                "rnn": jnp.zeros((batch, w), jnp.float32)}
    assert kind == "ssd"
    s = cfg.ssm
    di = s.expand * cfg.d_model
    nh = di // s.head_dim
    conv_ch = di + 2 * s.n_groups * s.d_state
    return {
        "conv": jnp.zeros((batch, s.conv_width - 1, conv_ch), dtype),
        "state": jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32),
    }


def apply_block_train(params, kind, x, cfg: ArchConfig, positions):
    """Full-sequence forward. Returns (x, aux_loss)."""
    aux = jnp.float32(0.0)
    if kind == "ssd":
        y, _ = ssd_block(params["ssd"], x, cfg)
        return x + y, aux
    if kind == "rec":
        y, _ = rglru_block(params["rec"], x, cfg)
        x = x + y
        return x + mlp(params["ffn"], x, cfg), aux
    if kind in ("mla", "mla_moe"):
        y, _ = mla_mod.mla_block(params["attn"], x, cfg, positions=positions)
        x = x + y
        return x + _mla_ffn(params["ffn"], kind, x, cfg)[0], aux
    window = cfg.window if cfg.block_pattern is not None else None
    y, _ = attn_block(params["attn"], x, cfg, positions=positions,
                      window=window, causal=cfg.causal)
    x = x + y
    if "cross" in params:
        raise ValueError("cross-attn blocks go through apply_block_decoder")
    if cfg.moe is not None:
        y, aux = moe_ffn(params["ffn"], x, cfg)
    else:
        y = mlp(params["ffn"], x, cfg)
    return x + y, aux


def _mla_ffn(params, kind, x, cfg: ArchConfig):
    """The MLP half of a latent-attention layer -> (y, expert rows or
    None): a dense SwiGLU, or the dropless held-expert layer."""
    if kind == "mla_moe":
        return routed_experts(params, x, cfg)
    return mlp(params, x, cfg), None


def apply_block_decode(params, kind, x, cfg: ArchConfig, cache, pos):
    """One-token step. Returns (x, new_cache_block)."""
    if kind in ("mla", "mla_moe"):
        y, lat = mla_mod.mla_decode(params["attn"], x, cfg,
                                    cache_lat=cache["lat"], pos=pos)
        x = x + y
        return x + _mla_ffn(params["ffn"], kind, x, cfg)[0], {"lat": lat}
    if kind == "ssd":
        y, (conv, state) = ssd_block(
            params["ssd"], x, cfg, conv_state=cache["conv"],
            ssm_state=cache["state"], streaming=True,
        )
        return x + y, {"conv": conv, "state": state}
    if kind == "rec":
        y, (conv, rnn) = rglru_block(
            params["rec"], x, cfg, conv_state=cache["conv"],
            rnn_state=cache["rnn"], streaming=True,
        )
        x = x + y
        return x + mlp(params["ffn"], x, cfg), {"conv": conv, "rnn": rnn}
    window = cfg.window if cfg.block_pattern is not None else None
    y, kv_updates = attn_decode(params["attn"], x, cfg, cache_k=cache["k"],
                                cache_v=cache["v"], pos=pos, window=window,
                                k_scale=cache.get("k_scale"),
                                v_scale=cache.get("v_scale"))
    x = x + y
    new_cache = dict(cache, **kv_updates)
    if "cross" in params:
        x = x + cross_attn_block(params["cross"], x, cfg,
                                 mem_k=cache["mem_k"], mem_v=cache["mem_v"])
    if cfg.moe is not None:
        y, _ = moe_ffn(params["ffn"], x, cfg)
    else:
        y = mlp(params["ffn"], x, cfg)
    return x + y, new_cache


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def init(rng, cfg: ArchConfig):
    pattern, num_units, tail = unit_layout(cfg)
    ks = split_keys(
        rng, ["embed", "units", "tail", "enc", "cross", "unembed"]
    )
    params: dict[str, Any] = {
        "embed": dense_init(ks["embed"], (cfg.vocab_size, cfg.d_model),
                            in_axis=1, dtype=cfg.param_dtype),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(
            ks["unembed"], (cfg.d_model, cfg.vocab_size),
            dtype=cfg.param_dtype,
        )
    with_cross = cfg.encoder_layers > 0

    def init_unit(key):
        sub = jax.random.split(key, len(pattern))
        return {f"b{j}": init_block(sub[j], kind, cfg, with_cross=with_cross
                                    and kind == "attn")
                for j, kind in enumerate(pattern)}

    unit_keys = jax.random.split(ks["units"], num_units)
    params["units"] = jax.vmap(init_unit)(unit_keys)
    lead = lead_kinds(cfg)
    if lead:  # a latent-attention stack has no tail: its key is free
        params["lead"] = jax.vmap(
            lambda key: {"b0": init_block(key, lead[0], cfg)})(
                jax.random.split(ks["tail"], len(lead)))
    if tail:
        tkeys = jax.random.split(ks["tail"], len(tail))
        params["tail"] = {
            f"t{j}": init_block(tkeys[j], kind, cfg, with_cross=with_cross
                                and kind == "attn")
            for j, kind in enumerate(tail)
        }
    if cfg.encoder_layers:
        ekeys = jax.random.split(ks["enc"], cfg.encoder_layers)

        def init_enc(key):
            s = split_keys(key, ["attn", "ffn"])
            return {"attn": init_attn(s["attn"], cfg),
                    "ffn": init_mlp(s["ffn"], cfg)}

        params["encoder"] = jax.vmap(init_enc)(ekeys)
        params["enc_final_norm"] = jnp.zeros((cfg.d_model,), cfg.param_dtype)
    return params


def _embed(params, tokens, cfg, frontend_embeds=None, positions=None):
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    if not cfg.rope:
        if positions is None:
            positions = jnp.arange(x.shape[1])
        pos = sinusoidal_positions(positions, cfg.d_model)
        x = x + pos[None].astype(x.dtype)
    return x


def _unembed(params, x, cfg):
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"].astype(h.dtype)
        return jnp.einsum("bsd,vd->bsv", h, w)
    return h @ params["unembed"].astype(h.dtype)


def encode(params, frames, cfg: ArchConfig):
    """Encoder stack over precomputed frontend frames (B, F, D)."""
    x = frames.astype(cfg.compute_dtype)
    if not cfg.rope:
        x = x + sinusoidal_positions(
            jnp.arange(x.shape[1]), cfg.d_model
        )[None].astype(x.dtype)
    positions = jnp.arange(x.shape[1])

    def body(x, p):
        y, _ = attn_block(p["attn"], x, cfg, positions=positions,
                          causal=False)
        x = x + y
        return x + mlp(p["ffn"], x, cfg), None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


def forward(params, tokens, cfg: ArchConfig, *, frontend_embeds=None,
            encoder_out=None):
    """Training/prefill-style full-sequence forward -> (logits, aux_loss).

    ``frontend_embeds``: (B, F, D) stub embeddings prepended to the token
    embeddings (VLM). ``encoder_out``: (B, F, D) encoder memory (enc-dec).
    """
    from repro.distributed import ctx

    pattern, num_units, tail = unit_layout(cfg)
    x = _embed(params, tokens, cfg, frontend_embeds)
    x = ctx.seq_sharded_activations(x)  # SP between blocks (§Perf iter 1)
    positions = jnp.arange(x.shape[1])
    aux_total = jnp.float32(0.0)

    mem_kv = None
    if encoder_out is not None:
        mem_kv = encoder_out  # projected per block below

    for i, kind in enumerate(lead_kinds(cfg)):
        x, _ = apply_block_train(_layer(params["lead"]["b0"], i), kind, x,
                                 cfg, positions)

    def unit_body(carry, p_unit):
        x, aux = carry
        for j, kind in enumerate(pattern):
            p = p_unit[f"b{j}"]
            if "cross" in p:
                y, a = _block_with_cross(p, x, cfg, positions, mem_kv)
            else:
                y, a = apply_block_train(p, kind, x, cfg, positions)
            x, aux = y, aux + a
        return (x, aux), None

    o = cfg.outer_scan
    if cfg.remat and o and num_units % o == 0 and num_units // o > 1:
        # §Perf iter 9: two-level scan — checkpoint at the OUTER level so
        # only `o` carries persist; the inner run of units/o layers is
        # recomputed per outer step in the backward.
        inner = num_units // o
        units2 = jax.tree.map(
            lambda t: t.reshape(o, inner, *t.shape[1:]), params["units"]
        )

        def outer_body(carry, p_outer):
            carry, _ = jax.lax.scan(unit_body, carry, p_outer)
            return carry, None

        outer_body = jax.checkpoint(
            outer_body, policy=jax.checkpoint_policies.nothing_saveable
        )
        (x, aux_total), _ = jax.lax.scan(outer_body, (x, aux_total), units2)
    else:
        if cfg.remat:
            unit_body = jax.checkpoint(
                unit_body, policy=jax.checkpoint_policies.nothing_saveable
            )
        (x, aux_total), _ = jax.lax.scan(unit_body, (x, aux_total),
                                         params["units"])
    for j, kind in enumerate(tail):
        x, a = apply_block_train(params["tail"][f"t{j}"], kind, x, cfg,
                                 positions)
        aux_total = aux_total + a
    return _unembed(params, x, cfg), aux_total


def _block_with_cross(p, x, cfg, positions, mem):
    y, _ = attn_block(p["attn"], x, cfg, positions=positions,
                      causal=cfg.causal)
    x = x + y
    dt = x.dtype
    e = cfg.hd
    hm = mem.astype(dt)  # encoder output is already final-normed
    mem_k = _split_heads(hm @ p["cross"]["wk"].astype(dt),
                         cfg.num_kv_heads, e)
    mem_v = _split_heads(hm @ p["cross"]["wv"].astype(dt),
                         cfg.num_kv_heads, e)
    x = x + cross_attn_block(p["cross"], x, cfg, mem_k=mem_k, mem_v=mem_v)
    y = mlp(p["ffn"], x, cfg)
    return x + y, jnp.float32(0.0)


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def make_cache(cfg: ArchConfig, batch: int, max_len: int, *, mem_len=0,
               kv_dtype=None):
    pattern, num_units, tail = unit_layout(cfg)
    with_cross = cfg.encoder_layers > 0

    def one_unit(_):
        return {
            f"b{j}": make_cache_block(
                kind, cfg, batch, max_len, cfg.compute_dtype,
                with_cross=with_cross and kind == "attn", mem_len=mem_len,
                kv_dtype=kv_dtype,
            )
            for j, kind in enumerate(pattern)
        }

    cache: dict[str, Any] = {
        "units": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (num_units,) + x.shape),
            one_unit(0),
        )
    }
    lead = lead_kinds(cfg)
    if lead:
        blk = make_cache_block(lead[0], cfg, batch, max_len,
                               cfg.compute_dtype, kv_dtype=kv_dtype)
        cache["lead"] = {"b0": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (len(lead),) + x.shape), blk)}
    if tail:
        cache["tail"] = {
            f"t{j}": make_cache_block(
                kind, cfg, batch, max_len, cfg.compute_dtype,
                with_cross=with_cross and kind == "attn", mem_len=mem_len,
                kv_dtype=kv_dtype,
            )
            for j, kind in enumerate(tail)
        }
    return cache


def _check_paged_support(cfg: ArchConfig):
    pattern, _, tail = unit_layout(cfg)
    if (pattern not in (("attn",), ("mla",), ("mla_moe",)) or tail
            or cfg.window is not None or cfg.encoder_layers or not cfg.rope):
        raise NotImplementedError(
            "paged cache layout supports pure-attention rope decoder "
            f"stacks only (got {cfg.name})"
        )


def make_paged_cache(cfg: ArchConfig, num_pages: int, page_size: int,
                     kv_dtype=None):
    """Global page pools, one (Hkv, P, page, E) pair per scanned unit.

    The page table is NOT part of this pytree: one table row per
    sequence is shared by every layer (a logical page maps to the same
    physical slot in all pools), so it travels as a decode-step argument
    instead. ``kv_dtype=jnp.int8`` adds the per-page fp32 scales
    side-table (Hkv, P) for K and V (DESIGN.md §5).

    A latent-attention stack holds one latent pool (1, P, page,
    pool_dim) per layer (DESIGN.md §12), the lead layers' under
    ``"lead"``; every leaf keeps the page axis at axis 2.
    """
    _check_paged_support(cfg)
    _, num_units, _ = unit_layout(cfg)
    kv_dt = kv_dtype or cfg.compute_dtype
    if cfg.mla is not None:
        if jnp.dtype(kv_dt) == jnp.int8:
            raise NotImplementedError("int8 latent pages (MLA)")
        z = jnp.zeros((1, num_pages, page_size, cfg.mla.pool_dim), kv_dt)
        return {name: {"b0": {"lat": jnp.broadcast_to(
                    z, (len(lead_kinds(cfg)) if name == "lead"
                        else num_units,) + z.shape)}}
                for name, _ in _stacks(cfg)}
    z = jnp.zeros((cfg.num_kv_heads, num_pages, page_size, cfg.hd), kv_dt)
    blk = {"k": z, "v": z}
    if jnp.dtype(kv_dt) == jnp.int8:
        zs = jnp.zeros((cfg.num_kv_heads, num_pages), jnp.float32)
        blk["k_scale"] = zs
        blk["v_scale"] = zs
    return {"units": jax.tree.map(
        lambda x: jnp.broadcast_to(x, (num_units,) + x.shape),
        {"b0": blk},
    )}


def write_prefill_pages(cfg: ArchConfig, cache, dense_cache, page_ids):
    """Copy-on-admit: scatter a batch-1 prefilled dense cache into pages.

    dense k/v: (U, 1, Hkv, C, E) with C >= len(page_ids) * page_size;
    page_ids: (n_pages,) physical pages allocated to the sequence.
    Positions past the prompt in the last page carry garbage — masked by
    the per-sequence kv_len at attention time. Int8 pools quantize here,
    at admit time: one symmetric absmax per (unit, head, page), written
    into the scales side-table alongside the values (the prompt pages of
    a reused physical page overwrite both, so freed-page scales never
    leak into a new sequence).
    """
    n = page_ids.shape[0]

    def chunked(pages, dense):
        u, h, _, page, e = pages.shape
        return dense[:, 0, :, :n * page].reshape(u, h, n, page, e)

    units = {}
    for key, blk in cache["units"].items():
        dense_blk = dense_cache["units"][key]
        new = dict(blk)
        for which in ("k", "v"):
            chunks = chunked(blk[which], dense_blk[which])
            if blk[which].dtype == jnp.int8:
                qv, sc = quantize_q8(chunks, (-2, -1))
                new[which] = blk[which].at[:, :, page_ids].set(qv)
                new[f"{which}_scale"] = (
                    blk[f"{which}_scale"].at[:, :, page_ids].set(sc)
                )
            else:
                new[which] = blk[which].at[:, :, page_ids].set(chunks)
        units[key] = new
    return dict(cache, units=units)


def _paged_layers(params, cfg: ArchConfig, cache, x, layer):
    """Scan each stacked layer group (``_stacks``) of a paged step:
    ``layer(kind, p, c, x) -> (x, c, expert rows or None)``. Returns (x,
    the new cache, the expert rows summed over layers)."""
    new_cache, rows = {}, jnp.int32(0)
    for name, kind in _stacks(cfg):
        if kind == "mla_moe":  # the rows ride the carry, summed
            def body(carry, xs, kind=kind):
                x, c, r = layer(kind, xs[0]["b0"], xs[1]["b0"], carry[0])
                return (x, carry[1] + r), {"b0": c}
            (x, rows), new_cache[name] = jax.lax.scan(
                body, (x, rows), (params[name], cache[name]))
        else:
            def body(x, xs, kind=kind):
                x, c, _ = layer(kind, xs[0]["b0"], xs[1]["b0"], x)
                return x, {"b0": c}
            x, new_cache[name] = jax.lax.scan(body, x, (params[name],
                                                        cache[name]))
    return x, new_cache, rows


def _dense_ffn(p, x, cfg: ArchConfig):
    if cfg.moe is not None:
        return moe_ffn(p, x, cfg)[0]
    return mlp(p, x, cfg)


def paged_decode_step(params, cfg: ArchConfig, token, cache, page_table,
                      positions, *, with_rows=False):
    """token: (B, 1) int32; page_table: (B, max_pages) int32; positions:
    (B,) int32 per-sequence -> (logits (B, 1, V), cache), and with
    ``with_rows`` the step's (token, held expert) rows over all layers
    (int32) last."""
    _check_paged_support(cfg)
    x = _embed(params, token, cfg)

    def layer(kind, p, c, x):
        if kind == "attn":
            y, pool_updates = attn_paged_decode(
                p["attn"], x, cfg, k_pages=c["k"], v_pages=c["v"],
                page_table=page_table, positions=positions,
                k_scales=c.get("k_scale"), v_scales=c.get("v_scale"),
            )
            x = x + y
            return x + _dense_ffn(p["ffn"], x, cfg), dict(
                c, **pool_updates), None
        y, pool = mla_mod.mla_paged_decode(
            p["attn"], x, cfg, pool=c["lat"], page_table=page_table,
            positions=positions)
        x = x + y
        y, rows = _mla_ffn(p["ffn"], kind, x, cfg)
        return x + y, {"lat": pool}, rows

    x, new_cache, rows = _paged_layers(params, cfg, cache, x, layer)
    out = (_unembed(params, x, cfg), new_cache)
    return out + (rows,) if with_rows else out


def paged_verify_step(params, cfg: ArchConfig, tokens, cache, page_table,
                      positions, n_rows):
    """Speculative verify step (DESIGN.md §9).

    tokens: (B, k) int32 — column 0 is each slot's last emitted token,
    columns 1..k-1 the drafted candidates; page_table: (B, max_pages);
    positions: (B,) absolute position of column 0 (== pre-step kv_len);
    n_rows: (B,) valid candidate rows per slot (1 + drafts actually
    used; 0 for idle slots — columns past ``n_rows`` are neither
    written to the pool nor meaningfully attended).
    Returns (logits (B, k, V), cache): logits[:, i] conditions on
    everything through candidate i, so ``argmax(logits[:, i-1])`` is the
    exact greedy token at the drafted position i — the host accepts the
    longest matching prefix plus one bonus token. k == 1 is
    op-equivalent to ``paged_decode_step``.
    """
    _check_paged_support(cfg)
    if cfg.mla is not None:
        raise NotImplementedError("speculative verify with latent "
                                  "attention (MLA)")
    x = _embed(params, tokens, cfg)

    def unit_body(x, xs):
        p_unit, c_unit = xs
        p, c = p_unit["b0"], c_unit["b0"]
        y, pool_updates = attn_paged_verify(
            p["attn"], x, cfg, k_pages=c["k"], v_pages=c["v"],
            page_table=page_table, positions=positions, n_rows=n_rows,
            k_scales=c.get("k_scale"), v_scales=c.get("v_scale"),
        )
        x = x + y
        if cfg.moe is not None:
            y, _ = moe_ffn(p["ffn"], x, cfg)
        else:
            y = mlp(p["ffn"], x, cfg)
        return x + y, {"b0": dict(c, **pool_updates)}

    x, new_units = jax.lax.scan(unit_body, x,
                                (params["units"], cache["units"]))
    return _unembed(params, x, cfg), {"units": new_units}


def prefill_chunk(params, cfg: ArchConfig, tokens, cache, page_table,
                  chunk_page_ids, q_offset, chunk_len, *, with_rows=False):
    """One prompt chunk of chunked paged prefill (DESIGN.md §6).

    tokens: (1, chunk) int32 — chunk rows at absolute positions
    ``q_offset + i``, ragged last chunks padded past ``chunk_len``;
    page_table: (max_pages,) int32 for THE one sequence;
    chunk_page_ids: (chunk // page,) physical pages of the chunk's span.
    Writes the chunk's K/V straight into the page pool per layer and
    returns ``(last_logits (1, V), cache)`` where ``last_logits`` is the
    chunk's last LIVE row — on the final chunk, the admitted request's
    first token, with no dense batch-1 cache and no copy-on-admit
    scatter anywhere on the path. ``with_rows`` appends the chunk's
    (token, held expert) rows, pad rows included.
    """
    _check_paged_support(cfg)
    x = _embed(params, tokens, cfg)
    kv_len = q_offset + chunk_len

    def layer(kind, p, c, x):
        if kind == "attn":
            y, pool_updates = attn_paged_prefill(
                p["attn"], x, cfg, k_pages=c["k"], v_pages=c["v"],
                page_table=page_table, chunk_page_ids=chunk_page_ids,
                q_offset=q_offset, kv_len=kv_len,
                k_scales=c.get("k_scale"), v_scales=c.get("v_scale"),
            )
            x = x + y
            return x + _dense_ffn(p["ffn"], x, cfg), dict(
                c, **pool_updates), None
        y, pool = mla_mod.mla_paged_prefill(
            p["attn"], x, cfg, pool=c["lat"], page_table=page_table,
            chunk_page_ids=chunk_page_ids, q_offset=q_offset, kv_len=kv_len)
        x = x + y
        y, rows = _mla_ffn(p["ffn"], kind, x, cfg)
        return x + y, {"lat": pool}, rows

    x, new_cache, rows = _paged_layers(params, cfg, cache, x, layer)
    last = jax.lax.dynamic_slice_in_dim(x, chunk_len - 1, 1, axis=1)
    logits = _unembed(params, last, cfg)
    out = (logits[:, 0], new_cache)
    return out + (rows,) if with_rows else out


def decode_step(params, cfg: ArchConfig, token, cache, pos):
    """token: (B, 1) int32; pos: scalar int32 -> (logits (B, 1, V), cache)."""
    pattern, num_units, tail = unit_layout(cfg)
    x = _embed(params, token, cfg, positions=jnp.asarray(pos)[None])
    new_cache: dict[str, Any] = {}
    lead = []
    for i, kind in enumerate(lead_kinds(cfg)):
        x, c = apply_block_decode(_layer(params["lead"]["b0"], i), kind, x,
                                  cfg, _layer(cache["lead"]["b0"], i), pos)
        lead.append(c)
    if lead:
        new_cache["lead"] = {"b0": jax.tree.map(lambda *a: jnp.stack(a),
                                                *lead)}

    def unit_body(x, xs):
        p_unit, c_unit = xs
        new_c = {}
        for j, kind in enumerate(pattern):
            x, new_c[f"b{j}"] = apply_block_decode(
                p_unit[f"b{j}"], kind, x, cfg, c_unit[f"b{j}"], pos
            )
        return x, new_c

    x, new_units = jax.lax.scan(unit_body, x,
                                (params["units"], cache["units"]))
    new_cache["units"] = new_units
    if tail:
        new_cache["tail"] = {}
        for j, kind in enumerate(tail):
            x, new_cache["tail"][f"t{j}"] = apply_block_decode(
                params["tail"][f"t{j}"], kind, x, cfg, cache["tail"][f"t{j}"],
                pos,
            )
    return _unembed(params, x, cfg), new_cache


def prefill(params, cfg: ArchConfig, tokens, max_len, *,
            frontend_embeds=None, encoder_out=None, kv_dtype=None):
    """Run the full prompt, build the cache -> (last_logits, cache).

    Cache is populated by re-running per-block K/V projections; hidden
    states flow through the same scanned units as training.
    ``kv_dtype=jnp.int8`` builds a quantized cache: prompt K/V rows are
    quantized per-row at fill time (DESIGN.md §5).
    """
    pattern, num_units, tail = unit_layout(cfg)
    x = _embed(params, tokens, cfg, frontend_embeds)
    b, s = x.shape[0], x.shape[1]
    positions = jnp.arange(s)
    with_cross = cfg.encoder_layers > 0
    mem = encoder_out

    def fill_attn(p, x, cache_blk):
        window = cfg.window if cfg.block_pattern is not None else None
        y, (k, v) = attn_block(p["attn"], x, cfg, positions=positions,
                               window=window, causal=cfg.causal)
        c = cache_blk["k"].shape[2]
        if k.shape[2] >= c:
            # keep the last window, placed at canonical ring slots
            # (position p lives at slot p % c) so decode writes line up
            k, v = k[:, :, -c:], v[:, :, -c:]
            shift = s % c
            if shift:
                k = jnp.roll(k, shift, axis=2)
                v = jnp.roll(v, shift, axis=2)
        new = dict(cache_blk)
        if cache_blk["k"].dtype == jnp.int8:
            k, ks = quantize_q8(k, -1)
            v, vs = quantize_q8(v, -1)
            new["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
                cache_blk["k_scale"], ks, 0, axis=2
            )
            new["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
                cache_blk["v_scale"], vs, 0, axis=2
            )
        new["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache_blk["k"], k, 0, axis=2
        )
        new["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache_blk["v"], v, 0, axis=2
        )
        x = x + y
        if with_cross and "cross" in p:
            dt = x.dtype
            e = cfg.hd
            hm = mem.astype(dt)
            mem_k = _split_heads(hm @ p["cross"]["wk"].astype(dt),
                                 cfg.num_kv_heads, e)
            mem_v = _split_heads(hm @ p["cross"]["wv"].astype(dt),
                                 cfg.num_kv_heads, e)
            new["mem_k"], new["mem_v"] = mem_k, mem_v
            x = x + cross_attn_block(p["cross"], x, cfg, mem_k=mem_k,
                                     mem_v=mem_v)
        if cfg.moe is not None:
            y, _ = moe_ffn(p["ffn"], x, cfg)
        else:
            y = mlp(p["ffn"], x, cfg)
        return x + y, new

    def fill_block(p, kind, x, cache_blk):
        if kind == "attn":
            return fill_attn(p, x, cache_blk)
        if kind in ("mla", "mla_moe"):
            y, lat = mla_mod.mla_block(p["attn"], x, cfg, positions=positions)
            x = x + y
            lat = jax.lax.dynamic_update_slice_in_dim(
                cache_blk["lat"], lat.astype(cache_blk["lat"].dtype), 0,
                axis=1)
            return x + _mla_ffn(p["ffn"], kind, x, cfg)[0], {"lat": lat}
        if kind == "rec":
            y, (conv, rnn) = rglru_block(p["rec"], x, cfg)
            x = x + y
            return x + mlp(p["ffn"], x, cfg), {"conv": conv, "rnn": rnn}
        y, (conv, state) = ssd_block(p["ssd"], x, cfg)
        return x + y, {"conv": conv, "state": state}

    def unit_body(x, xs):
        p_unit, c_unit = xs
        new_c = {}
        for j, kind in enumerate(pattern):
            x, new_c[f"b{j}"] = fill_block(p_unit[f"b{j}"], kind, x,
                                           c_unit[f"b{j}"])
        return x, new_c

    cache = make_cache(cfg, b, max_len,
                       mem_len=mem.shape[1] if mem is not None else 0,
                       kv_dtype=kv_dtype)
    new_cache: dict[str, Any] = {}
    lead = []
    for i, kind in enumerate(lead_kinds(cfg)):
        x, c = fill_block(_layer(params["lead"]["b0"], i), kind, x,
                          _layer(cache["lead"]["b0"], i))
        lead.append(c)
    if lead:
        new_cache["lead"] = {"b0": jax.tree.map(lambda *a: jnp.stack(a),
                                                *lead)}
    x, new_units = jax.lax.scan(unit_body, x,
                                (params["units"], cache["units"]))
    new_cache["units"] = new_units
    if tail:
        new_cache["tail"] = {}
        for j, kind in enumerate(tail):
            x, new_cache["tail"][f"t{j}"] = fill_block(
                params["tail"][f"t{j}"], kind, x, cache["tail"][f"t{j}"]
            )
    logits = _unembed(params, x[:, -1:], cfg)
    return logits, new_cache
