"""Multi-head latent attention (MLA, deepseek_v3 without q compression).

Per token and layer the cache holds one latent row of ``latent_dim``
values: ``c`` (``kv_lora_rank``, after ``kv_norm``) followed by the rope
key ``k_pe`` (``qk_rope_head_dim``, rotated), shared by every head. Head
h's key is ``[c @ W_UK_h, k_pe]`` and its value ``c @ W_UV_h``.

Two forms of the same arithmetic (DESIGN.md §12):

- expanded, for whole sequences and prompt chunks: K and V per head from
  the latent rows, then causal softmax attention with E = nope + rope;
- absorbed, for decode: ``q_lat = q_nope @ W_UK_h^T`` (nope -> rank per
  head), scores ``[q_lat, q_pe] . [c, k_pe]``, output
  ``(p . c) @ W_UV_h``. That is multi-query attention of every head on
  one latent "KV head" of ``latent_dim`` whose first ``kv_lora_rank``
  columns are also V, which the paged decode kernel runs in its latent
  mode.

The paged pool's rows are ``pool_dim`` wide: the latent row and zeros up
to the 128-lane tile (576 -> 640 at the published sizes), which no score
or output reads.

RoPE rotates interleaved pairs (2i, 2i+1) of the rope parts, as the
program does everywhere; deepseek_v3's published code de-interleaves q
and k before its half-split rotation, so its scores are these. ``_rope``
computes the same products as ``common.apply_rope`` with the pair swap
as one small matmul instead of strided gathers, which keeps the decode
step's device ops few (PERF.md §6, PR 16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import NEG_INF
from repro.models import attention as attn_mod
from repro.models.common import (
    ArchConfig,
    dense_init,
    rms_norm,
    rope_frequencies,
    split_keys,
)

PREFILL_Q_BLOCK = 512  # query rows a chunk's expanded attention scores at once


def init_mla(key, cfg: ArchConfig):
    a, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    r = a.kv_lora_rank
    ks = split_keys(key, ["q", "kv_a", "uk", "uv", "o"])
    dt = cfg.param_dtype
    return {
        "norm": jnp.zeros((d,), dt),
        "wq": dense_init(ks["q"], (d, h * a.qk_head_dim), dtype=dt),
        "wkv_a": dense_init(ks["kv_a"], (d, a.latent_dim), dtype=dt),
        "kv_norm": jnp.zeros((r,), dt),
        "w_uk": dense_init(ks["uk"], (r, h * a.qk_nope_head_dim), dtype=dt),
        "w_uv": dense_init(ks["uv"], (r, h * a.v_head_dim), dtype=dt),
        "wo": dense_init(ks["o"], (h * a.v_head_dim, d), dtype=dt),
    }


def _rope(x, positions, theta):
    """Interleaved-pair RoPE of x (..., E) at ``positions`` (x's leading
    shape): ``x cos + swap(x) sin``, where swap maps each pair (x1, x2) to
    (-x2, x1) by a signed permutation matmul (exact)."""
    e = x.shape[-1]
    ang = positions[..., None].astype(jnp.float32) * jnp.repeat(
        rope_frequencies(e, theta), 2)
    i = jnp.arange(e)
    swap = jnp.where((i[:, None] ^ 1) == i[None, :],
                     jnp.where(i[:, None] % 2 == 0, 1, -1), 0)
    swapped = jnp.dot(x, swap.astype(x.dtype),
                      preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * jnp.cos(ang)
            + swapped * jnp.sin(ang)).astype(x.dtype)


def _project(params, x, cfg: ArchConfig, positions):
    """x: (B, S, D); positions (B, S) or (S,) -> q_nope (B, S, H, nope),
    q_pe (B, S, H, rope) rotated, latent rows (B, S, latent_dim)."""
    a, dt = cfg.mla, x.dtype
    b, s, d = x.shape
    h = rms_norm(x, params["norm"], cfg.norm_eps).reshape(b * s, d)
    pos = jnp.broadcast_to(positions, (b, s)).reshape(b * s)
    q = (h @ params["wq"].astype(dt)).reshape(b * s, cfg.num_heads,
                                              a.qk_head_dim)
    q_nope, q_pe = q[..., :a.qk_nope_head_dim], q[..., a.qk_nope_head_dim:]
    q_pe = _rope(q_pe, pos[:, None], cfg.rope_theta)
    kv = h @ params["wkv_a"].astype(dt)
    c = rms_norm(kv[:, :a.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kv[:, a.kv_lora_rank:], pos, cfg.rope_theta)
    lat = jnp.concatenate([c, k_pe], axis=-1)
    return (q_nope.reshape(b, s, *q_nope.shape[1:]),
            q_pe.reshape(b, s, *q_pe.shape[1:]), lat.reshape(b, s, -1))


def _expanded(params, q_nope, q_pe, lat, cfg: ArchConfig, mask):
    """Attention from expanded K/V. q_*: (B, Q, H, ·); lat: (B, S, L);
    mask: (B or 1, Q, S) -> (B, Q, H * v_head_dim)."""
    a, dt = cfg.mla, q_nope.dtype
    b, s, _ = lat.shape
    hh, r = cfg.num_heads, a.kv_lora_rank
    c, k_pe = lat[..., :r], lat[..., r:a.latent_dim]
    k_nope = (c @ params["w_uk"].astype(dt)).reshape(b, s, hh, -1)
    v = (c @ params["w_uv"].astype(dt)).reshape(b, s, hh, -1)
    f32 = jnp.float32
    sc = (jnp.einsum("bqhn,bkhn->bhqk", q_nope.astype(f32),
                     k_nope.astype(f32))
          + jnp.einsum("bqhe,bke->bhqk", q_pe.astype(f32),
                       k_pe.astype(f32))) * a.sm_scale
    sc = jnp.where(mask[:, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhv->bqhv", p, v.astype(f32)).astype(dt)
    return o.reshape(o.shape[0], o.shape[1], -1)


def _pad_to(x, width):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _absorb(params, q_nope, q_pe, cfg: ArchConfig, width=None):
    """(B, H, nope), (B, H, rope) -> latent-space queries (B, H, width),
    zero-padded past ``latent_dim``."""
    a, dt = cfg.mla, q_nope.dtype
    w_uk = params["w_uk"].astype(dt).reshape(a.kv_lora_rank, cfg.num_heads,
                                             a.qk_nope_head_dim)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk)
    q = jnp.concatenate([q_lat, q_pe.astype(dt)], axis=-1)
    return _pad_to(q, width or a.latent_dim)


def _unabsorb(params, o_lat, x, cfg: ArchConfig):
    """(B, H, rank) latent outputs -> (B, 1, D) block output."""
    a, dt = cfg.mla, x.dtype
    w_uv = params["w_uv"].astype(dt).reshape(a.kv_lora_rank, cfg.num_heads,
                                             a.v_head_dim)
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(dt), w_uv)
    return o.reshape(x.shape[0], 1, -1) @ params["wo"].astype(dt)


def mla_block(params, x, cfg: ArchConfig, *, positions):
    """Full-sequence causal MLA (expanded). Returns (out, latent rows)."""
    with jax.named_scope("mla"):
        q_nope, q_pe, lat = _project(params, x, cfg, positions)
        s = x.shape[1]
        mask = (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :])[None]
        o = _expanded(params, q_nope, q_pe, lat, cfg, mask)
        return o @ params["wo"].astype(x.dtype), lat


def _latent_attend(q, lat, kv_len, cfg: ArchConfig):
    """Absorbed decode against dense latent rows: q (B, H, L), lat
    (B, S, L), kv_len (B,) -> (B, H, rank), fp32 softmax."""
    r = cfg.mla.kv_lora_rank
    f32 = jnp.float32
    sc = jnp.einsum("bhl,bsl->bhs", q.astype(f32),
                    lat.astype(f32)) * cfg.mla.sm_scale
    live = jnp.arange(lat.shape[1])[None, None, :] < kv_len[:, None, None]
    p = jax.nn.softmax(jnp.where(live, sc, NEG_INF), axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p, lat[..., :r].astype(f32))


def mla_decode(params, x, cfg: ArchConfig, *, cache_lat, pos):
    """One token against a dense latent cache (B, C, L) at scalar ``pos``
    -> (out, new cache)."""
    with jax.named_scope("mla"):
        b = x.shape[0]
        q_nope, q_pe, row = _project(params, x, cfg,
                                     pos + jnp.zeros((1,), jnp.int32))
        cache_lat = jax.lax.dynamic_update_slice_in_dim(
            cache_lat, row.astype(cache_lat.dtype), pos, axis=1)
        q = _absorb(params, q_nope[:, 0], q_pe[:, 0], cfg)
        o = _latent_attend(q, cache_lat, jnp.full((b,), pos + 1), cfg)
        return _unabsorb(params, o, x, cfg), cache_lat


def mla_paged_decode(params, x, cfg: ArchConfig, *, pool, page_table,
                     positions):
    """One token per slot against the latent pool (1, P, page, pool_dim).

    x: (B, 1, D); positions (B,). The token's latent row is written at
    its page first, then every head attends in the absorbed form through
    the page table (the paged decode kernel's latent mode under
    ``attn_impl="pallas"``, its XLA twin otherwise). Returns (out, pool).
    """
    with jax.named_scope("mla"):
        b, page = x.shape[0], pool.shape[2]
        q_nope, q_pe, row = _project(params, x, cfg, positions[:, None])
        page_ids = page_table[jnp.arange(b), positions // page]
        pool = pool.at[0, page_ids, positions % page].set(
            _pad_to(row[:, 0], pool.shape[-1]).astype(pool.dtype))
        q = _absorb(params, q_nope[:, 0], q_pe[:, 0], cfg, pool.shape[-1])
        o = attn_mod.paged_decode_attention(
            q, pool, None, page_table, positions + 1,
            impl="pallas" if cfg.attn_impl == "pallas" else "xla",
            sm_scale=cfg.mla.sm_scale, v_dim=cfg.mla.kv_lora_rank)
        return _unabsorb(params, o, x, cfg), pool


def mla_paged_prefill(params, x, cfg: ArchConfig, *, pool, page_table,
                      chunk_page_ids, q_offset, kv_len):
    """One prompt chunk against the latent pool, in the expanded form.

    x: (1, chunk, D), rows at q_offset + i; page_table (max_pages,) of the
    one sequence; chunk_page_ids (chunk // page,). The chunk's latent
    rows are written into its pages first (rows at or past ``kv_len``
    zeroed), then the sequence's pages are read back whole and the
    chunk attends to them causally, PREFILL_Q_BLOCK query rows at a
    time. Returns (out, pool).
    """
    with jax.named_scope("mla"):
        chunk = x.shape[1]
        _, _, page, lat_dim = pool.shape
        positions = q_offset + jnp.arange(chunk)
        q_nope, q_pe, rows = _project(params, x, cfg, positions)
        rows = jnp.where((positions < kv_len)[None, :, None], rows, 0)
        pool = pool.at[0, chunk_page_ids].set(
            _pad_to(rows, lat_dim).reshape(-1, page, lat_dim)
            .astype(pool.dtype))
        lat = pool[0, page_table].reshape(1, -1, lat_dim)
        blk = min(chunk, PREFILL_Q_BLOCK)
        n_blk = -(-chunk // blk)
        pad = n_blk * blk - chunk
        q_nope = jnp.pad(q_nope, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pe = jnp.pad(q_pe, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cols = jnp.arange(lat.shape[1])[None, :]

        def one_block(i):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, i * blk, blk, axis=1)
            qp = jax.lax.dynamic_slice_in_dim(q_pe, i * blk, blk, axis=1)
            r = q_offset + i * blk + jnp.arange(blk)[:, None]
            mask = (cols <= r) & (cols < kv_len)
            return _expanded(params, qn, qp, lat, cfg, mask[None])[0]

        o = jax.lax.map(one_block, jnp.arange(n_blk))
        o = o.reshape(1, n_blk * blk, -1)[:, :chunk]
        return o @ params["wo"].astype(x.dtype), pool
