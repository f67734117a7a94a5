"""Attention implementations used inside the models.

Three backends, selected by ``ArchConfig.attn_impl``:

* ``xla``      — the MAS dataflow expressed at XLA level: Q is cut into
  row chunks; per chunk the FULL score row is materialized (row-granularity
  softmax, Alg. 3) and the two MatMuls sandwich it. This is what the
  multi-pod dry-run lowers: it partitions cleanly under SPMD, its peak
  memory is bounded by the chunk (the (blk_q, N) row buffer), and the
  compute overlap the paper gets from MAC/VEC co-issue is delivered by the
  TPU core's MXU/VPU co-scheduling within the fused loop body.
* ``xla_full`` — naive O(N^2)-resident attention (tiny tests only).
* ``pallas``   — the Pallas kernels from repro.kernels (per-shard path;
  interpret mode on CPU).

All functions take q: (B, Hq, Nq, E), k/v: (B, Hkv, Nkv, E).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels import ops as kops
from repro.kernels.common import NEG_INF


def _repeat_kv(x, n_rep):
    if n_rep == 1:
        return x
    b, h, n, e = x.shape
    return jnp.broadcast_to(
        x[:, :, None], (b, h, n_rep, n, e)
    ).reshape(b, h * n_rep, n, e)


def _kv_shard_constrainers():
    """(head_constrain, replicate) while ``ctx.kv_shard`` is active.

    The paged XLA twins call these at their DESIGN.md §11 cut points:
    page pools and per-head intermediates constrained onto the mesh's
    KV-head axis (every op in between is per-(batch, kv-head) local, so
    GSPMD runs the step shard-local), and the attention output
    constrained replicated — one pure-data-movement all-gather before
    the output projection, never a cross-shard partial-sum all-reduce,
    so the sharded argmax is bitwise the single-chip argmax. Returns
    None (stock path) when no kv-shard state is active.
    """
    from repro.distributed import ctx

    st = ctx.kv_shard_state()
    if st is None:
        return None
    from repro.distributed import paged as dpaged

    mesh, axis = st
    return (lambda x, dim=0: dpaged.head_sharded(x, mesh, axis, dim),
            lambda x: dpaged.replicated(x, mesh))


def xla_full_attention(q, k, v, *, causal, window=None, q_offset=0):
    return kref.attention(q, k, v, causal=causal, window=window,
                          q_offset=q_offset)


def xla_chunked_attention(q, k, v, *, causal, window=None, q_offset=0,
                          chunk=1024, remat=True):
    """MAS-dataflow attention in pure XLA (see module docstring)."""
    from repro.distributed import ctx

    b, hq, nq, e = q.shape
    _, hkv, nkv, _ = k.shape
    # Q-row-block stream parallelism (§Perf iter 1): each model shard owns
    # a contiguous run of Q row blocks. K/V stay seq-sharded: XLA then
    # runs the PV contraction distributed with partial-sum combines —
    # same wire bytes as gathering K/V, but no replicated compute
    # (§Perf iter 7, refuted: forcing the gather replicated the whole
    # chunk loop on every shard).
    q = ctx.seq_sharded_heads(q)
    k = ctx.seq_sharded_heads(k)
    v = ctx.seq_sharded_heads(v)
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    scale = e**-0.5
    chunk = min(chunk, nq)
    # §Perf iter 3: a Q chunk must not straddle sequence shards, or the
    # per-chunk dynamic-slice turns into an all-gather of fp32 scores.
    msize = (ctx._axes() or {}).get("model", 1)
    if nq % msize == 0 and nq // msize >= 1:
        chunk = min(chunk, max(1, nq // msize))
    if nq % chunk != 0:  # pad rows; sliced off at the end
        pad = (-nq) % chunk
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_chunks = q.shape[2] // chunk

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def one_chunk(i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk, axis=2)
        # Alg. 2: full score row for this Q block
        s = jnp.einsum("bhqe,bhke->bhqk", qc.astype(jnp.float32), kf) * scale
        if causal or window is not None:
            rows = i * chunk + jnp.arange(chunk)[:, None] + q_offset
            cols = jnp.arange(nkv)[None, :]
            m = cols <= rows
            if window is not None:
                m = m & (cols > rows - window)
            s = jnp.where(m[None, None], s, NEG_INF)
        # Alg. 3: row-granularity softmax (full row, no online rescale)
        p = jax.nn.softmax(s, axis=-1)
        # Alg. 4: PV
        return jnp.einsum("bhqk,bhke->bhqe", p, vf).astype(q.dtype)

    f = jax.checkpoint(one_chunk) if remat else one_chunk
    out = jax.lax.map(f, jnp.arange(n_chunks))        # (C, B, H, chunk, E)
    out = jnp.moveaxis(out, 0, 2).reshape(b, hq, n_chunks * chunk, e)
    return out[:, :, :nq]


def pallas_attention(q, k, v, *, causal, window=None, q_offset=0):
    if q_offset:
        raise NotImplementedError("pallas path uses decode kernel for offsets")
    return kops.attention(q, k, v, causal=causal, window=window)


def attention(q, k, v, *, impl="xla", causal=True, window=None, q_offset=0,
              chunk=1024, remat=True):
    if impl == "xla":
        return xla_chunked_attention(q, k, v, causal=causal, window=window,
                                     q_offset=q_offset, chunk=chunk,
                                     remat=remat)
    if impl == "xla_full":
        return xla_full_attention(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset)
    if impl == "pallas":
        return pallas_attention(q, k, v, causal=causal, window=window,
                                q_offset=q_offset)
    raise ValueError(f"unknown attn impl {impl!r}")


def decode_attention(q, k_cache, v_cache, kv_len, *, impl="xla",
                     cache_layout="dense", page_table=None,
                     k_scale=None, v_scale=None):
    """q: (B, Hq, E) against caches (B, Hkv, S, E), masked at kv_len.

    ``cache_layout="paged"`` reinterprets the caches as global page
    pools (Hkv, P, page, E) addressed through ``page_table`` with
    per-sequence ``kv_len`` (B,) — the serving engine's block-table
    layout. ``k_scale``/``v_scale`` mark an int8 cache (DESIGN.md §5):
    per-row (B, Hkv, S) fp32 scales for the dense layout, per-page
    (Hkv, P) for the paged one.
    """
    if cache_layout == "paged":
        return paged_decode_attention(q, k_cache, v_cache, page_table,
                                      kv_len, impl=impl,
                                      k_scales=k_scale, v_scales=v_scale)
    if impl == "pallas":
        return kops.decode_attention(q, k_cache, v_cache, kv_len,
                                     k_scale=k_scale, v_scale=v_scale)
    return sharded_decode_attention(q, k_cache, v_cache, kv_len,
                                    k_scale=k_scale, v_scale=v_scale)


def paged_decode_attention(q, k_pages, v_pages, page_table, kv_lens, *,
                           impl="xla", k_scales=None, v_scales=None,
                           sm_scale=None, v_dim=None):
    """Single-token decode over a block-table paged KV cache.

    q: (B, Hq, E); pools: (Hkv, P, page, E); page_table: (B, max_pages)
    int32; kv_lens: (B,) int32 live tokens per sequence. The pallas path
    gathers pages through the prefetched page table; the XLA path
    gathers the pool into the dense per-sequence layout and runs the
    same fp32 masked softmax as ``sharded_decode_attention`` (kept
    op-for-op identical so batched greedy argmax agrees between the
    dense wave engine and the paged continuous engine). Int8 pools
    carry per-page fp32 ``k_scales``/``v_scales`` (Hkv, P); the twin
    applies them exactly where the kernel does — K scales on the score
    columns after the QK^T, V scales folded into P after the normalizer
    sum — so parity holds for quantized caches too.

    Latent mode (``v_dim`` given, ``v_pages`` None): V is the first
    ``v_dim`` columns of the K pool, and the output (B, Hq, v_dim).
    """
    if impl == "pallas":
        return kops.paged_decode_attention(q, k_pages, v_pages, page_table,
                                           kv_lens, k_scales=k_scales,
                                           v_scales=v_scales,
                                           sm_scale=sm_scale, v_dim=v_dim)
    b, hq, e = q.shape
    hkv, _, page, _ = k_pages.shape
    g = hq // hkv
    cs = _kv_shard_constrainers()
    if cs is not None:
        k_pages, v_pages = cs[0](k_pages), cs[0](v_pages)
    # (Hkv, B, max_pages, page, E) -> (B, Hkv, max_pages*page, E)
    k = jnp.moveaxis(k_pages[:, page_table], 0, 1).reshape(b, hkv, -1, e)
    if v_dim is not None:
        v = k[..., :v_dim]
    else:
        v = jnp.moveaxis(v_pages[:, page_table], 0, 1).reshape(b, hkv, -1, e)
    if cs is not None:
        k, v = cs[0](k, 1), cs[0](v, 1)
    s = k.shape[2]
    qg = q.reshape(b, hkv, g, e)
    scale = e**-0.5 if sm_scale is None else sm_scale
    sc = jnp.einsum("bkge,bkse->bkgs", qg.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale
    if cs is not None:
        sc = cs[0](sc, 1)

    def per_position(scales):
        # (Hkv, P) per-page scales -> (B, Hkv, S) per-position factors
        gathered = jnp.moveaxis(scales[:, page_table], 0, 1)
        return jnp.repeat(gathered, page, axis=-1)

    if k_scales is not None:
        sc = sc * per_position(k_scales)[:, :, None, :]
    mask = jnp.arange(s)[None, None, None, :] < kv_lens[:, None, None, None]
    sc = jnp.where(mask, sc, NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if v_scales is not None:
        p = p * per_position(v_scales)[:, :, None, :]
    o = jnp.einsum("bkgs,bkse->bkge", p, v.astype(jnp.float32))
    out = (o / l).reshape(b, hq, v.shape[-1]).astype(q.dtype)
    return out if cs is None else cs[1](out)


def paged_verify_attention(q, k_pages, v_pages, page_table, kv_lens,
                           q_starts, *, impl="xla", k_scales=None,
                           v_scales=None):
    """k-token speculative verify over a block-table paged KV cache.

    q: (B, k, Hq, E) — the k candidate positions per slot, whose K/V
    rows are already in the pages; position i of slot b sits at absolute
    position ``q_starts[b] + i``, and rows at or past ``kv_lens[b]``
    (slots verifying fewer than k rows) return full-context garbage the
    host discards (DESIGN.md §9). The pallas path
    gathers pages through the prefetched page table; the XLA path
    gathers the pool dense and applies the same fused causal-diagonal +
    kv-tail mask and fp32 softmax, kept op-for-op identical so the
    per-position greedy argmax agrees between backends — the property
    the engine's accept rule relies on. Int8 pools apply the per-page
    scales exactly where the kernel does (K on score columns, V folded
    into P).
    """
    if impl == "pallas":
        return kops.paged_verify_attention(q, k_pages, v_pages, page_table,
                                           kv_lens, q_starts,
                                           k_scales=k_scales,
                                           v_scales=v_scales)
    b, spec, hq, e = q.shape
    hkv, _, page, _ = k_pages.shape
    g = hq // hkv
    cs = _kv_shard_constrainers()
    if cs is not None:
        k_pages, v_pages = cs[0](k_pages), cs[0](v_pages)
    k = jnp.moveaxis(k_pages[:, page_table], 0, 1).reshape(b, hkv, -1, e)
    v = jnp.moveaxis(v_pages[:, page_table], 0, 1).reshape(b, hkv, -1, e)
    if cs is not None:
        k, v = cs[0](k, 1), cs[0](v, 1)
    s = k.shape[2]
    # (B, Hkv, k, G, E): query heads grouped under their kv head, the
    # speculative positions forming the short Q block.
    qg = q.reshape(b, spec, hkv, g, e).transpose(0, 2, 1, 3, 4)
    scale = e**-0.5
    sc = jnp.einsum("bkpge,bkse->bkpgs", qg.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale
    if cs is not None:
        sc = cs[0](sc, 1)

    def per_position(scales):
        gathered = jnp.moveaxis(scales[:, page_table], 0, 1)
        return jnp.repeat(gathered, page, axis=-1)

    if k_scales is not None:
        sc = sc * per_position(k_scales)[:, :, None, None, :]
    rows = q_starts[:, None] + jnp.arange(spec)[None, :]         # (B, k)
    cols = jnp.arange(s)[None, None, :]
    mask = (cols <= rows[:, :, None]) & (cols < kv_lens[:, None, None])
    sc = jnp.where(mask[:, None, :, None, :], sc, NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    if v_scales is not None:
        p = p * per_position(v_scales)[:, :, None, None, :]
    o = jnp.einsum("bkpgs,bkse->bkpge", p, v.astype(jnp.float32))
    out = ((o / l).transpose(0, 2, 1, 3, 4)
           .reshape(b, spec, hq, e).astype(q.dtype))
    return out if cs is None else cs[1](out)


def paged_prefill_attention(q, k_pages, v_pages, page_table, q_offset,
                            kv_len, *, impl="xla", k_scales=None,
                            v_scales=None):
    """One prompt chunk attending to all prior context in a paged cache.

    q: (Hq, chunk, E) for ONE sequence; pools: (Hkv, P, page, E);
    page_table: (max_pages,) int32; ``q_offset``/``kv_len`` are traced
    scalars (chunk row i sits at absolute position q_offset + i and
    sees keys < min(q_offset + i + 1, kv_len)). The chunk's own K/V are
    already in the pages (DESIGN.md §6). The pallas path gathers pages
    through the prefetched page table; the XLA path gathers the pool
    dense and runs the same causal fp32 masked softmax as
    ``ref.attention`` (op-for-op with the wave engine's prefill, so
    greedy argmax agrees between monolithic and chunked admission).
    Int8 pools apply the per-page scales exactly where the kernel does:
    K scales on the score columns, V scales folded into P.
    """
    if impl == "pallas":
        return kops.paged_prefill_attention(q, k_pages, v_pages, page_table,
                                            q_offset, kv_len,
                                            k_scales=k_scales,
                                            v_scales=v_scales)
    from repro.distributed import ctx

    st = ctx.kv_shard_state()
    if st is not None:
        # head-sharded pool: chunked prefill runs as ring attention over
        # the page gather (DESIGN.md §11) — head-block slabs rotate, Q
        # chunk rows shard, three-band masking per hop
        from repro.distributed import paged as dpaged

        return dpaged.ring_paged_prefill(q, k_pages, v_pages, page_table,
                                         q_offset, kv_len, st[0],
                                         axis=st[1], k_scales=k_scales,
                                         v_scales=v_scales)
    hq, chunk, e = q.shape
    hkv, _, page, _ = k_pages.shape
    k = k_pages[:, page_table].reshape(hkv, -1, e)  # (Hkv, S, E)
    v = v_pages[:, page_table].reshape(hkv, -1, e)
    if k_scales is None:
        return kref.attention(q[None], k[None], v[None], causal=True,
                              kv_len=kv_len, q_offset=q_offset)[0]
    g = hq // hkv
    s_len = k.shape[1]
    qg = q.reshape(hkv, g, chunk, e)
    scale = e**-0.5
    sc = jnp.einsum("kgqe,kse->kgqs", qg.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale

    def per_position(scales):
        return jnp.repeat(scales[:, page_table], page, axis=-1)

    sc = sc * per_position(k_scales)[:, None, None, :]
    rows = q_offset + jnp.arange(chunk)[:, None]
    cols = jnp.arange(s_len)[None, :]
    mask = (cols <= rows) & (cols < kv_len)
    sc = jnp.where(mask[None, None], sc, NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    p = p * per_position(v_scales)[:, None, None, :]
    o = jnp.einsum("kgqs,kse->kgqe", p, v.astype(jnp.float32))
    return (o / l).reshape(hq, chunk, e).astype(q.dtype)


def sharded_decode_attention(q, k_cache, v_cache, kv_len, *,
                             k_scale=None, v_scale=None):
    """Distributed flash-decode (§Perf iter 2a).

    The cache is sequence-sharded over 'model'; instead of letting XLA
    all-gather K/V (the baseline's dominant collective), scores are
    constrained to stay sharded over the cache's S axis, so the softmax
    max/sum and the PV contraction reduce over the model axis with
    (B, H, E)-sized all-reduces — the split-K combine of the decode
    kernel, executed across chips.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed import ctx

    b, hq, e = q.shape
    _, hkv, s, _ = k_cache.shape
    g = hq // hkv
    qg = q.reshape(b, hkv, g, e)  # grouped: no kv repeat, no resharding

    def seq_spec(axes):
        return P(ctx.batch_axes(), None,
                 "model" if "model" in axes else None, None)

    k = ctx.constrain(k_cache, seq_spec)
    v = ctx.constrain(v_cache, seq_spec)
    scale = e**-0.5
    sc = jnp.einsum("bkge,bkse->bkgs", qg.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale
    if k_scale is not None:
        # int8 cache: per-row fp32 scales dequantize the score columns
        # (same op order as the decode kernel — after QK^T and sm_scale)
        sc = sc * k_scale[:, :, None, :]
    sc = ctx.constrain(
        sc, lambda axes: P(ctx.batch_axes(), None, None,
                           "model" if "model" in axes else None)
    )
    mask = jnp.arange(s)[None, None, None, :] < kv_len
    sc = jnp.where(mask, sc, NEG_INF)
    # max/sum reduce over the sharded S axis -> (B, Hkv, G) all-reduces
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale[:, :, None, :]
    o = jnp.einsum("bkgs,bkse->bkge", p, v.astype(jnp.float32))
    return (o / l).reshape(b, hq, e).astype(q.dtype)
