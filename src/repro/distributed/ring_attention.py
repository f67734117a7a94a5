"""Ring attention: exact attention with sequence-sharded Q AND K/V.

The cluster-scale version of the paper's streaming: each chip owns a
contiguous Q row-block stream (as in our tp_sp policy) but K/V never
materialize fully anywhere — blocks rotate around a ring via
``ppermute`` while each chip maintains the online-softmax (m, l, acc)
combine per hop. ICI traffic per chip = the K/V bytes, independent of
the number of chips; VMEM/HBM working set = one K/V block. This is what
replaces the per-layer K/V all-gather of the tp_sp policy when S grows
past what a single chip can stage (e.g. 500k-class prefill).

Masking reuses the kernels' three-band helpers (DESIGN.md §3), so
partial hops mask correctly: ``kv_len`` truncates a tail-padded ring
block (a prompt that only partially fills the last shard's K/V slab)
and ``q_offset`` places the Q rows for chunked admission — a hop whose
block straddles the causal diagonal gets the same fused diagonal +
kv-tail select the paged kernels use, instead of the full-attention
assumption the first version made.

Validated against the dense oracle in tests (4-device subprocess).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.ctx import pvary as _pvary
from repro.kernels.common import NEG_INF, mask_kv_tail, three_band_select


def ring_attention(q, k, v, mesh: Mesh, *, axis: str = "model",
                   causal: bool = False, sm_scale: float | None = None,
                   kv_len=None, q_offset: int = 0):
    """q, k, v: (B, H, S, E) global arrays, S sharded over ``axis``.

    ``kv_len`` (traced scalar ok) masks kv positions >= kv_len on every
    hop — the partial-hop case where the live context does not fill the
    sharded K/V slab. ``q_offset`` shifts the Q rows' absolute positions
    for causal masking of a chunk that starts mid-sequence.
    """
    bsz, h, s, e = q.shape
    n_shards = mesh.shape[axis]
    assert s % n_shards == 0
    s_loc = s // n_shards
    scale = (e**-0.5) if sm_scale is None else sm_scale
    spec = P(None, None, axis, None)
    kv_lim = s if kv_len is None else kv_len

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )
    def run(q_loc, k_loc, v_loc):
        idx = jax.lax.axis_index(axis)
        q0 = idx * s_loc + q_offset  # absolute position of local row 0
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

        qf = q_loc.astype(jnp.float32)
        m0 = jnp.full((bsz, h, s_loc, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((bsz, h, s_loc, 1), jnp.float32)
        a0 = jnp.zeros((bsz, h, s_loc, e), jnp.float32)

        def hop(t, carry):
            k_cur, v_cur, m, l, acc = carry
            src = (idx - t) % n_shards      # owner of the block we hold
            col0 = src * s_loc              # absolute kv position of col 0
            scores = jnp.einsum(
                "bhqe,bhke->bhqk", qf, k_cur.astype(jnp.float32)
            ) * scale
            if causal:
                scores = jax.vmap(jax.vmap(
                    lambda t2: three_band_select(t2, q0, col0, kv_lim)
                ))(scores)
            elif kv_len is not None:
                scores = jax.vmap(jax.vmap(
                    lambda t2: mask_kv_tail(t2, col0, kv_lim)
                ))(scores)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "bhqk,bhke->bhqe", p, v_cur.astype(jnp.float32)
            )
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
            return k_cur, v_cur, m_new, l, acc

        # freshly-created zeros are device-invariant; mark them varying
        # so the fori_loop carry types stay stable (inputs already vary)
        m0, l0, a0 = (_pvary(x, (axis,)) for x in (m0, l0, a0))
        init = (k_loc, v_loc, m0, l0, a0)
        _, _, m, l, acc = jax.lax.fori_loop(0, n_shards, hop, init)
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows
        return (acc / l).astype(q_loc.dtype)

    return run(q, k, v)
