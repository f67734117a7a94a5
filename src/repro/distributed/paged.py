"""Multi-chip paged serving collectives (DESIGN.md §11).

The continuous-batching engine's page pools are (Hkv, P, page, E): the
KV-head axis leads, so head parallelism — not sequence parallelism — is
the natural shard dim (a physical page holds one head-shard's rows for
its token span; page identity stays chip-local and the block tables and
``kv_lens`` replicate). Two pieces live here:

* ``head_sharded`` / ``replicated`` — ``with_sharding_constraint``
  helpers the ``models.attention`` paged dispatchers apply while
  ``ctx.kv_shard`` is active. Decode and verify need NO collectives of
  their own: every op between the pool gather and the attention output
  is per-(batch, kv-head) local, so constraining the pools and
  intermediates onto the head axis lets GSPMD run the whole step
  shard-local, and constraining the final output replicated forces one
  pure-data-movement all-gather of the per-head outputs before the
  (replicated) output projection. No cross-shard partial-sum all-reduce
  ever exists, so there is no reduction-order hazard and the sharded
  argmax is bitwise the single-chip argmax.

* ``ring_paged_prefill`` — chunked prefill as ring attention over the
  page gather. Sequence rotation (distributed/ring_attention.py) is
  impossible on a head-sharded pool, so the ring rotates GATHERED HEAD
  BLOCKS instead: each chip gathers its local heads' dense K/V slab
  through the page table once, Q chunk rows shard over chips, and the
  slabs rotate via ``ppermute``. At hop t a chip holds the full-context
  slab of head shard (idx - t) % n, so it computes that head slot of
  its own Q rows with a FULL-S softmax — no online combine: hops fill
  disjoint head slots and the result is an exact concatenation. Per-hop
  masking is the kernels' §3 three-band select. Wire bytes per chip =
  the gathered K/V slab, independent of chip count — the same invariant
  the sequence ring has.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.ctx import pvary as _pvary
from repro.kernels.common import three_band_select


def head_sharded(x, mesh: Mesh, axis: str = "model", dim: int = 0):
    """Constrain array dim ``dim`` (the KV-head axis) over ``axis``."""
    spec = [None] * x.ndim
    spec[dim] = axis
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def replicated(x, mesh: Mesh):
    """Constrain ``x`` replicated — the all-gather point of the step."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


def ring_paged_prefill(q, k_pages, v_pages, page_table, q_offset, kv_len,
                       mesh: Mesh, *, axis: str = "model",
                       k_scales=None, v_scales=None):
    """One prompt chunk on a KV-head-sharded paged pool (see module doc).

    Mirrors ``models.attention.paged_prefill_attention``'s contract:
    q (Hq, chunk, E) for ONE sequence, pools (Hkv, P, page, E) sharded
    on Hkv over ``axis``, page_table (max_pages,) replicated,
    ``q_offset``/``kv_len`` traced scalars. The fp32 hop body replicates
    ``kernels.ref.attention`` op-for-op (fp32 scores, NEG_INF select,
    full-row ``jax.nn.softmax``); the int8 hop body replicates the XLA
    twin's manual math (K page scales on the score columns before the
    mask, V scales folded into P, ``l == 0 -> 1`` guard) — so greedy
    argmax agrees token-for-token with the single-chip path.
    """
    hq, chunk, e = q.shape
    hkv, _, page, _ = k_pages.shape
    g = hq // hkv
    n = mesh.shape[axis]
    assert hkv % n == 0, f"kv heads {hkv} must divide over {n} chips"
    hkv_loc = hkv // n
    pad = (-chunk) % n       # Q rows shard over chips; pad, slice after
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    chunk_loc = (chunk + pad) // n
    scale = e**-0.5
    quant = k_scales is not None
    out_dtype = q.dtype

    pool = P(axis, None, None, None)
    in_specs = [P(None, axis, None), pool, pool, P(), P(), P()]
    args = [q, k_pages, v_pages, page_table,
            jnp.asarray(q_offset, jnp.int32), jnp.asarray(kv_len, jnp.int32)]
    if quant:
        in_specs += [P(axis, None), P(axis, None)]
        args += [k_scales, v_scales]

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=P(), check_vma=False)
    def run(q_loc, kp, vp, table, q_off, klen, *scales):
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % n) for i in range(n)]
        # gather the local heads' dense slab through the table ONCE;
        # the ring then rotates the gathered slab, not the pool
        k_blk = kp[:, table].reshape(hkv_loc, -1, e)      # (Hkv_loc, S, E)
        v_blk = vp[:, table].reshape(hkv_loc, -1, e)
        if quant:
            ks_blk = jnp.repeat(scales[0][:, table], page, axis=-1)
            vs_blk = jnp.repeat(scales[1][:, table], page, axis=-1)
        else:  # zero-width placeholders keep the carry structure fixed
            ks_blk = jnp.zeros((hkv_loc, 0), jnp.float32)
            vs_blk = jnp.zeros((hkv_loc, 0), jnp.float32)
        qg = q_loc.reshape(hkv, g, chunk_loc, e).astype(jnp.float32)
        q0 = q_off + idx * chunk_loc    # absolute position of local row 0
        out0 = _pvary(jnp.zeros((hkv, g, chunk_loc, e), out_dtype), (axis,))
        ks_blk, vs_blk = (_pvary(x, (axis,)) for x in (ks_blk, vs_blk))

        def hop(t, carry):
            kb, vb, ksb, vsb, out = carry
            src = (idx - t) % n         # head shard whose slab we hold
            q_sub = jax.lax.dynamic_slice_in_dim(qg, src * hkv_loc,
                                                 hkv_loc, 0)
            sc = jnp.einsum("kgqe,kse->kgqs", q_sub,
                            kb.astype(jnp.float32)) * scale
            if quant:
                sc = sc * ksb[:, None, None, :]
            sc = jax.vmap(jax.vmap(
                lambda t2: three_band_select(t2, q0, 0, klen)))(sc)
            if quant:
                m = jnp.max(sc, axis=-1, keepdims=True)
                p = jnp.exp(sc - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                l = jnp.where(l == 0.0, 1.0, l)
                p = p * vsb[:, None, None, :]
                o = jnp.einsum("kgqs,kse->kgqe", p, vb.astype(jnp.float32))
                o = (o / l).astype(out_dtype)
            else:
                p = jax.nn.softmax(sc, axis=-1)
                o = jnp.einsum("kgqs,kse->kgqe", p,
                               vb.astype(jnp.float32)).astype(out_dtype)
            # disjoint head slot per hop -> exact concat, no online combine
            out = jax.lax.dynamic_update_slice_in_dim(out, o,
                                                      src * hkv_loc, 0)
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            if quant:
                ksb = jax.lax.ppermute(ksb, axis, perm)
                vsb = jax.lax.ppermute(vsb, axis, perm)
            return kb, vb, ksb, vsb, out

        init = (k_blk, v_blk, ks_blk, vs_blk, out0)
        *_, out = jax.lax.fori_loop(0, n, hop, init)
        out = out.reshape(hq, chunk_loc, e)
        return jax.lax.all_gather(out, axis, axis=1, tiled=True)

    return run(*args)[:, :chunk]
