"""MAS-Attention Pallas TPU kernel — the paper-faithful dataflow.

TPU adaptation of Alg. 1-4 (see DESIGN.md §2):

* MAC unit -> MXU, VEC unit -> VPU. Both live in one TPU core; Mosaic
  co-issues MXU and VPU work from a single fused kernel and overlaps the
  DMA stream via the grid pipeline — the semi-synchronous two-stream
  schedule is expressed structurally.
* Row-granularity softmax: the FULL score row ``S in (blk_q, N)`` is
  materialized in VMEM per Q-row block (fp32). No online-softmax rescaling —
  that is the paper's exactness argument and its §5.6 memory limitation.
* Multi-tiered tiling: Q is cut into ``blk_q`` row blocks (N_Q), K/V into
  ``blk_kv`` sub-matrix tiles (N_{K,V}).

Two variants realize the §4.3 proactive-overwrite policy:

* ``kv_resident=True``  — K and V are pinned in VMEM for a whole (batch,
  head): the paper's ideal regime when L1 fits the operands.
* ``kv_resident=False`` — K/V tiles are streamed: every grid step a
  (blk_kv, E) tile OVERWRITES the previous one in VMEM, and V is re-fetched
  from HBM for the PV pass (the "evict the reloadable operand, reload,
  redo" policy, expressed as dataflow; DRAM-read inflation matches §5.4.2).

Causal prefill prunes fully-masked KV tiles in both variants (DESIGN.md
§3): the resident loops stop at the last tile intersecting the Q row
block, the streamed grid skips compute AND clamps its index maps so dead
steps issue no DMA, and only diagonal-straddling tiles pay for the
in-tile mask.

Inputs are pre-flattened to (B*H, N, E) by ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import DEFAULT_VMEM_BUDGET
from repro.kernels.common import (
    NEG_INF,
    causal_tile_bounds as _causal_tile_bounds,
    causal_tile_mask as _causal_tile_mask,
    mask_kv_tail,
)


# ---------------------------------------------------------------------------
# Variant 1: K/V resident in VMEM (paper's ideal regime)
# ---------------------------------------------------------------------------


def _mas_resident_kernel(
    q_ref, k_ref, v_ref, o_ref, s_ref, *, blk_q, blk_kv, sm_scale, causal,
    kv_len
):
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (blk_q, E)
    n = k_ref.shape[1]
    nkv = n // blk_kv
    if causal:
        n_full, n_needed = _causal_tile_bounds(iq, blk_q, blk_kv, nkv)
    else:
        n_full = n_needed = nkv

    # ---- Alg. 2: MAC stream, S tiles into the full on-chip row buffer ----
    def s_body(j, masked):
        k_tile = k_ref[0, pl.ds(j * blk_kv, blk_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if masked:  # only diagonal-straddling tiles pay for the mask
            m = _causal_tile_mask(blk_q, blk_kv, iq * blk_q, j * blk_kv)
            s = jnp.where(m, s, NEG_INF)
        if kv_len is not None:
            s = mask_kv_tail(s, j * blk_kv, kv_len)
        s_ref[:, pl.ds(j * blk_kv, blk_kv)] = s

    jax.lax.fori_loop(0, n_full, lambda j, c: (s_body(j, False), c)[1], 0)
    if causal:
        jax.lax.fori_loop(
            n_full, n_needed, lambda j, c: (s_body(j, True), c)[1], 0
        )

    # ---- Alg. 3: VEC stream, row-granularity softmax (exact, one pass) ----
    s = s_ref[...]
    if causal:
        # Tiles beyond n_needed were never written: mask the stale tail so
        # the row max/sum only see live columns (exactness invariant).
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < n_needed * blk_kv, s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    s_ref[...] = p / l  # P_i kept on-chip (never spilled — §4.3 invariant)

    # ---- Alg. 4: MAC stream, O accumulation over V tiles ----
    def o_body(j, acc):
        v_tile = v_ref[0, pl.ds(j * blk_kv, blk_kv), :].astype(jnp.float32)
        p_tile = s_ref[:, pl.ds(j * blk_kv, blk_kv)]
        return acc + jax.lax.dot_general(
            p_tile, v_tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    e = q_ref.shape[2]
    acc = jax.lax.fori_loop(
        0, n_needed, o_body, jnp.zeros((blk_q, e), jnp.float32)
    )
    o_ref[0] = acc.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Variant 2: K/V streamed (proactive-overwrite regime)
# ---------------------------------------------------------------------------


def _mas_streamed_kernel(
    q_ref, k_ref, v_ref, o_ref, s_ref, acc_ref, *, blk_q, blk_kv, nkv,
    sm_scale, causal, kv_len
):
    iq = pl.program_id(1)
    j = pl.program_id(2)
    if causal:
        n_full, n_needed = _causal_tile_bounds(iq, blk_q, blk_kv, nkv)
    else:
        n_full = n_needed = nkv

    # Dead grid steps (j in [n_needed, nkv) and the mirrored PV range) do
    # no compute; the index maps in mas_attention_flat clamp the K/V block
    # index there so no DMA is issued for fully-masked tiles either.
    @pl.when(jnp.logical_and(j < nkv, j < n_needed))
    def _s_pass():
        # MAC stream: this K tile overwrites the previous one in VMEM.
        q = q_ref[0].astype(jnp.float32)
        k_tile = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if causal:
            # Only diagonal-straddling tiles (j >= n_full) pay for the
            # in-tile mask; strictly-below-diagonal tiles skip it.
            def _mask(x):
                m = _causal_tile_mask(blk_q, blk_kv, iq * blk_q, j * blk_kv)
                return jnp.where(m, x, NEG_INF)

            s = jax.lax.cond(j >= n_full, _mask, lambda x: x, s)
        if kv_len is not None:
            s = mask_kv_tail(s, j * blk_kv, kv_len)
        s_ref[:, pl.ds(j * blk_kv, blk_kv)] = s

    @pl.when(j == nkv)
    def _softmax():
        # VEC stream: full-row softmax once all S tiles landed.
        s = s_ref[...]
        if causal:
            # Fully-masked tiles were never written: mask the stale tail.
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols < n_needed * blk_kv, s, NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        s_ref[...] = p / l
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(j >= nkv, j - nkv < n_needed))
    def _pv_pass():
        # MAC stream resumes: V tiles are RE-FETCHED from HBM (the reload
        # after overwrite) and accumulated — only the intersecting ones.
        jj = j - nkv
        p_tile = s_ref[:, pl.ds(jj * blk_kv, blk_kv)]
        v_tile = v_ref[0].astype(jnp.float32)
        acc_ref[...] += jax.lax.dot_general(
            p_tile, v_tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == 2 * nkv - 1)
    def _writeback():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------


def mas_attention_flat(
    q: jax.Array,  # (BHq, Nq, E)
    k: jax.Array,  # (BHkv, Nkv, E)
    v: jax.Array,  # (BHkv, Nkv, E)
    *,
    blk_q: int,
    blk_kv: int,
    causal: bool = False,
    sm_scale: float | None = None,
    kv_resident: bool = True,
    kv_len: int | None = None,
    vmem_limit_bytes: int = DEFAULT_VMEM_BUDGET,
    interpret: bool = False,
) -> jax.Array:
    bhq, nq, e = q.shape
    bhkv, nkv_len, _ = k.shape
    assert bhq % bhkv == 0
    group = bhq // bhkv
    assert nq % blk_q == 0, (nq, blk_q)
    assert nkv_len % blk_kv == 0, (nkv_len, blk_kv)
    scale = (e**-0.5) if sm_scale is None else sm_scale
    n_q_blocks = nq // blk_q
    n_kv_blocks = nkv_len // blk_kv
    if kv_len is not None and kv_len >= nkv_len:
        kv_len = None  # no padding — skip the mask

    out_shape = jax.ShapeDtypeStruct((bhq, nq, e), q.dtype)
    q_spec = pl.BlockSpec((1, blk_q, e), lambda bh, iq, *_: (bh, iq, 0))
    o_spec = pl.BlockSpec((1, blk_q, e), lambda bh, iq, *_: (bh, iq, 0))

    if kv_resident:
        kernel = functools.partial(
            _mas_resident_kernel,
            blk_q=blk_q, blk_kv=blk_kv, sm_scale=scale, causal=causal,
            kv_len=kv_len,
        )
        grid = (bhq, n_q_blocks)
        kv_spec = pl.BlockSpec(
            (1, nkv_len, e), lambda bh, iq: (bh // group, 0, 0)
        )
        scratch = [pltpu.VMEM((blk_q, nkv_len), jnp.float32)]
        dimension_semantics = ("arbitrary", "arbitrary")
    else:
        kernel = functools.partial(
            _mas_streamed_kernel,
            blk_q=blk_q, blk_kv=blk_kv, nkv=n_kv_blocks, sm_scale=scale,
            causal=causal, kv_len=kv_len,
        )
        grid = (bhq, n_q_blocks, 2 * n_kv_blocks)
        last = n_kv_blocks - 1

        def _last_needed(iq):
            # Last KV tile intersecting Q row block iq. Clamping the block
            # index here means dead grid steps revisit the same tile, so
            # the pipeline issues no DMA for fully-masked tiles. Derived
            # from _causal_tile_bounds so the clamp and the kernel's
            # pl.when compute gate stay in lockstep.
            if not causal:
                return last
            return _causal_tile_bounds(iq, blk_q, blk_kv, n_kv_blocks)[1] - 1

        kv_k_spec = pl.BlockSpec(
            (1, blk_kv, e),
            lambda bh, iq, j: (bh // group, jnp.minimum(j, _last_needed(iq)), 0),
        )
        kv_v_spec = pl.BlockSpec(
            (1, blk_kv, e),
            lambda bh, iq, j: (
                bh // group,
                jnp.clip(j - n_kv_blocks, 0, _last_needed(iq)),
                0,
            ),
        )
        scratch = [
            pltpu.VMEM((blk_q, nkv_len), jnp.float32),
            pltpu.VMEM((blk_q, e), jnp.float32),
        ]
        dimension_semantics = ("arbitrary", "arbitrary", "arbitrary")

    kwargs = {}
    if not interpret:
        # The compiler's scoped-VMEM limit is the budget the policy planned
        # the working set against (core/policy.py), so a decision the
        # policy accepts is one the compiler accepts.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=dimension_semantics,
            vmem_limit_bytes=vmem_limit_bytes,
        )
    if kv_resident:
        in_specs = [q_spec, kv_spec, kv_spec]
    else:
        in_specs = [q_spec, kv_k_spec, kv_v_spec]
    return pl.pallas_call(
        kernel,
        name="mas_attention",
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(q, k, v)
