"""Pallas TPU kernel for the SSD intra-chunk block (mamba2).

The chunked SSD computation (models/ssm.py) splits into a quadratic
intra-chunk part — (C B^T ⊙ L) X plus the chunk-state contraction, both
MXU matmuls with VPU decay/elementwise work interleaved (the same
MAC/VEC two-stream structure MAS exploits, DESIGN.md §4) — and a cheap
sequential inter-chunk recurrence. This kernel fuses the intra-chunk
part per (batch·head, chunk) grid cell so the (q, q) decay mask and
score tile never leave VMEM; the recurrence stays in jnp.

Layouts (pre-flattened by ops): x (BH, NC, Q, P); a (BH, NC, Q);
b, c (BH, NC, Q, N). Outputs: y_diag (BH, NC, Q, P) and per-chunk
states (BH, NC, N, P).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_chunk_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, s_ref, *, q, n, p):
    a = a_ref[0, 0].astype(jnp.float32)                    # (Q,)
    a_cum = jnp.cumsum(a)                                  # (Q,)
    # L[i, j] = exp(a_cum[i] - a_cum[j]) for i >= j else 0
    diff = a_cum[:, None] - a_cum[None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lmat = jnp.exp(jnp.where(cols <= rows, diff, NEG_INF))

    x = x_ref[0, 0].astype(jnp.float32)                    # (Q, P)
    b = b_ref[0, 0].astype(jnp.float32)                    # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)                    # (Q, N)

    # MAC stream: scores; VEC stream: decay mask; MAC stream: Y
    scores = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * lmat
    y = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # chunk state: sum_t exp(a_cum[-1] - a_cum[t]) * b_t x_t^T  -> (N, P)
    decay = jnp.exp(a_cum[-1] - a_cum)                     # (Q,)
    bd = b * decay[:, None]
    state = jax.lax.dot_general(
        bd, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s_ref[0, 0] = state.astype(s_ref.dtype)


def ssd_intra_chunk(x, a, b, c, *, interpret: bool = False):
    """x: (BH, NC, Q, P); a: (BH, NC, Q); b, c: (BH, NC, Q, N) ->
    (y (BH, NC, Q, P) fp32, states (BH, NC, N, P) fp32)."""
    bh, nc, q, p = x.shape
    n = b.shape[-1]
    kernel = functools.partial(_ssd_chunk_kernel, q=q, n=n, p=p)
    grid = (bh, nc)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        )
    return pl.pallas_call(
        kernel,
        name="ssd_scan",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, q), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nc, q, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, nc, n, p), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(x, a, b, c)


def ssd_chunked_pallas(x, a, bmat, cmat, chunk: int, initial_state=None,
                       *, interpret: bool = True):
    """Drop-in for models.ssm.ssd_chunked with the intra-chunk part on
    the Pallas kernel. Shapes as in ssd_chunked: x (B, L, H, P),
    a (B, L, H), bmat/cmat (B, L, H, N)."""
    bsz, l, h, p = x.shape
    n = bmat.shape[-1]
    assert l % chunk == 0
    nc = l // chunk

    def flat(t, feat):
        # (B, L, H, F) -> (B*H, NC, Q, F)
        t = t.reshape(bsz, nc, chunk, h, feat)
        return t.transpose(0, 3, 1, 2, 4).reshape(bsz * h, nc, chunk, feat)

    xf = flat(x, p)
    bf = flat(bmat, n)
    cf = flat(cmat, n)
    af = a.reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2).reshape(
        bsz * h, nc, chunk
    ).astype(jnp.float32)

    y_diag, states = ssd_intra_chunk(xf, af, bf, cf, interpret=interpret)

    # inter-chunk recurrence (jnp; cheap and sequential)
    a_sum = af.sum(axis=2)                                 # (BH, NC)
    chunk_decay = jnp.exp(a_sum)
    s0 = (jnp.zeros((bsz * h, n, p), jnp.float32) if initial_state is None
          else initial_state.reshape(bsz * h, p, n).transpose(0, 2, 1)
          .astype(jnp.float32))

    def step(s, inp):
        dec, st = inp
        return s * dec[:, None, None] + st, s

    final, state_in = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0),
                   jnp.moveaxis(states, 1, 0)),
    )
    state_in = jnp.moveaxis(state_in, 0, 1)                # (BH, NC, N, P)

    # inter-chunk contribution: C @ state_in with left decay
    a_cum = jnp.cumsum(af, axis=2)                         # (BH, NC, Q)
    decay_in = jnp.exp(a_cum)
    y_off = jnp.einsum("ktqn,ktnp,ktq->ktqp", cf.astype(jnp.float32),
                       state_in, decay_in)

    y = (y_diag + y_off).reshape(bsz, h, nc, chunk, p).transpose(
        0, 2, 3, 1, 4
    ).reshape(bsz, l, h, p).astype(x.dtype)
    final = final.transpose(0, 2, 1).reshape(bsz, h, p, n)
    return y, final
