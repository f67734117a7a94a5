"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

For depth-dominant models (deepseek-coder's 62 layers) PP trades the TP
all-reduces for point-to-point ``ppermute`` traffic. The stacked layer
parameters (L, ...) are sharded onto S stages (axis 0); microbatches flow
through a rotating buffer; tick t: stage 0 ingests microbatch t, stage
S-1 emits microbatch t-S+1. Total ticks = M + S - 1; bubble fraction
(S-1)/(M+S-1).

This module is exercised by tests (vs the sequential reference) and by
the PP example; the default production config uses FSDP+TP, with PP as
the opt-in for deep models.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.ctx import pvary as _pvary


def pipelined_apply(params_stacked, x, body_fn, mesh: Mesh, *,
                    axis: str = "stage", num_microbatches: int):
    """y = body_fn(layer_params, x) applied over all L layers, pipelined.

    params_stacked: pytree with leading layer axis L (L % S == 0).
    x: (B, ...) global batch; B % num_microbatches == 0.
    body_fn: (layer_params, x) -> x, applied per layer.
    """
    s = mesh.shape[axis]
    m = num_microbatches
    b = x.shape[0]
    assert b % m == 0
    xs = x.reshape(m, b // m, *x.shape[1:])

    def run_local_layers(p_local, h):
        def step(h, p_layer):
            return body_fn(p_layer, h), None

        h, _ = jax.lax.scan(step, h, p_local)
        return h

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    def run(p_local, xs):
        stage = jax.lax.axis_index(axis)
        # mark carries device-varying up front so loop types stay stable
        buf = _pvary(jnp.zeros_like(xs[0]), (axis,))
        outs = _pvary(jnp.zeros_like(xs), (axis,))
        perm = [(i, (i + 1) % s) for i in range(s)]

        def tick(t, carry):
            buf, outs = carry
            inp = _pvary(xs[jnp.clip(t, 0, m - 1)], (axis,))
            buf = jnp.where(stage == 0, inp, buf)
            y = run_local_layers(p_local, buf)
            out_idx = t - (s - 1)
            write = jnp.logical_and(stage == s - 1,
                                    jnp.logical_and(out_idx >= 0,
                                                    out_idx < m))
            cand = jax.lax.dynamic_update_slice_in_dim(
                outs, y[None], jnp.clip(out_idx, 0, m - 1), axis=0
            )
            outs = jnp.where(write, cand, outs)
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, m + s - 1, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast via psum
        outs = outs * jnp.where(stage == s - 1, 1.0, 0.0).astype(outs.dtype)
        return jax.lax.psum(outs, axis)

    with jax.set_mesh(mesh):
        ys = run(params_stacked, xs)
    return ys.reshape(b, *x.shape[1:])


def sequential_apply(params_stacked, x, body_fn):
    def step(h, p_layer):
        return body_fn(p_layer, h), None

    h, _ = jax.lax.scan(step, x, params_stacked)
    return h
