"""Mixture-of-Experts FFN: top-k routing with capacity buffers.

Scatter/gather dispatch (Switch-Transformer style) rather than one-hot
einsum dispatch: the (tokens, experts, capacity) one-hot never
materializes, so per-device transients stay small and the expert compute
is a clean batched einsum over (E, C, D) buffers that shards over the
'model' axis (expert parallelism). Over-capacity tokens are dropped for
the dropped slots (standard capacity semantics); the router's
load-balancing aux loss (Switch eq. 4) keeps drops rare.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig, dense_init, rms_norm, split_keys


def init_moe(key, cfg: ArchConfig):
    m = cfg.moe
    d, f, ne = cfg.d_model, m.d_expert, m.num_experts
    ks = split_keys(key, ["router", "gate", "up", "down", "sg", "su", "sd"])
    params = {
        "router": dense_init(ks["router"], (d, ne), dtype=cfg.param_dtype),
        "w_gate": dense_init(ks["gate"], (ne, d, f), in_axis=1,
                             dtype=cfg.param_dtype),
        "w_up": dense_init(ks["up"], (ne, d, f), in_axis=1,
                           dtype=cfg.param_dtype),
        "w_down": dense_init(ks["down"], (ne, f, d), in_axis=1,
                             dtype=cfg.param_dtype),
    }
    if m.num_shared:
        fs = f * m.num_shared
        params["shared"] = {
            "w_gate": dense_init(ks["sg"], (d, fs), dtype=cfg.param_dtype),
            "w_up": dense_init(ks["su"], (d, fs), dtype=cfg.param_dtype),
            "w_down": dense_init(ks["sd"], (fs, d), dtype=cfg.param_dtype),
        }
    return params


def _swiglu(x, wg, wu, wd):
    return jnp.einsum(
        "...f,fd->...d",
        jax.nn.silu(jnp.einsum("...d,df->...f", x, wg))
        * jnp.einsum("...d,df->...f", x, wu),
        wd,
    )


def _token_groups(b: int, s: int):
    """Group factorization aligned with the active sharding: tokens are
    dispatched within shard-local groups so the position cumsum and the
    buffer scatter never cross shards — expert exchange then lowers to
    all-to-all instead of all-reducing the (E, C, D) buffers
    (§Perf iter 8)."""
    from repro.distributed import ctx

    axes = ctx._axes() or {}
    g_b = 1
    for a in ctx.batch_axes():
        g_b *= axes.get(a, 1)
    if b % max(g_b, 1) != 0:
        g_b = 1
    g_s = axes.get("model", 1) if ctx.policy_kind() != "fsdp" else 1
    if s % max(g_s, 1) != 0:
        g_s = 1
    return g_b, g_s


def moe_ffn(params, x: jax.Array, cfg: ArchConfig):
    """x: (B, S, D) -> (y, aux_loss)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed import ctx

    m = cfg.moe
    b, s, d = x.shape
    dt = x.dtype
    g_b, g_s = _token_groups(b, s)
    g = g_b * g_s
    tg = (b * s) // g
    # (B, S, D) -> (G, Tg, D) with G blocks aligned to the shard grid
    tokens = (
        x.reshape(g_b, b // g_b, g_s, s // g_s, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(g, tg, d)
    )
    tokens = ctx.constrain(
        tokens,
        lambda axes: P(
            (ctx.batch_axes() + (("model",) if g_s > 1 else ()))
            if g > 1 else None,
        ),
    )
    capacity = max(4, int(m.capacity_factor * tg * m.top_k / m.num_experts))

    logits = jnp.einsum(
        "gtd,de->gte", tokens.astype(jnp.float32),
        params["router"].astype(jnp.float32),
    )
    probs = jax.nn.softmax(logits, axis=-1)                   # (G, Tg, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, m.top_k)     # (G, Tg, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Switch load-balance loss: E * sum_e f_e * p_e
    density = jnp.mean(
        jax.nn.one_hot(expert_ids[..., 0], m.num_experts), axis=(0, 1)
    )
    aux = m.num_experts * jnp.sum(density * jnp.mean(probs, axis=(0, 1)))

    # positions in per-group expert buffers (local cumsum)
    flat_ids = expert_ids.reshape(g, tg * m.top_k)            # (G, Tg*K)
    onehot = jax.nn.one_hot(flat_ids, m.num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=1) - 1                      # (G, Tg*K, E)
    pos_in_expert = jnp.take_along_axis(
        pos, flat_ids[..., None], axis=2
    )[..., 0]                                                 # (G, Tg*K)
    keep = pos_in_expert < capacity

    # scatter tokens into (G, E, C, D) buffers — vmapped per group so G
    # stays a real (sharded) dimension and the scatter is shard-local
    tok_rep = jnp.repeat(tokens, m.top_k, axis=1)             # (G, Tg*K, D)
    safe_pos = jnp.where(keep, pos_in_expert, 0)
    masked_tok = jnp.where(keep[..., None], tok_rep, 0)

    def _scatter_group(ids, pos_, tok):
        z = jnp.zeros((m.num_experts, capacity, d), dt)
        return z.at[ids, pos_].add(tok, mode="drop")

    buf = jax.vmap(_scatter_group)(flat_ids, safe_pos, masked_tok)

    # expert exchange: (G, E, C, D) -> (E, G, C, D); with G on the token
    # shards and E on 'model', this is the MoE all-to-all. Fully specify
    # both sides so GSPMD lowers one a2a instead of reshard copies
    # (§Perf iter 8 residual).
    def pre_spec(axes):
        g_ax = ctx.batch_axes() + (("model",) if g_s > 1 else ())
        return P(g_ax if g > 1 else None)

    def post_spec(axes):
        return P("model" if "model" in axes else None,
                 ctx.batch_axes() if g > 1 else None)

    buf = ctx.constrain(buf, pre_spec)
    buf = buf.transpose(1, 0, 2, 3)
    buf = ctx.constrain(buf, post_spec)

    # expert compute: batched swiglu over (E, G*C, D)
    h = jax.nn.silu(
        jnp.einsum("egcd,edf->egcf", buf, params["w_gate"].astype(dt))
    ) * jnp.einsum("egcd,edf->egcf", buf, params["w_up"].astype(dt))
    out_buf = jnp.einsum("egcf,efd->egcd", h, params["w_down"].astype(dt))
    out_buf = ctx.constrain(out_buf, post_spec)
    out_buf = out_buf.transpose(1, 0, 2, 3)                   # back: a2a
    out_buf = ctx.constrain(out_buf, pre_spec)

    # gather back and combine with gate weights (vmapped per group)
    gathered = jax.vmap(lambda o, ids, pos_: o[ids, pos_])(
        out_buf, flat_ids, safe_pos
    )                                                         # (G, Tg*K, D)
    gathered = jnp.where(keep[..., None], gathered, 0)
    combined = (
        gathered.reshape(g, tg, m.top_k, d)
        * gate_vals[..., None].astype(dt)
    ).sum(axis=2)
    combined = (
        combined.reshape(g_b, g_s, b // g_b, s // g_s, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, s, d)
    )
    tokens = tokens.reshape(g_b, g_s, b // g_b, s // g_s, d).transpose(
        0, 2, 1, 3, 4
    ).reshape(b, s, d)

    if m.num_shared:
        sh = params["shared"]
        combined = combined + _swiglu(
            tokens, sh["w_gate"].astype(dt), sh["w_up"].astype(dt),
            sh["w_down"].astype(dt),
        )
    return combined.reshape(b, s, d), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# dropless held-expert layer (kind "mla_moe"; DESIGN.md §12)
# ---------------------------------------------------------------------------

def init_routed(key, cfg: ArchConfig):
    """RMSNorm, a router over all ``num_experts``, the held experts'
    SwiGLU weights (num_held, ...) and the shared experts."""
    m = cfg.moe
    d, f, ne, nh = cfg.d_model, m.d_expert, m.num_experts, m.num_held
    ks = split_keys(key, ["router", "gate", "up", "down", "sg", "su", "sd"])
    params = {
        "norm": jnp.zeros((d,), cfg.param_dtype),
        "router": dense_init(ks["router"], (d, ne), dtype=cfg.param_dtype),
        "w_gate": dense_init(ks["gate"], (nh, d, f), in_axis=1,
                             dtype=cfg.param_dtype),
        "w_up": dense_init(ks["up"], (nh, d, f), in_axis=1,
                           dtype=cfg.param_dtype),
        "w_down": dense_init(ks["down"], (nh, f, d), in_axis=1,
                             dtype=cfg.param_dtype),
    }
    if m.score_bias:
        params["score_bias"] = jnp.zeros((ne,), cfg.param_dtype)
    if m.num_shared:
        fs = f * m.num_shared
        params["shared"] = {
            "w_gate": dense_init(ks["sg"], (d, fs), dtype=cfg.param_dtype),
            "w_up": dense_init(ks["su"], (d, fs), dtype=cfg.param_dtype),
            "w_down": dense_init(ks["sd"], (fs, d), dtype=cfg.param_dtype),
        }
    return params


def route(params, h, m):
    """h: (T, D) -> (expert ids (T, top_k), weights (T, top_k) f32).

    Float32 router logits over every expert, scored by sigmoid; the
    correction bias (when present) takes part in the choice only; the
    chosen scores, renormalised over the top_k chosen (held or not),
    times ``routed_scale``, weight the experts. The one routing that a
    configuration of this layer uses (deepseek_v3's ``noaux_tc``).
    """
    if m.scoring != "sigmoid" or not m.norm_topk:
        raise NotImplementedError(
            "routed_experts routes by sigmoid scores renormalised over "
            f"the chosen experts only (scoring={m.scoring!r}, "
            f"norm_topk={m.norm_topk})")
    logits = h.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    choice = scores
    if m.score_bias:
        choice = scores + params["score_bias"].astype(jnp.float32)
    _, ids = jax.lax.top_k(choice, m.top_k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return ids, weights * m.routed_scale


def routed_experts(params, x: jax.Array, cfg: ArchConfig):
    """Dropless expert layer that holds experts [lo, hi) of the router's
    ``num_experts`` (MoEConfig.held) -> (y, rows).

    x: (..., D). The layer normalises x, routes every token over all
    experts, and computes only the (token, held expert) rows: the rows
    are sorted by expert, unheld ones last, and each held expert's
    SwiGLU runs on its own rows in one grouped matmul per matrix
    (``lax.ragged_dot``, on the TPU the Mosaic kernel
    ``ragged-dot-none``; rows past the last group are neither computed
    nor kept), so the work follows the routed rows and no token is
    dropped. Each token then sums its own rows in choice order, whatever
    else is in the batch. The absent experts' part is left out (another
    chip's share); the shared experts apply to every token. ``rows``
    (int32) counts the rows computed.
    """
    with jax.named_scope("moe"):
        m = cfg.moe
        lo, _ = m.held_range
        nh, k = m.num_held, m.top_k
        shape, dt = x.shape, x.dtype
        h = rms_norm(x, params["norm"], cfg.norm_eps).reshape(-1, shape[-1])
        t = h.shape[0]
        ids, weights = route(params, h, m)
        local = ids.reshape(-1) - lo
        held = (local >= 0) & (local < nh)
        key = jnp.where(held, local, nh)                  # unheld sort last
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=nh + 1)[:nh].astype(jnp.int32)
        rows_in = h[order // k]

        def grouped(x, w):
            return jax.lax.ragged_dot(x, w.astype(dt), sizes)

        out = grouped(jax.nn.silu(grouped(rows_in, params["w_gate"]))
                      * grouped(rows_in, params["w_up"]), params["w_down"])
        # back to (token, choice) order: a permutation, so each token
        # sums its own top_k rows in choice order
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype))
        out = out[back].reshape(t, k, -1).astype(jnp.float32)
        keep = held.reshape(t, k)[..., None]
        y = jnp.sum(jnp.where(keep, out * weights[..., None], 0.0), axis=1)
        y = y.astype(dt)
        if m.num_shared:
            sh = params["shared"]
            y = y + _swiglu(h, sh["w_gate"].astype(dt), sh["w_up"].astype(dt),
                            sh["w_down"].astype(dt))
        return y.reshape(shape), jnp.sum(held, dtype=jnp.int32)
