"""What every architecture's plain reference shares: float32 numerics,
their int8 control, and the reading of logits row by row.

No cache, no kernels, no batching: one sequence, in ``jax.numpy`` at
``default_matmul_precision("highest")``. An architecture's module
(``bench/archs/<arch>.py``) writes its layer equations with these parts
and hands ``row_stats`` its ``hidden`` function; it imports nothing of
the program. The parts follow the published equations (RMSNorm with a
multiplicative weight, rotary embedding, causal softmax attention with
grouped K/V heads, SwiGLU), with the departures the program makes and the
configuration files list under ``program_departures``: the embedding is
multiplied by sqrt(hidden), and RoPE rotates interleaved pairs.

``precision="int8"`` is the control: every matrix product takes int8
operands (weights per output column, activations per row, symmetric
absmax) and K and V are rounded to int8 per row and head, the precision
below the configurations' bfloat16. It has to come out not correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LOGIT_BLOCK = 512  # rows of logits made at a time (bounds the (rows, V) block)


def _q8(x, axis):
    """Symmetric absmax int8 along ``axis``: (int8 values, f32 scales)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def round_q8(x):
    """x rounded to int8 per last-axis row and scaled back."""
    q, scale = _q8(x, -1)
    return q.astype(jnp.float32) * scale


def matmul(x, w, precision):
    """(rows, k) @ (k, n) in f32, or with int8 operands for the control."""
    if precision == "float32":
        return x @ w
    xq, xs = _q8(x, -1)
    wq, ws = _q8(w, 0)
    acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (S, H, E); interleaved pairs (2i, 2i+1) rotate by pos * theta^(-2i/E)."""
    s, _, e = x.shape
    freqs = theta ** (-jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def gqa_attention(x, lw, config, precision):
    """The attention half of a layer: ``x`` plus the causal grouped-query
    attention of its RMSNorm, with optional per-head q/k RMSNorm. Reads
    ``attn_norm``, ``wq``, ``wk``, ``wv``, ``wo`` (and ``q_norm``,
    ``k_norm`` when ``config["qk_norm"]``) from ``lw``, in float32."""
    s = x.shape[0]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    e, eps = config["head_dim"], config["rms_norm_eps"]
    h = rms(x, lw["attn_norm"], eps)
    q = matmul(h, lw["wq"], precision).reshape(s, hq, e)
    k = matmul(h, lw["wk"], precision).reshape(s, hkv, e)
    v = matmul(h, lw["wv"], precision).reshape(s, hkv, e)
    if config["qk_norm"]:
        q = rms(q, lw["q_norm"], eps)
        k = rms(k, lw["k_norm"], eps)
    q, k = rope(q, config["rope_theta"]), rope(k, config["rope_theta"])
    if precision == "int8":
        k, v = round_q8(k), round_q8(v)
    g = hq // hkv
    qg = q.reshape(s, hkv, g, e)
    scores = jnp.einsum("qkge,ske->kgqs", qg, k) * e ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("kgqs,ske->qkge", p, v).reshape(s, hq * e)
    return x + matmul(o, lw["wo"], precision)


def swiglu(h, w_gate, w_up, w_down, precision):
    """silu(h @ w_gate) * (h @ w_up) @ w_down."""
    up = jax.nn.silu(matmul(h, w_gate, precision)) * matmul(h, w_up,
                                                            precision)
    return matmul(up, w_down, precision)


def scan_hidden(weights, tokens, config, precision, layer):
    """Final-norm hidden rows (S, hidden) and the output head (hidden, V)
    of a decoder whose layers, stacked in ``weights["layers"]``, are all
    ``layer(x, lw, config, precision)``."""
    x = weights["embed"][tokens].astype(jnp.float32)
    x = x * jnp.sqrt(jnp.float32(config["hidden_size"]))

    def body(x, lw):
        lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
        return layer(x, lw, config, precision), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    h = rms(x, weights["final_norm"].astype(jnp.float32),
            config["rms_norm_eps"])
    head = (weights["lm_head"] if "lm_head" in weights
            else weights["embed"].T).astype(jnp.float32)
    return h, head


@functools.partial(jax.jit,
                   static_argnames=("hidden", "config_items", "precision"))
def _row_stats(weights, tokens, asked, hidden, config_items, precision):
    config = dict(config_items)
    with jax.default_matmul_precision("highest"):
        h, head = hidden(weights, tokens, config, precision)
        s = tokens.shape[0]

        def block(i):
            hb = jax.lax.dynamic_slice_in_dim(h, i * LOGIT_BLOCK,
                                              LOGIT_BLOCK)
            logits = matmul(hb, head, precision)
            ab = jax.lax.dynamic_slice_in_dim(asked, i * LOGIT_BLOCK,
                                              LOGIT_BLOCK, axis=1)
            picked = jnp.take_along_axis(logits[None], ab[..., None],
                                         axis=-1)[..., 0]
            return (jnp.max(logits, axis=-1),
                    jnp.argmax(logits, axis=-1).astype(jnp.int32), picked)

        mx, am, picked = jax.lax.map(block, jnp.arange(s // LOGIT_BLOCK))
    return (mx.reshape(s), am.reshape(s),
            picked.transpose(1, 0, 2).reshape(asked.shape[0], s))


@functools.partial(jax.jit,
                   static_argnames=("hidden", "config_items", "precision"))
def _logits(weights, tokens, hidden, config_items, precision):
    with jax.default_matmul_precision("highest"):
        h, head = hidden(weights, tokens, dict(config_items), precision)
        return matmul(h, head, precision)


def row_stats(hidden, keys, weights, config, tokens, asked=None, *,
              precision="float32", length=None):
    """Run ``hidden(weights, tokens, config, precision) -> (rows, head)``
    over ``tokens`` (1-D) padded to ``length`` rows; ``keys`` are the
    configuration keys it reads (hashable, so that the jit keys on them).

    Returns numpy arrays for the first ``len(tokens)`` rows: the largest
    logit, its token, and for each row of ``asked`` (K, len(tokens)) the
    logit of that token. Padding sits after the sequence, so causal rows
    before it are unchanged; ``length`` is rounded up to LOGIT_BLOCK.
    """
    n = len(tokens)
    length = max(n, length or n)
    length = -(-length // LOGIT_BLOCK) * LOGIT_BLOCK
    toks = np.zeros((length,), np.int32)
    toks[:n] = tokens
    k = 0 if asked is None else len(asked)
    ask = np.zeros((max(k, 1), length), np.int32)
    if k:
        ask[:, :n] = asked
    items = tuple((key, config[key]) for key in keys)
    mx, am, picked = _row_stats(weights, jnp.asarray(toks), jnp.asarray(ask),
                                hidden, items, precision)
    mx, am, picked = jax.device_get((mx, am, picked))
    return mx[:n], am[:n], picked[:k, :n]


def logits(hidden, keys, weights, config, tokens, *, precision="float32"):
    """Every row's logits (S, V) in float32: for small sizes (tests)."""
    items = tuple((key, config[key]) for key in keys)
    return _logits(weights, jnp.asarray(tokens, jnp.int32), hidden, items,
                   precision)
