"""Plain float32 forward of a dense GQA decoder, and its int8 control.

No cache, no kernels, no batching: one sequence, every layer in
``jax.numpy`` at ``default_matmul_precision("highest")``. It reads the
benchmark's weight layout (``bench/sut.py``) and imports nothing of the
program. The layer equations are the published ones (RMSNorm with a
multiplicative weight, optional per-head q/k RMSNorm, rotary embedding,
causal softmax attention with grouped K/V heads, SwiGLU MLP), with the
departures the program makes and the configuration file lists under
``program_departures``: the embedding is multiplied by sqrt(hidden), and
RoPE rotates interleaved pairs.

``precision="int8"`` is the control: every matrix product takes int8
operands (weights per output column, activations per row, symmetric
absmax) and K and V are rounded to int8 per row and head, the precision
below the configuration's bfloat16. It has to come out not correct.

``row_stats`` returns, for every row, the largest logit and the logits of
the tokens asked about, so that the caller can read by how much a served
token's logit lies below the reference's best.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LOGIT_BLOCK = 512  # rows of logits made at a time (bounds the (rows, V) block)


def _q8(x, axis):
    """Symmetric absmax int8 along ``axis``: (int8 values, f32 scales)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def _round_q8(x):
    """x rounded to int8 per last-axis row and scaled back."""
    q, scale = _q8(x, -1)
    return q.astype(jnp.float32) * scale


def _matmul(x, w, precision):
    """(rows, k) @ (k, n) in f32, or with int8 operands for the control."""
    if precision == "float32":
        return x @ w
    xq, xs = _q8(x, -1)
    wq, ws = _q8(w, 0)
    acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, E); interleaved pairs (2i, 2i+1) rotate by pos * theta^(-2i/E)."""
    s, _, e = x.shape
    freqs = theta ** (-jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _layer(x, lw, config, precision):
    s = x.shape[0]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    e, eps = config["head_dim"], config["rms_norm_eps"]
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    h = _rms(x, lw["attn_norm"], eps)
    q = _matmul(h, lw["wq"], precision).reshape(s, hq, e)
    k = _matmul(h, lw["wk"], precision).reshape(s, hkv, e)
    v = _matmul(h, lw["wv"], precision).reshape(s, hkv, e)
    if config["qk_norm"]:
        q = _rms(q, lw["q_norm"], eps)
        k = _rms(k, lw["k_norm"], eps)
    q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    if precision == "int8":
        k, v = _round_q8(k), _round_q8(v)
    g = hq // hkv
    qg = q.reshape(s, hkv, g, e)
    scores = jnp.einsum("qkge,ske->kgqs", qg, k) * e ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("kgqs,ske->qkge", p, v).reshape(s, hq * e)
    x = x + _matmul(o, lw["wo"], precision)
    h = _rms(x, lw["mlp_norm"], eps)
    up = jax.nn.silu(_matmul(h, lw["w_gate"], precision)) * _matmul(
        h, lw["w_up"], precision)
    return x + _matmul(up, lw["w_down"], precision)


def _hidden(weights, tokens, config, precision):
    """Final-norm hidden rows (S, hidden) and the output head (hidden, V)."""
    x = weights["embed"][tokens].astype(jnp.float32)
    x = x * jnp.sqrt(jnp.float32(config["hidden_size"]))

    def body(x, lw):
        return _layer(x, lw, config, precision), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    h = _rms(x, weights["final_norm"].astype(jnp.float32),
             config["rms_norm_eps"])
    head = (weights["lm_head"] if "lm_head" in weights
            else weights["embed"].T).astype(jnp.float32)
    return h, head


@functools.partial(jax.jit, static_argnames=("config_items", "precision"))
def _row_stats(weights, tokens, asked, config_items, precision):
    config = dict(config_items)
    with jax.default_matmul_precision("highest"):
        h, head = _hidden(weights, tokens, config, precision)
        s = tokens.shape[0]

        def block(i):
            hb = jax.lax.dynamic_slice_in_dim(h, i * LOGIT_BLOCK,
                                              LOGIT_BLOCK)
            logits = _matmul(hb, head, precision)
            ab = jax.lax.dynamic_slice_in_dim(asked, i * LOGIT_BLOCK,
                                              LOGIT_BLOCK, axis=1)
            picked = jnp.take_along_axis(logits[None], ab[..., None],
                                         axis=-1)[..., 0]
            return (jnp.max(logits, axis=-1),
                    jnp.argmax(logits, axis=-1).astype(jnp.int32), picked)

        mx, am, picked = jax.lax.map(block, jnp.arange(s // LOGIT_BLOCK))
    return (mx.reshape(s), am.reshape(s),
            picked.transpose(1, 0, 2).reshape(asked.shape[0], s))


@functools.partial(jax.jit, static_argnames=("config_items", "precision"))
def _logits(weights, tokens, config_items, precision):
    with jax.default_matmul_precision("highest"):
        h, head = _hidden(weights, tokens, dict(config_items), precision)
        return _matmul(h, head, precision)


# configuration keys the forward reads (hashable, so the jit can key on them)
_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "qk_norm", "rope_theta", "hidden_size")


def row_stats(weights, config, tokens, asked=None, *, precision="float32",
              length=None):
    """Run the forward over ``tokens`` (1-D) padded to ``length`` rows.

    Returns numpy arrays for the first ``len(tokens)`` rows: the largest
    logit, its token, and for each row of ``asked`` (K, len(tokens)) the
    logit of that token. Padding sits after the sequence, so causal rows
    before it are unchanged; ``length`` is rounded up to LOGIT_BLOCK.
    """
    import numpy as np

    n = len(tokens)
    length = max(n, length or n)
    length = -(-length // LOGIT_BLOCK) * LOGIT_BLOCK
    toks = np.zeros((length,), np.int32)
    toks[:n] = tokens
    k = 0 if asked is None else len(asked)
    ask = np.zeros((max(k, 1), length), np.int32)
    if k:
        ask[:, :n] = asked
    items = tuple((key, config[key]) for key in _KEYS)
    mx, am, picked = _row_stats(weights, jnp.asarray(toks), jnp.asarray(ask),
                                items, precision)
    mx, am, picked = jax.device_get((mx, am, picked))
    return mx[:n], am[:n], picked[:k, :n]


def logits(weights, config, tokens, *, precision="float32"):
    """Every row's logits (S, V) in float32: for small sizes (tests)."""
    items = tuple((key, config[key]) for key in _KEYS)
    return _logits(weights, jnp.asarray(tokens, jnp.int32), items, precision)
