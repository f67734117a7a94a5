"""Latent attention with sigmoid-routed experts (deepseek_v3): Moonlight.

Everything the harness knows of this architecture: the program's
``ArchConfig`` for a configuration file, the benchmark's weight layout
and the same arrays in the program's tree, the plain float32 reference
with its int8 control, and the operations of a step.

Layer equations (deepseek_v3 with ``q_lora_rank`` null), each layer's
input ``x``:

- attention: ``h = RMSNorm(x)``; ``q = h Wq`` split per head into
  ``q_nope`` and ``q_pe``; ``[c, k_pe] = h Wkv_a``, ``c = RMSNorm(c)``;
  RoPE on ``q_pe`` and on ``k_pe`` (one rope key shared by every head);
  head h's key ``[c W_UK_h, k_pe]``, value ``c W_UV_h`` (``kv_b_proj``
  split into its K and V halves); causal softmax at scale
  ``(nope + rope)^-1/2``; ``x += o Wo``. The published, non-absorbed
  form: keys and values are expanded per head.
- the first ``first_k_dense_replace`` layers: ``x += SwiGLU(RMSNorm(x))``
  of width ``intermediate_size``.
- the other layers: ``h = RMSNorm(x)``; router logits ``h R`` over all
  ``n_routed_experts_published`` experts, sigmoid scores; the top
  ``num_experts_per_tok`` by score plus ``e_score_correction_bias``
  (``noaux_tc``; ``n_group`` = ``topk_group`` = 1, so no group limit);
  weights the chosen scores over their sum (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``x += sum over the chosen held experts of
  weight x SwiGLU_e(h) + SwiGLU_shared(h)`` (width ``moe_intermediate_size
  x n_shared_experts``). This chip holds experts [0, n_routed_experts):
  the absent experts' part is another chip's share and is left out, in
  the program and here alike.

The program's departures the reference copies are in the configuration
file (``program_departures``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import decoder
from bench.sut import DTYPES, program_norm

# std of the correction bias: about the gap between neighbouring sigmoid
# scores near a token's sixth choice (64 scores of sigmoid(N(0, 1))), so
# it changes some choices and leaves most
SCORE_BIAS_STD = 0.02


def _check(config: dict) -> None:
    if config.get("q_lora_rank") is not None:
        raise NotImplementedError("q compression (q_lora_rank)")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise NotImplementedError("group-limited routing (n_group > 1)")


def arch_config(config: dict, *, attn_impl: str = "pallas"):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.models.common import ArchConfig, MLAConfig, MoEConfig

    _check(config)
    dt = DTYPES[config["torch_dtype"]]
    return ArchConfig(
        name=config["name"], family="moe",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], mlp="swiglu",
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=float(config["rms_norm_eps"]),
        mla=MLAConfig(kv_lora_rank=config["kv_lora_rank"],
                      qk_nope_head_dim=config["qk_nope_head_dim"],
                      qk_rope_head_dim=config["qk_rope_head_dim"],
                      v_head_dim=config["v_head_dim"]),
        dense_lead=config["first_k_dense_replace"],
        moe=MoEConfig(num_experts=config["n_routed_experts_published"],
                      top_k=config["num_experts_per_tok"],
                      d_expert=config["moe_intermediate_size"],
                      num_shared=config["n_shared_experts"],
                      scoring=config["scoring_func"],
                      score_bias=config["topk_method"] == "noaux_tc",
                      norm_topk=bool(config["norm_topk_prob"]),
                      routed_scale=float(config["routed_scaling_factor"]),
                      held=(0, config["n_routed_experts"])),
        attn_impl=attn_impl, param_dtype=dt, compute_dtype=dt,
    )


def _attn_shapes(n: int, config: dict) -> dict:
    d, hq = config["hidden_size"], config["num_attention_heads"]
    r, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v = config["qk_nope_head_dim"], config["v_head_dim"]
    return {"attn_norm": (n, d), "wq": (n, d, hq * (nope + rope)),
            "wkv_a": (n, d, r + rope), "kv_norm": (n, r),
            "w_uk": (n, r, hq * nope), "w_uv": (n, r, hq * v),
            "wo": (n, hq * v, d), "mlp_norm": (n, d)}


def weight_shapes(config: dict) -> dict:
    """The benchmark's layout: the leading dense layers and the expert
    layers, each a stack of arrays, norms as the published
    multiplicative weights."""
    d, v = config["hidden_size"], config["vocab_size"]
    n_lead = config["first_k_dense_replace"]
    n = config["num_hidden_layers"] - n_lead
    ff, f = config["intermediate_size"], config["moe_intermediate_size"]
    ne, fs = config["n_routed_experts"], f * config["n_shared_experts"]
    lead = _attn_shapes(n_lead, config)
    lead.update(w_gate=(n_lead, d, ff), w_up=(n_lead, d, ff),
                w_down=(n_lead, ff, d))
    layers = _attn_shapes(n, config)
    layers.update(
        router=(n, d, config["n_routed_experts_published"]),
        e_score_correction_bias=(n, config["n_routed_experts_published"]),
        w_gate=(n, ne, d, f), w_up=(n, ne, d, f), w_down=(n, ne, f, d),
        shared_gate=(n, d, fs), shared_up=(n, d, fs), shared_down=(n, fs, d))
    shapes = {"embed": (v, d), "final_norm": (d,), "lead": lead,
              "layers": layers}
    if not config["tie_word_embeddings"]:
        shapes["lm_head"] = (d, v)
    return shapes


def init_rule(name: str, shape: tuple):
    """The correction bias: normal with std SCORE_BIAS_STD."""
    if "e_score_correction_bias" in name:
        return lambda z: z * SCORE_BIAS_STD
    return None


def program_params(weights: dict, config: dict) -> dict:
    """The same weights in the program's parameter tree."""
    dt = DTYPES[config["torch_dtype"]]

    def block(lay):
        attn = {"norm": program_norm(lay["attn_norm"], dt), "wq": lay["wq"],
                "wkv_a": lay["wkv_a"],
                "kv_norm": program_norm(lay["kv_norm"], dt),
                "w_uk": lay["w_uk"], "w_uv": lay["w_uv"], "wo": lay["wo"]}
        ffn = {"norm": program_norm(lay["mlp_norm"], dt),
               "w_gate": lay["w_gate"], "w_up": lay["w_up"],
               "w_down": lay["w_down"]}
        if "router" in lay:
            ffn.update(router=lay["router"],
                       score_bias=lay["e_score_correction_bias"],
                       shared={"w_gate": lay["shared_gate"],
                               "w_up": lay["shared_up"],
                               "w_down": lay["shared_down"]})
        return {"b0": {"attn": attn, "ffn": ffn}}

    params = {"embed": weights["embed"],
              "final_norm": program_norm(weights["final_norm"], dt),
              "lead": block(weights["lead"]),
              "units": block(weights["layers"])}
    if "lm_head" in weights:
        params["unembed"] = weights["lm_head"]
    return params


# ---- the plain reference ----

def _mla(x, lw, config, precision):
    """``x`` plus the causal latent attention of its RMSNorm, expanded."""
    s = x.shape[0]
    hq, eps = config["num_attention_heads"], config["rms_norm_eps"]
    r, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope = config["qk_nope_head_dim"]
    h = decoder.rms(x, lw["attn_norm"], eps)
    q = decoder.matmul(h, lw["wq"], precision).reshape(s, hq, nope + rope)
    kv = decoder.matmul(h, lw["wkv_a"], precision)
    c = decoder.rms(kv[:, :r], lw["kv_norm"], eps)
    q_pe = decoder.rope(q[..., nope:], config["rope_theta"])
    k_pe = decoder.rope(kv[:, None, r:], config["rope_theta"])[:, 0]
    if precision == "int8":  # the cached latent row, rounded to int8
        c, k_pe = decoder.round_q8(c), decoder.round_q8(k_pe)
    k_nope = decoder.matmul(c, lw["w_uk"], precision).reshape(s, hq, nope)
    v = decoder.matmul(c, lw["w_uv"], precision).reshape(s, hq, -1)
    scores = (jnp.einsum("qhn,khn->hqk", q[..., :nope], k_nope)
              + jnp.einsum("qhe,ke->hqk", q_pe, k_pe)) * (nope + rope) ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khv->qhv", p, v).reshape(s, -1)
    return x + decoder.matmul(o, lw["wo"], precision)


def _dense_layer(x, lw, config, precision):
    x = _mla(x, lw, config, precision)
    h = decoder.rms(x, lw["mlp_norm"], config["rms_norm_eps"])
    return x + decoder.swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"],
                              precision)


def route(h, lw, config, precision):
    """(S, hidden) -> each row's weight on each held expert (S, held): 0
    where not chosen."""
    k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(decoder.matmul(h, lw["router"], precision))
    _, ids = jax.lax.top_k(scores + lw["e_score_correction_bias"], k)
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if config["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * config["routed_scaling_factor"]
    held = jax.nn.one_hot(ids, config["n_routed_experts_published"])
    return jnp.sum(held * top[..., None], axis=1)[:, :config[
        "n_routed_experts"]]


def _moe_layer(x, lw, config, precision):
    x = _mla(x, lw, config, precision)
    h = decoder.rms(x, lw["mlp_norm"], config["rms_norm_eps"])
    weight = route(h, lw, config, precision)
    y = decoder.swiglu(h, lw["shared_gate"], lw["shared_up"],
                       lw["shared_down"], precision)
    for i in range(config["n_routed_experts"]):
        y = y + weight[:, i:i + 1] * decoder.swiglu(
            h, lw["w_gate"][i], lw["w_up"][i], lw["w_down"][i], precision)
    return x + y


def hidden(weights, tokens, config, precision):
    """Final-norm hidden rows and the output head: the lead layers, then
    the expert layers."""
    x = weights["embed"][tokens].astype(jnp.float32)
    x = x * jnp.sqrt(jnp.float32(config["hidden_size"]))

    def scan(fn, x, layers):
        def step(x, lw):
            lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
            return fn(x, lw, config, precision), None
        return jax.lax.scan(step, x, layers)[0]

    x = scan(_moe_layer, scan(_dense_layer, x, weights["lead"]),
             weights["layers"])
    h = decoder.rms(x, weights["final_norm"].astype(jnp.float32),
                    config["rms_norm_eps"])
    head = (weights["lm_head"] if "lm_head" in weights
            else weights["embed"].T).astype(jnp.float32)
    return h, head


# configuration keys the forward reads
_KEYS = ("num_attention_heads", "rms_norm_eps", "rope_theta", "hidden_size",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
         "n_routed_experts", "n_routed_experts_published")


def row_stats(weights, config, tokens, asked=None, *, precision="float32",
              length=None):
    """``decoder.row_stats`` of this architecture's forward."""
    return decoder.row_stats(hidden, _KEYS, weights, config, tokens, asked,
                             precision=precision, length=length)


def logits(weights, config, tokens, *, precision="float32"):
    """Every row's logits (S, V) in float32: for small sizes (tests)."""
    return decoder.logits(hidden, _KEYS, weights, config, tokens,
                          precision=precision)


# ---- the operations of a step ----
# Per token and layer, 2 operations per weight used: the q and latent
# projections, the absorbed decode's W_UK and W_UV (nope -> rank and
# rank -> v per head), Wo; the dense layers' SwiGLU; in an expert layer
# the router, the shared experts, and the held experts at their expected
# share of the token's rows, num_experts_per_tok x held / published.
# Attention in the absorbed form: 2 * heads * (latent + rank) a key and
# layer. The output head: 2 * hidden * vocab. Norms, RoPE, softmax and
# the expert sort are left out.

def _mla_weights(config: dict) -> int:
    d, hq = config["hidden_size"], config["num_attention_heads"]
    r, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v = config["qk_nope_head_dim"], config["v_head_dim"]
    return (d * hq * (nope + rope) + d * (r + rope) + hq * nope * r
            + hq * r * v + hq * v * d)


def expected_rows_per_token(config: dict) -> float:
    """Held-expert rows a token makes in one expert layer, on average."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["n_routed_experts_published"])


def matmul_flops_per_token(config: dict) -> float:
    d = config["hidden_size"]
    n_lead = config["first_k_dense_replace"]
    n = config["num_hidden_layers"] - n_lead
    f = config["moe_intermediate_size"]
    lead = _mla_weights(config) + 3 * d * config["intermediate_size"]
    expert = (_mla_weights(config) + d * config["n_routed_experts_published"]
              + 3 * d * f * config["n_shared_experts"]
              + 3 * d * f * expected_rows_per_token(config))
    return 2 * (n_lead * lead + n * expert)


def attention_flops(config: dict, keys: int) -> int:
    """All layers' absorbed attention operations for ``keys`` pairs."""
    lat = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return (2 * config["num_attention_heads"] * (lat + config["kv_lora_rank"])
            * keys * config["num_hidden_layers"])


def head_flops(config: dict) -> int:
    return 2 * config["hidden_size"] * config["vocab_size"]
