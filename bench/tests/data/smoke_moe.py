"""The program's paged MoE decoder (deepseek-moe layout): an architecture
added to a benchmark root as one new file, ``bench/archs/smoke_moe.py``.

Every layer: causal attention (``bench/reference/decoder.py``), then an
expert layer that reads the residual stream as the program's does, with
no norm before it. A token's router logits (hidden x experts) go through
a softmax; its top ``num_experts_per_tok`` experts, their gates
renormalised to sum 1, each apply a SwiGLU of width
``moe_intermediate_size``; ``n_shared_experts`` shared experts, one
SwiGLU of their summed width, apply to every token. The program's
capacity buffers (``capacity_factor``) hold every token at the sizes of
the configuration that names this module, so the reference drops none.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import decoder
from bench.sut import DTYPES, program_norm


def arch_config(config: dict, *, attn_impl: str = "pallas"):
    from repro.models.common import ArchConfig, MoEConfig

    dt = DTYPES[config["torch_dtype"]]
    return ArchConfig(
        name=config["name"], family="moe",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["moe_intermediate_size"],
        vocab_size=config["vocab_size"],
        qk_norm=bool(config["qk_norm"]), mlp="swiglu",
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=float(config["rms_norm_eps"]),
        moe=MoEConfig(num_experts=config["n_routed_experts"],
                      top_k=config["num_experts_per_tok"],
                      d_expert=config["moe_intermediate_size"],
                      num_shared=config["n_shared_experts"],
                      capacity_factor=float(config["capacity_factor"])),
        attn_impl=attn_impl, param_dtype=dt, compute_dtype=dt,
    )


def weight_shapes(config: dict) -> dict:
    n, d = config["num_hidden_layers"], config["hidden_size"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    e, v = config["head_dim"], config["vocab_size"]
    ne, f = config["n_routed_experts"], config["moe_intermediate_size"]
    fs = f * config["n_shared_experts"]
    layers = {
        "attn_norm": (n, d), "wq": (n, d, hq * e), "wk": (n, d, hkv * e),
        "wv": (n, d, hkv * e), "wo": (n, hq * e, d), "router": (n, d, ne),
        "w_gate": (n, ne, d, f), "w_up": (n, ne, d, f),
        "w_down": (n, ne, f, d),
    }
    if fs:
        layers.update(shared_gate=(n, d, fs), shared_up=(n, d, fs),
                      shared_down=(n, fs, d))
    shapes = {"embed": (v, d), "final_norm": (d,), "layers": layers}
    if not config["tie_word_embeddings"]:
        shapes["lm_head"] = (d, v)
    return shapes


def program_params(weights: dict, config: dict) -> dict:
    dt = DTYPES[config["torch_dtype"]]
    lay = weights["layers"]
    attn = {"norm": program_norm(lay["attn_norm"], dt), "wq": lay["wq"],
            "wk": lay["wk"], "wv": lay["wv"], "wo": lay["wo"]}
    ffn = {"router": lay["router"], "w_gate": lay["w_gate"],
           "w_up": lay["w_up"], "w_down": lay["w_down"]}
    if "shared_gate" in lay:
        ffn["shared"] = {"w_gate": lay["shared_gate"],
                         "w_up": lay["shared_up"],
                         "w_down": lay["shared_down"]}
    params = {"embed": weights["embed"],
              "final_norm": program_norm(weights["final_norm"], dt),
              "units": {"b0": {"attn": attn, "ffn": ffn}}}
    if "lm_head" in weights:
        params["unembed"] = weights["lm_head"]
    return params


def _experts(x, lw, config, precision):
    ne, k = config["n_routed_experts"], config["num_experts_per_tok"]
    probs = jax.nn.softmax(decoder.matmul(x, lw["router"], precision),
                           axis=-1)
    top, ids = jax.lax.top_k(probs, k)
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    # (S, experts): each token's gate on each expert, 0 where not chosen
    weight = jnp.sum(jax.nn.one_hot(ids, ne) * gates[..., None], axis=1)
    y = sum(weight[:, i:i + 1] * decoder.swiglu(
        x, lw["w_gate"][i], lw["w_up"][i], lw["w_down"][i], precision)
        for i in range(ne))
    if "shared_gate" in lw:
        y = y + decoder.swiglu(x, lw["shared_gate"], lw["shared_up"],
                               lw["shared_down"], precision)
    return y


def _layer(x, lw, config, precision):
    x = decoder.gqa_attention(x, lw, config, precision)
    return x + _experts(x, lw, config, precision)


def hidden(weights, tokens, config, precision):
    return decoder.scan_hidden(weights, tokens, config, precision, _layer)


_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "qk_norm", "rope_theta", "hidden_size",
         "n_routed_experts", "num_experts_per_tok")


def row_stats(weights, config, tokens, asked=None, *, precision="float32",
              length=None):
    return decoder.row_stats(hidden, _KEYS, weights, config, tokens, asked,
                             precision=precision, length=length)


def logits(weights, config, tokens, *, precision="float32"):
    return decoder.logits(hidden, _KEYS, weights, config, tokens,
                          precision=precision)


# Per token and layer: the four attention projections, the router, and the
# chosen and shared experts' SwiGLU matrices (2 operations per weight); the
# program's capacity buffers compute more, which is not work the step
# needs.

def matmul_flops_per_token(config: dict) -> int:
    d, hq = config["hidden_size"], config["num_attention_heads"]
    hkv, e = config["num_key_value_heads"], config["head_dim"]
    active = config["num_experts_per_tok"] + config["n_shared_experts"]
    per_layer = (d * hq * e + 2 * d * hkv * e + hq * e * d
                 + d * config["n_routed_experts"]
                 + 3 * d * config["moe_intermediate_size"] * active)
    return 2 * per_layer * config["num_hidden_layers"]


def attention_flops(config: dict, keys: int) -> int:
    return (4 * config["num_attention_heads"] * config["head_dim"] * keys
            * config["num_hidden_layers"])


def head_flops(config: dict) -> int:
    return 2 * config["hidden_size"] * config["vocab_size"]
