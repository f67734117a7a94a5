"""A dense decoder with grouped-query attention: qwen3 and internlm2.

Everything the harness knows of this architecture: the program's
``ArchConfig`` for a configuration file, the benchmark's weight layout
and the same arrays in the program's tree, the plain float32 reference
with its int8 control, and the operations of a step.

Layer equations: RMSNorm with a multiplicative weight, optional per-head
q/k RMSNorm, rotary embedding, causal softmax attention with grouped K/V
heads, SwiGLU MLP (``bench/reference/decoder.py`` gives the parts and the
program's departures the reference copies).
"""

from __future__ import annotations

from bench.reference import decoder
from bench.sut import DTYPES, program_norm


def arch_config(config: dict, *, attn_impl: str = "pallas"):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.models.common import ArchConfig

    dt = DTYPES[config["torch_dtype"]]
    return ArchConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        qk_norm=bool(config["qk_norm"]), mlp="swiglu",
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=float(config["rms_norm_eps"]),
        attn_impl=attn_impl, param_dtype=dt, compute_dtype=dt,
    )


def weight_shapes(config: dict) -> dict:
    """The benchmark's layout: one stacked array per kind of matrix, norms
    as the published multiplicative weights."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    e, f, v = config["head_dim"], config["intermediate_size"], \
        config["vocab_size"]
    layers = {
        "attn_norm": (n, d), "wq": (n, d, hq * e), "wk": (n, d, hkv * e),
        "wv": (n, d, hkv * e), "wo": (n, hq * e, d), "mlp_norm": (n, d),
        "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
    }
    if config["qk_norm"]:
        layers.update(q_norm=(n, e), k_norm=(n, e))
    shapes = {"embed": (v, d), "final_norm": (d,), "layers": layers}
    if not config["tie_word_embeddings"]:
        shapes["lm_head"] = (d, v)
    return shapes


def program_params(weights: dict, config: dict) -> dict:
    """The same weights in the program's parameter tree."""
    dt = DTYPES[config["torch_dtype"]]
    lay = weights["layers"]
    attn = {"norm": program_norm(lay["attn_norm"], dt), "wq": lay["wq"],
            "wk": lay["wk"], "wv": lay["wv"], "wo": lay["wo"]}
    if config["qk_norm"]:
        attn.update(q_norm=program_norm(lay["q_norm"], dt),
                    k_norm=program_norm(lay["k_norm"], dt))
    ffn = {"norm": program_norm(lay["mlp_norm"], dt),
           "w_gate": lay["w_gate"], "w_up": lay["w_up"],
           "w_down": lay["w_down"]}
    params = {"embed": weights["embed"],
              "final_norm": program_norm(weights["final_norm"], dt),
              "units": {"b0": {"attn": attn, "ffn": ffn}}}
    if "lm_head" in weights:
        params["unembed"] = weights["lm_head"]
    return params


# ---- the plain reference ----

def _layer(x, lw, config, precision):
    x = decoder.gqa_attention(x, lw, config, precision)
    h = decoder.rms(x, lw["mlp_norm"], config["rms_norm_eps"])
    return x + decoder.swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"],
                              precision)


def hidden(weights, tokens, config, precision):
    return decoder.scan_hidden(weights, tokens, config, precision, _layer)


# configuration keys the forward reads
_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "qk_norm", "rope_theta", "hidden_size")


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
# Per token and layer: the four attention projections and the three SwiGLU
# matrices (2 operations per weight), and attention over its keys
# (4 * Hq * E a key). Per token whose logits are made: the output head,
# 2 * hidden * vocab. Norms, RoPE and softmax are left out (they run on
# the vector unit and are a small share).

def matmul_flops_per_token(config: dict) -> int:
    d, f = config["hidden_size"], config["intermediate_size"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    e = config["head_dim"]
    per_layer = d * hq * e + 2 * d * hkv * e + hq * e * d + 3 * d * f
    return 2 * per_layer * config["num_hidden_layers"]


def attention_flops(config: dict, keys: int) -> int:
    """All layers' attention operations for ``keys`` query-key pairs."""
    return (4 * config["num_attention_heads"] * config["head_dim"] * keys
            * config["num_hidden_layers"])


def head_flops(config: dict) -> int:
    return 2 * config["hidden_size"] * config["vocab_size"]
