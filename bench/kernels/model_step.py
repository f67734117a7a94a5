"""Model operations per token of a dense GQA decoder (the work a step must
do, for the whole-step share of peak).

Per token and layer: the four attention projections and the three SwiGLU
matrices (2 operations per weight), and attention over ``context`` keys
(4 * Hq * E * context). Per token whose logits are made: the output head,
2 * hidden * vocab. Norms, RoPE and softmax are left out (they run on the
vector unit and are a small share).
"""

from __future__ import annotations


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
