"""Operations and bytes the held experts' grouped matmuls need, per step
program (one pass of the model over its rows).

Bytes: each held expert's three matrices (gate, up: hidden x width;
down: width x hidden) read once per expert layer. Operations: 2 * 3 *
hidden * width for each expected routed row, a token making
``num_experts_per_tok * held / published`` rows in each expert layer.
Counted from the work, so the count stays fixed when a change alters the
kernel's tiles.
"""

from __future__ import annotations


def cost(config: dict, tokens: int, passes: int, itemsize: int = 2):
    """(operations, bytes) of every expert layer's held experts over
    ``tokens`` tokens in ``passes`` step programs."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    held = config["n_routed_experts"]
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    rows = (tokens * config["num_experts_per_tok"] * held
            / config["n_routed_experts_published"])
    flops = 2 * 3 * d * f * rows * layers
    nbytes = passes * held * 3 * d * f * itemsize * layers
    return flops, nbytes
