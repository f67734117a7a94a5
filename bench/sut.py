"""The system under test: the model configuration, its weights, the engine.

Weights are made here from the seed, on the device and in the dtype they
are served in, in one jitted call. Their layout is the benchmark's own
(``weight_shapes``: one stacked array per kind of matrix, norms as the
published multiplicative weights), which the reference reads directly;
``program_params`` hands the same arrays to the program in its tree.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
EMBED_RMS = 0.05  # RMS of an embedded row, after the sqrt(hidden) multiplier


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


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits
    too): both 32-bit words of a SeedSequence of the seed."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(a)), int(b))


def weight_shapes(config: dict) -> dict:
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


def make_weights(config: dict, seed: int) -> dict:
    """Random weights from ``seed``, made on the device in one jitted call.

    Matrices are normal with std fan_in**-0.5. The embedding's std is
    EMBED_RMS * hidden**-0.5, so that the program's sqrt(hidden) multiplier
    gives rows of RMS EMBED_RMS: with rows of RMS 1 a tied output head
    scores the input token sqrt(hidden) above the rest, and greedy decoding
    only repeats it. Norm weights are 1 + 0.1 * normal, clipped to
    [0.5, 1.5], so that they do work and ``w - 1`` is exact in bfloat16.
    """
    dt = DTYPES[config["torch_dtype"]]
    shapes = weight_shapes(config)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(
        x, tuple))
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, shape, name in zip(keys, flat, names):
            z = jax.random.normal(k, shape, jnp.float32)
            if "norm" in name:
                w = jnp.clip(1.0 + 0.1 * z, 0.5, 1.5)
            elif "embed" in name:
                w = z * EMBED_RMS * shape[-1] ** -0.5
            else:
                w = z * shape[-2] ** -0.5
            out.append(w.astype(dt))
        return jax.tree.unflatten(tree, out)

    return jax.jit(make)(key_from_seed(seed))


def program_params(weights: dict, config: dict) -> dict:
    """The same weights in the program's parameter tree. The program's
    RMSNorm multiplies by ``1 + scale``, so a norm weight ``w`` is handed
    over as ``w - 1`` (exact in bfloat16 for w in [0.5, 2))."""
    dt = DTYPES[config["torch_dtype"]]
    lay = weights["layers"]

    def shift(w):
        return (w.astype(jnp.float32) - 1.0).astype(dt)

    attn = {"norm": shift(lay["attn_norm"]), "wq": lay["wq"],
            "wk": lay["wk"], "wv": lay["wv"], "wo": lay["wo"]}
    if config["qk_norm"]:
        attn.update(q_norm=shift(lay["q_norm"]), k_norm=shift(lay["k_norm"]))
    ffn = {"norm": shift(lay["mlp_norm"]), "w_gate": lay["w_gate"],
           "w_up": lay["w_up"], "w_down": lay["w_down"]}
    params = {"embed": weights["embed"],
              "final_norm": shift(weights["final_norm"]),
              "units": {"b0": {"attn": attn, "ffn": ffn}}}
    if "lm_head" in weights:
        params["unembed"] = weights["lm_head"]
    return params


def check_params_tree(model, params) -> None:
    """Refuse a tree that differs in structure, shape or dtype from the
    program's own ``init`` (a program change the adapter has not
    followed)."""
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (w.shape, w.dtype) != (g.shape, g.dtype)
            for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter tree")


@dataclasses.dataclass
class System:
    model: object
    engine: object
    weights: dict


def build_system(config: dict, cell: dict, seed: int, *,
                 attn_impl: str = "pallas") -> System:
    """Weights from ``seed`` and a ``ContinuousBatchingEngine`` at the
    cell's deployment sizes; every other engine option at its default."""
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine

    model = build_model(arch_config(config, attn_impl=attn_impl))
    weights = make_weights(config, seed)
    params = program_params(weights, config)
    check_params_tree(model, params)
    kwargs = {}
    if "chunk_size" in cell:
        kwargs["chunk_size"] = int(cell["chunk_size"])
    engine = ContinuousBatchingEngine(
        model, params, max_len=int(cell["max_len"]),
        batch_size=int(cell["slots"]), page_size=int(cell["page_size"]),
        num_pages=int(cell["pool_pages"]), **kwargs)
    return System(model=model, engine=engine, weights=weights)
