"""The system under test: the model configuration, its weights, the engine.

What one architecture's layers need lives in its module,
``bench/archs/<arch>.py``, which the configuration file names
(``arch``): the program's ``ArchConfig``, the benchmark's weight layout
(``weight_shapes``), which the reference reads directly, and
``program_params``, which hands the same arrays to the program in its
tree. Here is what every architecture shares: weights made from the seed,
on the device and in the dtype they are served in, in one jitted call,
the check of the program's tree, and the engine at a cell's sizes.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
EMBED_RMS = 0.05  # RMS of an embedded row, after the sqrt(hidden) multiplier


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits
    too): both 32-bit words of a SeedSequence of the seed."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(a)), int(b))


def init_rule(name: str, shape: tuple):
    """The rule that makes the leaf ``name`` of ``shape`` from a standard
    normal draw ``z`` (``make_weights``)."""
    if "norm" in name:
        return lambda z: jnp.clip(1.0 + 0.1 * z, 0.5, 1.5)
    if "embed" in name:
        return lambda z: z * EMBED_RMS * shape[-1] ** -0.5
    return lambda z: z * shape[-2] ** -0.5


def make_weights(arch, config: dict, seed: int) -> dict:
    """Random weights in ``arch.weight_shapes(config)`` from ``seed``, made
    on the device in one jitted call.

    Matrices are normal with std fan_in**-0.5. The embedding's std is
    EMBED_RMS * hidden**-0.5, so that the program's sqrt(hidden) multiplier
    gives rows of RMS EMBED_RMS: with rows of RMS 1 a tied output head
    scores the input token sqrt(hidden) above the rest, and greedy decoding
    only repeats it. Norm weights are 1 + 0.1 * normal, clipped to
    [0.5, 1.5], so that they do work and ``w - 1`` is exact in bfloat16.
    An architecture whose leaves need another rule gives
    ``init_rule(name, shape)``: a function of the leaf's normal draw, or
    None where the rule above holds.
    """
    dt = DTYPES[config["torch_dtype"]]
    shapes = arch.weight_shapes(config)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(
        x, tuple))
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]
    own = getattr(arch, "init_rule", lambda name, shape: None)
    rules = [own(name, shape) or init_rule(name, shape)
             for shape, name in zip(flat, names)]

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = [rule(jax.random.normal(k, shape, jnp.float32)).astype(dt)
               for k, shape, rule in zip(keys, flat, rules)]
        return jax.tree.unflatten(tree, out)

    return jax.jit(make)(key_from_seed(seed))


def program_norm(w, dt):
    """A norm weight ``w`` in the program's tree: the program's RMSNorm
    multiplies by ``1 + scale``, so ``w`` goes over as ``w - 1`` (exact in
    bfloat16 for w in [0.5, 2))."""
    return (w.astype(jnp.float32) - 1.0).astype(dt)


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


def build_system(arch, config: dict, cell: dict, seed: int, *,
                 attn_impl: str = "pallas") -> System:
    """Weights from ``seed`` and a ``ContinuousBatchingEngine`` at the
    cell's deployment sizes, for the architecture module ``arch``; every
    other engine option at its default."""
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine

    model = build_model(arch.arch_config(config, attn_impl=attn_impl))
    weights = make_weights(arch, config, seed)
    params = arch.program_params(weights, config)
    check_params_tree(model, params)
    kwargs = {}
    if "chunk_size" in cell:
        kwargs["chunk_size"] = int(cell["chunk_size"])
    engine = ContinuousBatchingEngine(
        model, params, max_len=int(cell["max_len"]),
        batch_size=int(cell["slots"]), page_size=int(cell["page_size"]),
        num_pages=int(cell["pool_pages"]), **kwargs)
    return System(model=model, engine=engine, weights=weights)
