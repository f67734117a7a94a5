"""Per-architecture smoke tests: reduced config of the same family,
one forward + one train step + one prefill/decode roundtrip on CPU;
asserts output shapes and finiteness.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch, get_smoke
from repro.models import build_model


def _batch(cfg, rng, b=2, s=16):
    tokens = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    if cfg.frontend is not None:
        batch["frontend"] = jax.random.normal(
            rng, (b, cfg.num_frontend_tokens, cfg.d_model), jnp.float32
        )
    return batch


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_forward_and_train_step(arch_id):
    cfg = get_smoke(arch_id)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    batch = _batch(cfg, rng)

    # forward
    kwargs = {}
    if cfg.frontend == "vision":
        kwargs["frontend_embeds"] = batch["frontend"]
    if cfg.encoder_layers:
        kwargs["encoder_out"] = model.encode(params, batch["frontend"])
    logits, aux = model.forward(params, batch["tokens"], cfg, **kwargs)
    b, s = batch["tokens"].shape
    extra = cfg.num_frontend_tokens if cfg.frontend == "vision" else 0
    assert logits.shape == (b, s + extra, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    # one SGD step: loss decreases-or-finite and grads are finite
    loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
    assert np.isfinite(float(loss))
    leaves = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in leaves)
    params2 = jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)
    loss2 = model.loss_fn(params2, batch)
    assert np.isfinite(float(loss2))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_prefill_decode_matches_forward(arch_id):
    """prefill(t[:k]) + decode steps == forward logits (teacher forcing)."""
    cfg = get_smoke(arch_id)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(1)
    params = model.init(rng)
    b, s, k = 2, 12, 8
    tokens = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)

    kwargs = {}
    enc_out = None
    if cfg.frontend == "vision":
        kwargs["frontend_embeds"] = jax.random.normal(
            rng, (b, cfg.num_frontend_tokens, cfg.d_model)
        )
    if cfg.encoder_layers:
        frames = jax.random.normal(
            rng, (b, cfg.num_frontend_tokens, cfg.d_model)
        )
        enc_out = model.encode(params, frames)
        kwargs["encoder_out"] = enc_out

    full_logits, _ = model.forward(params, tokens, cfg, **kwargs)
    extra = cfg.num_frontend_tokens if cfg.frontend == "vision" else 0

    pre_kwargs = dict(kwargs)
    last, cache = model.prefill(params, cfg, tokens[:, :k], max_len=s + extra,
                                **{k_: v for k_, v in pre_kwargs.items()})
    np.testing.assert_allclose(
        np.asarray(last[:, 0], np.float32),
        np.asarray(full_logits[:, extra + k - 1], np.float32),
        atol=2e-2, rtol=2e-2,
    )

    # decode the next tokens with teacher forcing; compare logits
    logits = last
    for i in range(k, s):
        logits, cache = model.decode_step(
            params, cfg, tokens[:, i:i + 1], cache,
            jnp.int32(extra + i),
        )
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(full_logits[:, extra + i], np.float32),
            atol=5e-2, rtol=5e-2,
            err_msg=f"{arch_id} step {i}",
        )


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_full_config_is_exact(arch_id):
    """The FULL configs match the assignment table (dims only; the full
    models are exercised via the dry-run with ShapeDtypeStructs)."""
    expected = {
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "internlm2-1.8b": (24, 2048, 16, 8, 8192, 92544),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
        "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "mamba2-130m": (24, 768, 12, 12, 0, 50280),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
    }[arch_id]
    cfg = get_arch(arch_id)
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected, (got, expected)
    assert cfg.param_count() > 0 and cfg.active_param_count() > 0
