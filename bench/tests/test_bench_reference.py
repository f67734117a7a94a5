"""The architectures' plain references against the program's paged path,
and the kernels' and steps' operation and byte counts against hand
counts. CPU, smoke sizes; the Pallas kernels run in interpret mode here.
The tests that take the ``smoke`` fixture run for both smoke
configurations: ``smoke`` (``bench/archs/dense_gqa.py``) and
``smoke-moe`` (an architecture that a test root adds as a new file,
``bench/tests/data/smoke_moe.py``)."""

import json
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import sut
from bench.kernels import paged_decode, paged_prefill
from bench.tests.smoke_root import BENCH, CONFIGS, DATA, smoke_arch, \
    smoke_config

PAGE, CHUNK, MAX_LEN = 16, 32, 128
SEED = 2**33 + 17


@pytest.fixture(scope="module", params=list(CONFIGS))
def smoke(request):
    arch, config = smoke_arch(request.param), smoke_config(request.param)
    weights = sut.make_weights(arch, config, seed=SEED)
    return arch, config, weights


def _paged_logits(arch, config, weights, tokens, n_prompt, n_decode):
    """Logits of the program's window path for one sequence: chunked
    prefill of ``n_prompt`` tokens (``prefill_chunk``, the paged prefill
    kernel), then ``n_decode`` decode steps fed the next tokens
    (``paged_decode_step``, the paged decode kernel). Returns
    {row: logits}."""
    from repro.models import build_model

    model = build_model(arch.arch_config(config, attn_impl="pallas"))
    params = arch.program_params(weights, config)
    sut.check_params_tree(model, params)
    max_pages = MAX_LEN // PAGE
    cache = model.make_cache(1, MAX_LEN, cache_layout="paged",
                             page_size=PAGE)
    table = jnp.arange(1, max_pages + 1, dtype=jnp.int32)  # 0: scratch
    out = {}
    for q0 in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - q0)
        chunk = np.ones((1, CHUNK), np.int32)
        chunk[0, :n] = tokens[q0:q0 + n]
        ids = table[q0 // PAGE:(q0 + CHUNK) // PAGE]
        last, cache = model.prefill_chunk(params, model.cfg,
                                          jnp.asarray(chunk), cache, table,
                                          ids, jnp.int32(q0), jnp.int32(n))
        out[q0 + n - 1] = np.asarray(last[0])
    for i in range(n_decode):
        pos = n_prompt + i
        logits, cache = model.paged_decode_step(
            params, model.cfg, jnp.asarray(tokens[pos:pos + 1])[None], cache,
            table[None], jnp.asarray([pos], jnp.int32))
        out[pos] = np.asarray(logits[0, 0])
    return out


def test_reference_matches_paged_prefill_and_decode(smoke):
    arch, config, weights = smoke
    rng = np.random.default_rng(3)
    tokens = rng.integers(3, config["vocab_size"], 48).astype(np.int32)
    got = _paged_logits(arch, config, weights, tokens, n_prompt=40,
                        n_decode=6)
    want = np.asarray(arch.logits(weights, config, tokens[:46]))
    scale = np.max(np.abs(want))
    for row, g in got.items():
        # both sides compute in float32; they differ by the order of
        # float32 sums (paged online softmax against one dense softmax)
        np.testing.assert_allclose(g, want[row], atol=1e-4 * scale, rtol=0)


def test_row_stats_reads_the_reference_logits(smoke):
    arch, config, weights = smoke
    tokens = np.arange(3, 3 + 40, dtype=np.int32)
    full = np.asarray(arch.logits(weights, config, tokens))
    asked = np.stack([np.roll(tokens, -1), np.zeros_like(tokens)])
    mx, am, picked = arch.row_stats(weights, config, tokens, asked,
                                    length=100)
    np.testing.assert_allclose(mx, full.max(-1), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(am, full.argmax(-1))
    np.testing.assert_allclose(
        picked, np.take_along_axis(full.T, asked, axis=0), rtol=1e-5,
        atol=1e-5)


def test_int8_control_departs_from_the_reference(smoke):
    arch, config, weights = smoke
    tokens = np.arange(3, 3 + 40, dtype=np.int32)
    f32 = np.asarray(arch.logits(weights, config, tokens))
    i8 = np.asarray(arch.logits(weights, config, tokens, precision="int8"))
    err = np.max(np.abs(i8 - f32)) / np.max(np.abs(f32))
    assert 1e-3 < err < 0.2, err


def test_weights_are_made_from_the_seed(smoke):
    arch, config, weights = smoke
    again = sut.make_weights(arch, config, seed=SEED)
    other = sut.make_weights(arch, config, seed=SEED + 1)
    for a, b, c in zip(jax.tree.leaves(weights), jax.tree.leaves(again),
                       jax.tree.leaves(other)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_dense_arch_keeps_the_parent_weights_logits_and_counts():
    """The smoke configuration's weights, its reference's largest logits
    and both published configurations' step counts, as the parent commit
    of ``bench/archs/`` made them (``data/smoke_pin.json``). The weights'
    sums are exact (``math.fsum``). The logits were bitwise equal on one
    machine; the test allows float32 rounding (2e-7 relative was seen
    between XLA's threaded and single-threaded CPU matmuls)."""
    pin = json.loads((DATA / "smoke_pin.json").read_text())
    arch, config = smoke_arch("smoke"), smoke_config("smoke")
    weights = sut.make_weights(arch, config, seed=pin["seed"])
    sums = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(weights)[0]:
        a = np.asarray(leaf, np.float64).ravel()
        sums[jax.tree_util.keystr(path)] = [math.fsum(a), math.fsum(a * a)]
    assert sums == pin["weight_sums"]
    mx, am, _ = arch.row_stats(weights, config,
                               np.asarray(pin["tokens"], np.int32))
    np.testing.assert_array_equal(am, pin["argmax"])
    np.testing.assert_allclose(mx, np.asarray(pin["max_logit"], np.float32),
                               rtol=1e-6, atol=0)
    keys = pin["step_flops"]["keys"]
    for name in ("qwen3-1.7b", "internlm2-1.8b"):
        c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        assert [arch.matmul_flops_per_token(c), arch.attention_flops(c, keys),
                arch.head_flops(c)] == pin["step_flops"][name]


def test_an_architecture_may_give_its_own_init_rule():
    """``make_weights`` takes an architecture's ``init_rule`` for the
    leaves it names, and the shared rule for the rest; each leaf keeps
    the draw it had."""
    base = smoke_arch("smoke")
    config = smoke_config("smoke")
    own = types.SimpleNamespace(
        weight_shapes=base.weight_shapes,
        init_rule=lambda name, shape: ((lambda z: 3.0 * z)
                                       if name.endswith("['wq']") else None))
    a = sut.make_weights(base, config, seed=SEED)
    b = sut.make_weights(own, config, seed=SEED)
    scale = config["hidden_size"] ** -0.5
    np.testing.assert_allclose(np.asarray(b["layers"]["wq"]),
                               np.asarray(a["layers"]["wq"]) * 3.0 / scale,
                               rtol=1e-6)
    for path in (("layers", "wk"), ("layers", "attn_norm"), ("embed",)):
        x, y = a, b
        for k in path:
            x, y = x[k], y[k]
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


HAND = {"num_attention_heads": 16, "num_key_value_heads": 8,
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
        "vocab_size": 151936, "num_hidden_layers": 28}


def test_paged_decode_counts_by_hand():
    # two sequences of 100 and 64 keys, pages of 64: 2 + 1 live pages
    flops, nbytes = paged_decode.cost(HAND, [100, 64], 64)
    assert flops == 4 * 16 * 128 * 164
    kv = 2 * (2 + 1) * 64 * 8 * 128 * 2      # K and V, live pages, bf16
    qo = 2 * 2 * 16 * 128 * 2                # q and out rows of each
    assert nbytes == kv + qo


def test_paged_prefill_counts_by_hand():
    # 3 live rows from position 10: they see 11, 12 and 13 keys
    flops, nbytes = paged_prefill.cost(HAND, 10, 3, 64)
    assert flops == 4 * 16 * 128 * (11 + 12 + 13)
    assert nbytes == (2 * 1 * 64 * 8 * 128 + 2 * 3 * 16 * 128) * 2
    # a chunk from 0 covers the causal triangle
    assert paged_prefill.cost(HAND, 0, 64, 64)[0] == \
        4 * 16 * 128 * (64 * 65 // 2)


def test_model_step_counts_by_hand():
    arch = smoke_arch("smoke")
    per_layer = (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
                 + 3 * 2048 * 6144)
    assert arch.matmul_flops_per_token(HAND) == 2 * 28 * per_layer
    assert arch.attention_flops(HAND, 5) == 4 * 16 * 128 * 5 * 28
    assert arch.head_flops(HAND) == 2 * 2048 * 151936


def test_moe_step_counts_by_hand():
    """deepseek-moe-16b's published sizes: 16 heads of 128 over hidden
    2048, 64 routed experts of width 1408 (6 chosen) and 2 shared, 28
    layers."""
    arch = smoke_arch("smoke-moe")
    moe = {"num_attention_heads": 16, "num_key_value_heads": 16,
           "head_dim": 128, "hidden_size": 2048, "n_routed_experts": 64,
           "num_experts_per_tok": 6, "n_shared_experts": 2,
           "moe_intermediate_size": 1408, "vocab_size": 102400,
           "num_hidden_layers": 28}
    per_layer = (4 * 2048 * 2048 + 2048 * 64 + (6 + 2) * 3 * 2048 * 1408)
    assert arch.matmul_flops_per_token(moe) == 2 * 28 * per_layer
    assert arch.attention_flops(moe, 5) == 4 * 16 * 128 * 5 * 28
    assert arch.head_flops(moe) == 2 * 2048 * 102400


def test_int8_control_is_further_from_the_reference_than_bf16():
    """The control sits below the configuration's precision: at a size a
    test can hold, the int8 reference's logits lie further from the
    float32 reference than the bfloat16 program's paged path does. (Dense
    only: in the MoE block a bfloat16 router can pick another expert than
    the float32 reference where two experts nearly tie, and one such row
    outweighs the rest.)"""
    arch, config = smoke_arch("smoke"), smoke_config("smoke")
    config.update(torch_dtype="bfloat16", vocab_size=2048, hidden_size=128,
                  head_dim=32, intermediate_size=256)
    for seed in (1, 2):
        weights = sut.make_weights(arch, config, seed=seed)
        tokens = np.random.default_rng(seed).integers(
            3, config["vocab_size"], 48).astype(np.int32)
        got = _paged_logits(arch, config, weights, tokens, n_prompt=40,
                            n_decode=6)
        rows = sorted(got)
        ref = np.asarray(arch.logits(weights, config, tokens[:46]))
        ctl = np.asarray(arch.logits(weights, config, tokens[:46],
                                     precision="int8"))
        prog_err = np.mean([np.abs(got[r] - ref[r]).mean() for r in rows])
        ctl_err = np.mean([np.abs(ctl[r] - ref[r]).mean() for r in rows])
        # measured at this size on the CPU: 3.4 to 3.8 times
        assert ctl_err > 2 * prog_err, (seed, ctl_err, prog_err)
