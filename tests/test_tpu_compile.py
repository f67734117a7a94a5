"""Compile the main path's kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(scoped VMEM overruns, unaligned tiles, programs past HBM). Shapes are the
policy's own decisions and qwen3-1.7b's published widths.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.policy import choose_attention_method
from repro.kernels import ops as kops
from repro.kernels.paged_decode_attention import (
    DECODE_VMEM_BUDGET,
    decode_pages_per_block,
)
from repro.models import build_model

HQ, HKV, E = 16, 8, 128  # qwen3-1.7b attention widths
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it.
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()  # no trace from an interpret-mode run is reused
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()  # nor is a chip trace reused by a later CPU test
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture
def compile_for_chip(one_chip, monkeypatch):
    """compile_(fn, *shapes) -> fn compiled for the described chip, which
    must hold a Pallas kernel. Steers ops' default interpret mode off."""
    monkeypatch.setattr(kops, "_default_interpret",
                        lambda interpret: False if interpret is None
                        else interpret)

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                for s in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    return compile_


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _custom_calls(compiled) -> set:
    """The custom calls of a compiled program by name, less the
    instruction's numeric suffix: the names a device trace's op events
    carry, which the benchmark's kernel readers look for."""
    return {re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%([\w\-.]+) = [^\n]*custom-call", compiled.as_text())}


@pytest.mark.parametrize("n,causal", [
    (2048, True), (8192, True), (32768, True),
    # the policy's boundaries: the largest resident N, the largest N
    # streamed at blk_q=128, and the first N streamed at blk_q=64
    (32256, False), (64000, True), (65536, False),
])
def test_policy_decision_compiles(compile_for_chip, n, causal):
    d = choose_attention_method(n_kv=n, e=E, itemsize=2, causal=causal)
    assert d.method != "flash"
    compile_for_chip(
        lambda q, k, v: kops.attention(q, k, v, causal=causal),
        _sds((1, HQ, n, E)), _sds((1, HKV, n, E)), _sds((1, HKV, n, E)))


def test_flash_compiles_at_32k(compile_for_chip):
    n = 32768
    compile_for_chip(
        lambda q, k, v: kops.attention(q, k, v, causal=True, method="flash"),
        _sds((1, HQ, n, E)), _sds((1, HKV, n, E)), _sds((1, HKV, n, E)))


BATCH, MAX_PAGES, POOL = 8, 128, 1025


@pytest.mark.parametrize("batch,page,max_pages,pool_pages,dtype", [
    (BATCH, 16, MAX_PAGES, POOL, jnp.bfloat16),
    (BATCH, 64, MAX_PAGES, POOL, jnp.bfloat16),
    # the benchmark cells: 16 (internlm2-1.8b) and 20 (qwen3-1.7b) slots
    # of 80 pages of 64 rows over a pool of 577, and the same with int8
    # pools (twice the pages a block, each dequantized to fp32)
    (16, 64, 80, 577, jnp.bfloat16), (20, 64, 80, 577, jnp.bfloat16),
    (16, 64, 80, 577, jnp.int8), (20, 64, 80, 577, jnp.int8),
])
def test_paged_decode_kernel_compiles(compile_for_chip, batch, page,
                                      max_pages, pool_pages, dtype):
    pool = _sds((HKV, pool_pages, page, E), dtype)
    shapes = [_sds((batch, HQ, E)), pool, pool,
              _sds((batch, max_pages), jnp.int32), _sds((batch,), jnp.int32)]
    fn = kops.paged_decode_attention
    if dtype == jnp.int8:
        scales = _sds((HKV, pool_pages), jnp.float32)
        shapes += [scales, scales]

        def fn(q, k, v, table, lens, ks, vs):
            return kops.paged_decode_attention(q, k, v, table, lens,
                                               k_scales=ks, v_scales=vs)
    compiled = compile_for_chip(fn, *shapes)
    # the compile holds the kernel to v5e's scoped VMEM; its K and V
    # double buffers take the fixed budget's share of it
    itemsize = jnp.dtype(dtype).itemsize
    ppb = decode_pages_per_block(HKV, page, E, itemsize, max_pages)
    assert 4 * ppb * HKV * page * E * itemsize <= DECODE_VMEM_BUDGET
    assert "paged_decode_attention" in _custom_calls(compiled)


@pytest.mark.parametrize("page", [16, 64])
def test_paged_verify_kernel_compiles(compile_for_chip, page):
    pool = _sds((HKV, POOL, page, E))
    compiled = compile_for_chip(
        kops.paged_verify_attention, _sds((BATCH, 4, HQ, E)), pool, pool,
        _sds((BATCH, MAX_PAGES), jnp.int32), _sds((BATCH,), jnp.int32),
        _sds((BATCH,), jnp.int32))
    assert "paged_verify_attention" in _custom_calls(compiled)


@pytest.mark.parametrize("page", [16, 64])
def test_paged_prefill_kernel_compiles(compile_for_chip, page):
    pool = _sds((HKV, POOL, page, E))
    compiled = compile_for_chip(
        kops.paged_prefill_attention, _sds((HQ, 512, E)), pool, pool,
        _sds((MAX_PAGES,), jnp.int32), _sds((), jnp.int32),
        _sds((), jnp.int32))
    assert "paged_prefill_attention" in _custom_calls(compiled)


def test_qwen3_paged_decode_step_compiles(compile_for_chip):
    """One whole paged decode step at published widths fits one chip."""
    cfg = dataclasses.replace(get_arch("qwen3-1.7b"), attn_impl="pallas")
    model = build_model(cfg)
    max_len, page = 2048, 16
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.make_cache(
        BATCH, max_len, cache_layout="paged", page_size=page))
    flat, tree = jax.tree.flatten((params, cache))

    def step(*leaves_and_args):
        p, c = jax.tree.unflatten(tree, leaves_and_args[:len(flat)])
        token, table, pos = leaves_and_args[len(flat):]
        return model.paged_decode_step(p, cfg, token, c, table, pos)

    compiled = compile_for_chip(
        step, *flat, _sds((BATCH, 1), jnp.int32),
        _sds((BATCH, max_len // page), jnp.int32), _sds((BATCH,), jnp.int32))
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
