"""Integration: end-to-end training (loss decreases), checkpoint-restart
resume equality, serving engine vs teacher-forced forward, MoE capacity
semantics, pipeline parallelism vs sequential (subprocess, multi-device).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.launch.train import main as train_main
from repro.models import build_model
from repro.serving import Request, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_loss_decreases(tmp_path):
    losses = train_main([
        "--arch", "qwen3-1.7b", "--smoke", "--steps", "30",
        "--batch", "8", "--seq", "64", "--lr", "5e-3",
        "--metrics-file", str(tmp_path / "m.jsonl"),
    ])
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses[:3]
    with open(tmp_path / "m.jsonl") as f:
        assert len(f.readlines()) == 30


def test_train_restart_resumes_stream(tmp_path):
    """Train 20 steps with a checkpoint at 10; a fresh process restoring
    at 10 must see the same final loss as the uninterrupted run."""
    common = ["--arch", "internlm2-1.8b", "--smoke", "--steps", "20",
              "--total-steps", "20", "--batch", "4", "--seq", "32",
              "--save-every", "10"]
    full = train_main(common + ["--ckpt-dir", str(tmp_path / "a")])
    # interrupted run: first 10 steps only (same LR-schedule horizon)
    train_main(["--arch", "internlm2-1.8b", "--smoke", "--steps", "10",
                "--total-steps", "20", "--batch", "4", "--seq", "32",
                "--save-every", "10", "--ckpt-dir", str(tmp_path / "b")])
    resumed = train_main(common + ["--ckpt-dir", str(tmp_path / "b")])
    assert abs(full[-1] - resumed[-1]) < 5e-3, (full[-1], resumed[-1])


def test_train_with_int8_compression_converges():
    losses = train_main([
        "--arch", "qwen3-1.7b", "--smoke", "--steps", "25",
        "--batch", "8", "--seq", "64", "--lr", "5e-3",
        "--compression", "int8",
    ])
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2


def test_serving_engine_greedy_matches_forward():
    cfg = get_smoke("internlm2-1.8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_len=64, batch_size=2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, cfg.vocab_size, size=(9,)).astype(np.int32),
               rng.integers(3, cfg.vocab_size, size=(9,)).astype(np.int32),
               rng.integers(3, cfg.vocab_size, size=(5,)).astype(np.int32)]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4, eos_id=-2)
            for i, p in enumerate(prompts)]
    out = eng.serve(reqs)
    assert set(out) == {0, 1, 2}

    # check request 2 against manual greedy roll-out
    toks = prompts[2].tolist()
    for _ in range(4):
        logits, _ = model.forward(params, jnp.asarray([toks]), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    np.testing.assert_array_equal(out[2], np.array(toks[5:], np.int32))


def test_moe_capacity_drops_tokens():
    import dataclasses

    from repro.models.common import MoEConfig
    from repro.models.moe import init_moe, moe_ffn

    cfg = dataclasses.replace(
        get_smoke("deepseek-moe-16b"),
        moe=MoEConfig(num_experts=4, top_k=2, d_expert=16, num_shared=0,
                      capacity_factor=0.25),
    )
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, aux = moe_ffn(params, x, cfg)
    assert y.shape == x.shape
    assert float(aux) >= 1.0  # load-balance loss >= 1 at perfect balance
    # tight capacity must zero-out some tokens' expert contribution
    y_full, _ = moe_ffn(
        params, x,
        dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
        ),
    )
    assert not np.allclose(np.asarray(y), np.asarray(y_full))


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    """Runs in a subprocess with 4 fake devices (device count locks at
    first jax init)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipelined_apply, sequential_apply
mesh = jax.make_mesh((4,), ("stage",))
rng = np.random.default_rng(0)
L, D = 8, 16
params = jnp.asarray(rng.standard_normal((L, D, D)) * 0.2, jnp.float32)
x = jnp.asarray(rng.standard_normal((8, D)), jnp.float32)
body = lambda w, h: jnp.tanh(h @ w)
seq = sequential_apply(params, x, body)
pp = pipelined_apply(params, x, body, mesh, num_microbatches=4)
np.testing.assert_allclose(np.asarray(pp), np.asarray(seq), atol=1e-5, rtol=1e-5)
print("PP_OK")
"""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        timeout=300,
    )
    assert "PP_OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.slow
def test_dryrun_tiny_mesh_subprocess():
    """A reduced dry-run (2 cells, 8 fake devices) must lower+compile."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.launch.mesh import make_test_mesh
from repro.launch.dryrun import run_cell
mesh = make_test_mesh(8)
for arch, shape in [("qwen3-1.7b", "train_4k"), ("mamba2-130m", "decode_32k")]:
    r = run_cell(arch, shape, mesh, "tiny")
    assert r["ok"] and r["cost"].get("flops", 0) > 0
print("DRYRUN_OK")
"""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        timeout=900,
    )
    assert "DRYRUN_OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.slow
def test_ring_attention_matches_oracle():
    """Ring attention over 4 sequence shards == dense attention."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.ring_attention import ring_attention
from repro.kernels import ref
mesh = jax.make_mesh((4,), ("model",))
rng = np.random.default_rng(0)
for causal in (False, True):
    q = jnp.asarray(rng.standard_normal((2, 3, 64, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 3, 64, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 3, 64, 16)), jnp.float32)
    out = ring_attention(q, k, v, mesh, causal=causal)
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
print("RING_OK")
"""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        timeout=600,
    )
    assert "RING_OK" in r.stdout, r.stderr[-2000:]
