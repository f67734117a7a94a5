"""Fault-tolerant training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Features wired in: checkpoint/restart (restore-latest on boot, atomic
async saves), deterministic seekable data (resume == no-failure stream),
straggler detection (step-time EMA watchdog + heartbeat files),
gradient compression, mesh selection. On the CPU container this drives
the ~100M-class end-to-end example; on a fleet the same file is the
per-host entrypoint (jax.distributed.initialize is a no-op here).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch, get_smoke
from repro.data import SyntheticLMData
from repro.distributed import sharding as shd
from repro.distributed.compression import init_error_feedback
from repro.distributed.elastic import StepTimer, Watchdog
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import (
    make_production_mesh,
    make_single_device_mesh,
    make_test_mesh,
)
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import OptConfig, adamw_init


def build_mesh(kind: str):
    if kind == "1dev":
        return make_single_device_mesh()
    if kind == "tiny":
        return make_test_mesh(8)
    if kind == "single":
        return make_production_mesh(multi_pod=False)
    if kind == "multi":
        return make_production_mesh(multi_pod=True)
    raise ValueError(kind)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config of the same family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--total-steps", type=int, default=None,
                    help="LR-schedule horizon (defaults to --steps); set "
                    "explicitly when a run will stop early and resume")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="1dev",
                    choices=["1dev", "tiny", "single", "multi"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--d-model", type=int, default=None,
                    help="override width (e.g. ~100M example)")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-file", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    overrides = {}
    if args.d_model:
        overrides["d_model"] = args.d_model
        overrides["head_dim"] = max(8, args.d_model // cfg.num_heads)
        overrides["d_ff"] = (args.d_model * 4) if cfg.d_ff else 0
        if cfg.lru_width:
            overrides["lru_width"] = args.d_model
    if args.layers:
        overrides["num_layers"] = args.layers
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    mesh = build_mesh(args.mesh)
    model = build_model(cfg)
    horizon = args.total_steps or args.steps
    opt_cfg = OptConfig(lr=args.lr, warmup_steps=min(20, horizon // 10),
                        total_steps=horizon)
    data = SyntheticLMData(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=17,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        frontend_tokens=cfg.num_frontend_tokens if cfg.frontend else 0,
        d_model=cfg.d_model,
    )

    with jax.set_mesh(mesh):
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        p_specs = shd.param_specs(params, mesh)
        params = jax.device_put(params, shd.named(mesh, p_specs))
        opt_state = adamw_init(params)
        if args.compression == "int8":
            opt_state["err"] = init_error_feedback(params)

        start_step = 0
        ckpt = None
        if args.ckpt_dir:
            ckpt = CheckpointManager(args.ckpt_dir, keep=3)
            state = {"params": params, "opt": opt_state}
            step_found, restored = ckpt.restore_latest(state)
            if step_found is not None:
                params = restored["params"]
                opt_state = restored["opt"]
                start_step = step_found
                print(f"[train] restored checkpoint at step {start_step}")

        step_fn = jax.jit(
            make_train_step(model, opt_cfg, args.compression
                            if args.compression != "none" else None),
            donate_argnums=(0, 1),
        )

        timer = StepTimer()
        watchdog = (Watchdog(os.path.join(args.ckpt_dir, "hb"))
                    if args.ckpt_dir else None)
        metrics_f = open(args.metrics_file, "a") if args.metrics_file else None
        worker = f"proc{jax.process_index()}"

        losses = []
        for step in range(start_step, args.steps):
            t0 = time.time()
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
            params, opt_state, m = step_fn(params, opt_state, batch)
            loss = float(m["loss"])
            dt = time.time() - t0
            slow = timer.observe(dt)
            losses.append(loss)
            if watchdog:
                watchdog.beat(worker, step)
            if slow:
                print(f"[train] step {step}: straggler step "
                      f"({dt:.2f}s vs ema {timer.ema:.2f}s)")
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step} loss={loss:.4f} "
                      f"gnorm={float(m['grad_norm']):.3f} "
                      f"lr={float(m['lr']):.2e} {dt:.2f}s", flush=True)
            if metrics_f:
                metrics_f.write(json.dumps(
                    {"step": step, "loss": loss, "dt": dt}) + "\n")
            if ckpt and (step + 1) % args.save_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state},
                          blocking=False)
        if ckpt:
            ckpt.save(args.steps, {"params": params, "opt": opt_state},
                      blocking=True)
        if metrics_f:
            metrics_f.close()
        print(f"[train] done. first loss={losses[0]:.4f} "
              f"last loss={losses[-1]:.4f}")
        return losses


if __name__ == "__main__":
    main()
