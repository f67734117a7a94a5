"""Smoke run of the MAS kernels and the paged serving path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # KV-head-sharded engine on four chips

One process, run from the repository root. It refuses to run anywhere but
a TPU: there is no CPU or interpret-mode fallback. With no arguments it
runs three phases, and any failure exits non-zero:

1. kernels: ``repro.kernels.ops.attention`` (B=1, Hq=16, Hkv=8, E=128,
   causal, bf16) at N = 2048, 8192 and 32768, for the policy's pick,
   ``flash``, and each MAS variant the policy accepts. Each output is
   checked against the fp32 ``repro.kernels.ref.attention`` on a few
   256-row query blocks (a dense fp32 reference at N=32768 would need
   ~68 GB of scores). Then ``repro.kernels.ops.paged_decode_attention``
   at the benchmark's decode shapes (16 slots of 80 pages of 64 rows
   over a pool of 577), with bf16 pools and with int8 pools and their
   per-page scales, against the fp32 oracle over the same (dequantized)
   pages; and the same kernel in its latent (MLA) mode at the Moonlight
   cell's decode shapes (32 slots, a pool of 1200 latent pages).
2. serving: qwen3-1.7b at published widths, random weights from a seed,
   ``attn_impl="pallas"``, through ``ContinuousBatchingEngine``: 16
   requests (prompts of 128-1536 tokens, 32 new tokens each) at batch 8.
   Every request must finish with its full token count under a clean
   pool audit.
3. logits: one prefill chunk and one decode step of the same model,
   Pallas kernels against the XLA twins (``attn_impl="xla"``).

``--chips 4`` runs only the sharded path: ``ShardedContinuousBatchingEngine``
(shard=4, XLA twins, the only backend that partitions today) against the
one-chip engine on the same requests, token for token.

The earlier lines of output are host-clock set-up and run times, for
reading, not benchmark metrics. The last line is one JSON object naming
the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

SEED = 0
ARCH = "qwen3-1.7b"
BATCH, MAX_LEN, PAGE = 8, 2048, 16
N_REQUESTS, PROMPT_LENS, MAX_NEW = 16, (128, 1536), 32
# Pool: every slot can hold the longest request (98 pages) at once, plus
# the scratch page. The full max_len residency (1025 pages) would put the
# undonated mixed chunk+decode step at 16.6 GB on a 16 GiB chip.
POOL_PAGES = BATCH * -(-(PROMPT_LENS[1] + MAX_NEW) // PAGE) + 1
KERNEL_NS = (2048, 8192, 32768)
KERNEL_HEADS = (16, 8)  # (Hq, Hkv)
HEAD_DIM = 128
REF_ROWS = 256
# the paged decode kernel's check: internlm2-1.8b.reasoning's slots,
# pages a slot, page rows and pool pages
PAGED_SLOTS, PAGED_MAX_PAGES, PAGED_PAGE, PAGED_POOL = 16, 80, 64, 577
# the Moonlight cell's latent decode: 32 slots over a pool of 1200 pages,
# 16 heads; latent rank 512 + rope 64 = 576 values padded to 640
LATENT_SLOTS, LATENT_POOL, LATENT_HEADS = 32, 1200, 16
LATENT_DIMS = (576, 512, 640)      # (latent, rank = V width, pool width)
LATENT_SM_SCALE = (128 + 64) ** -0.5
# bf16 tolerance of the kernel tests (atol = rtol)
KERNEL_TOL = 3e-2
# Pallas against XLA-twin logits: max |difference| over max |logit|. Both
# backends round each layer's attention output to bf16 and then run the
# same 28 layers, so they differ by bf16 rounding, not by math.
LOGITS_TOL = 3e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int):
    """Print the devices; exit non-zero unless JAX sees ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    d = devices[0]
    log(f"devices: {devices}")
    log(f"platform={d.platform} device_kind={d.device_kind} "
        f"count={len(devices)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{d.platform!r}); no phase was run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPUs, "
                 f"found {len(devices)}")
    return devices


def _require_kernel(compiled, what: str) -> None:
    if "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError(f"{what}: no Pallas kernel in the compiled "
                           f"program")


def _timed(fn, *args):
    """(result, seconds): ``fn(*args)`` waited for on the host clock."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Phase 1: kernels
# ---------------------------------------------------------------------------


def kernel_methods(n: int, e: int) -> list[tuple[str, str]]:
    """(method argument, kernel) pairs to run at length ``n``: the policy's
    pick (``"auto"``), flash, and each MAS variant the policy accepts at
    the default tiling."""
    from repro.core.policy import (
        DEFAULT_VMEM_BUDGET,
        TilingConfig,
        choose_attention_method,
        mas_vmem_bytes,
    )

    pick = choose_attention_method(n_kv=n, e=e, itemsize=2, causal=True)
    runs = [("auto", pick.method)]
    t = TilingConfig()
    for name, resident in (("mas_resident", True), ("mas_streamed", False)):
        fits = mas_vmem_bytes(t.blk_q, t.blk_kv, n, e, 2,
                              resident) <= DEFAULT_VMEM_BUDGET
        if fits and name != pick.method:
            runs.append((name, name))
    if pick.method != "flash":
        runs.append(("flash", "flash"))
    return runs


def kernel_phase(ns=KERNEL_NS, heads=KERNEL_HEADS, e=HEAD_DIM,
                 ref_rows=REF_ROWS) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.kernels import ref

    hq, hkv = heads
    ref_block = jax.jit(ref.attention, static_argnames=("causal", "q_offset"))
    for n in ns:
        kq, kk, kv = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(SEED), n), 3)
        q = jax.random.normal(kq, (1, hq, n, e), jnp.bfloat16)
        k = jax.random.normal(kk, (1, hkv, n, e), jnp.bfloat16)
        v = jax.random.normal(kv, (1, hkv, n, e), jnp.bfloat16)
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
        starts = sorted({0, n // 2, n - ref_rows})
        want = {}
        for s in starts:
            want[s] = np.asarray(ref_block(
                q[:, :, s:s + ref_rows].astype(jnp.float32), kf, vf,
                causal=True, q_offset=s))
        for method, kernel in kernel_methods(n, e):
            t0 = time.perf_counter()
            compiled = kops.attention.lower(
                q, k, v, causal=True, method=method, interpret=False,
            ).compile()
            compile_s = time.perf_counter() - t0
            _require_kernel(compiled, f"N={n} {kernel}")
            out, run_s = _timed(compiled, q, k, v)
            out = np.asarray(out.astype(jnp.float32))
            err = 0.0
            for s in starts:
                got = out[:, :, s:s + ref_rows]
                np.testing.assert_allclose(
                    got, want[s], atol=KERNEL_TOL, rtol=KERNEL_TOL,
                    err_msg=f"N={n} {kernel} rows {s}:{s + ref_rows}")
                err = max(err, float(np.max(np.abs(got - want[s]))))
            pick = " (policy pick)" if method == "auto" else ""
            log(f"kernel N={n} {kernel}{pick}: max|out-ref|={err:.3e} on "
                f"rows {starts} (+{ref_rows}), tol {KERNEL_TOL}; "
                f"compile {compile_s:.2f}s, first call {run_s:.3f}s "
                f"(host clock)")


def paged_kernel_phase(slots=PAGED_SLOTS, max_pages=PAGED_MAX_PAGES,
                       page=PAGED_PAGE, pool_pages=PAGED_POOL,
                       heads=KERNEL_HEADS, e=HEAD_DIM,
                       interpret=False) -> None:
    """The paged decode kernel with bf16 and with int8 pools against the
    fp32 oracle over the pages it reads, at kv_len 0, 1, one page, a
    whole number of the kernel's blocks and one past, the full table,
    and random lengths."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.kernels.common import dequantize_q8, quantize_q8

    hq, hkv = heads
    s_max = max_pages * page
    rng = np.random.default_rng(SEED)
    lens = rng.integers(1, s_max + 1, size=slots)
    lens[:6] = (0, 1, page, 16 * page, 16 * page + 1, s_max)
    lens = jnp.asarray(lens, jnp.int32)
    table = jnp.asarray(rng.integers(1, pool_pages, size=(slots, max_pages)),
                        jnp.int32)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(kq, (slots, hq, e), jnp.bfloat16)
    k, v = (jax.random.normal(key, (hkv, pool_pages, page, e), jnp.bfloat16)
            for key in (kk, kv))
    axes = (-2, -1)  # one scale per (kv head, page)
    (k8, ks), (v8, vs) = quantize_q8(k, axes), quantize_q8(v, axes)

    def dense(pool):
        """(Hkv, P, page, E) -> each slot's (Hkv, S, E) cache, fp32."""
        return jnp.moveaxis(pool[:, table], 0, 1).reshape(
            slots, hkv, s_max, e).astype(jnp.float32)

    @jax.jit
    def oracle(kp, vp):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda q1, k1, v1, n: ref.decode_attention(
                q1[None], k1[None], v1[None], n)[0])(
                    q, dense(kp), dense(vp), lens)

    def kernel(q_, kp, vp, tbl, kv_lens, *scales):
        ksc, vsc = scales or (None, None)
        return kops.paged_decode_attention(q_, kp, vp, tbl, kv_lens,
                                           k_scales=ksc, v_scales=vsc,
                                           interpret=interpret)

    live = np.asarray(lens) > 0  # an empty slot's output is not attention
    for name, pools, scales, exact in (
            ("bf16", (k, v), (), (k, v)),
            ("int8", (k8, v8), (ks, vs),
             (dequantize_q8(k8, ks, axes), dequantize_q8(v8, vs, axes)))):
        args = (q, *pools, table, lens, *scales)
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        _require_kernel(compiled, f"paged decode {name}")
        out, run_s = _timed(compiled, *args)
        got = np.asarray(out.astype(jnp.float32))[live]
        want = np.asarray(oracle(*exact))[live]
        np.testing.assert_allclose(got, want, atol=KERNEL_TOL,
                                   rtol=KERNEL_TOL,
                                   err_msg=f"paged decode {name}")
        log(f"paged decode {name} pools: {slots} slots x {max_pages} pages "
            f"of {page} rows, pool {pool_pages}: max|out-ref|="
            f"{float(np.max(np.abs(got - want))):.3e}, tol {KERNEL_TOL}; "
            f"compile {compile_s:.2f}s, first call {run_s:.3f}s "
            f"(host clock)")


def latent_kernel_phase(slots=LATENT_SLOTS, max_pages=PAGED_MAX_PAGES,
                        page=PAGED_PAGE, pool_pages=LATENT_POOL,
                        heads=LATENT_HEADS, dims=LATENT_DIMS,
                        interpret=False) -> None:
    """The paged decode kernel in its latent (MLA) mode at the Moonlight
    cell's decode shapes: 16 query heads on one latent row per token, zero
    padded to the pool's width, V its first ``rank`` columns; against the
    fp32 oracle over the same pages, at the lengths of
    ``paged_kernel_phase``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.kernels import ref

    lat, rank, width = dims
    scale = LATENT_SM_SCALE
    s_max = max_pages * page
    blk = kops.decode_pages_per_block(1, page, width, 2, max_pages) * page
    rng = np.random.default_rng(SEED)
    lens = rng.integers(1, s_max + 1, size=slots)
    lens[:6] = (0, 1, page, blk, min(blk + 1, s_max), s_max)
    lens = jnp.asarray(lens, jnp.int32)
    table = jnp.asarray(rng.integers(1, pool_pages, size=(slots, max_pages)),
                        jnp.int32)
    kq, kp = jax.random.split(jax.random.PRNGKey(SEED))
    pad = ((0, 0),) * 3 + ((0, width - lat),)
    q = jnp.pad(jax.random.normal(kq, (slots, heads, lat), jnp.bfloat16),
                pad[1:])
    pool = jnp.pad(jax.random.normal(kp, (1, pool_pages, page, lat),
                                     jnp.bfloat16), pad)

    @jax.jit
    def oracle(pool):
        dense = jnp.moveaxis(pool[:, table], 0, 1).reshape(
            slots, 1, s_max, width).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda q1, k1, n: ref.decode_attention(
                q1[None], k1[None], k1[None, ..., :rank], n,
                sm_scale=scale)[0])(q, dense, lens)

    def kernel(q_, pool_, tbl, kv_lens):
        return kops.paged_decode_attention(q_, pool_, None, tbl, kv_lens,
                                           sm_scale=scale, v_dim=rank,
                                           interpret=interpret)

    args = (q, pool, table, lens)
    t0 = time.perf_counter()
    compiled = jax.jit(kernel).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    _require_kernel(compiled, "paged decode latent")
    out, run_s = _timed(compiled, *args)
    live = np.asarray(lens) > 0
    got = np.asarray(out.astype(jnp.float32))[live]
    want = np.asarray(oracle(pool))[live]
    np.testing.assert_allclose(got, want, atol=KERNEL_TOL, rtol=KERNEL_TOL,
                               err_msg="paged decode latent")
    log(f"paged decode latent pool: {slots} slots x {max_pages} pages of "
        f"{page} rows of {lat} (pool width {width}), pool {pool_pages}, "
        f"{heads} heads, V the first {rank}: max|out-ref|="
        f"{float(np.max(np.abs(got - want))):.3e}, tol {KERNEL_TOL}; "
        f"compile {compile_s:.2f}s, first call {run_s:.3f}s (host clock)")


# ---------------------------------------------------------------------------
# Phase 2: serving
# ---------------------------------------------------------------------------


def make_requests(vocab: int, n=N_REQUESTS, lens=PROMPT_LENS,
                  max_new=MAX_NEW):
    """Seeded requests; ``eos_id=-2`` is never sampled, so each request
    runs its full ``max_new`` tokens."""
    from repro.serving import Request

    rng = np.random.default_rng(SEED)
    plens = rng.integers(lens[0], lens[1] + 1, size=n)
    return [Request(rid=i,
                    prompt=rng.integers(3, vocab, size=int(p)).astype(
                        np.int32),
                    max_new_tokens=max_new, eos_id=-2)
            for i, p in enumerate(plens)]


def fresh(requests):
    from repro.serving import Request

    return [Request(**r.__dict__) for r in requests]


def init_params(model):
    import jax

    params, sec = _timed(jax.jit(model.init), jax.random.PRNGKey(SEED))
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"set-up: {n:,} parameters initialised from seed {SEED} in "
        f"{sec:.1f}s (host clock)")
    return params


def serve_checked(engine, requests, label: str):
    """Serve under a pool auditor; every request must finish in full."""
    from repro.serving import PoolAuditor, RequestState

    engine.auditor = PoolAuditor()
    out, sec = _timed(engine.serve, fresh(requests))
    bad = [r.rid for r in requests
           if engine.results[r.rid].state is not RequestState.FINISHED
           or len(out[r.rid]) != r.max_new_tokens]
    if bad:
        raise RuntimeError(f"{label}: requests {bad} did not finish with "
                           f"their full token count")
    n_tok = sum(len(t) for t in out.values())
    log(f"{label}: {len(requests)} requests finished, {n_tok} tokens, "
        f"pool audit clean over {engine.auditor.steps_checked} steps; "
        f"serve {sec:.1f}s (host clock, includes compiling the steps)")
    return out


def decode_step_memory(model, params, *, batch, max_len, page,
                       pages) -> None:
    """Compile one paged decode step on its own and print its memory."""
    import jax
    import jax.numpy as jnp

    cache = jax.eval_shape(lambda: model.make_cache(
        batch, max_len, cache_layout="paged", page_size=page,
        num_pages=pages))
    max_pages = -(-max_len // page)
    i32 = jnp.int32
    step = jax.jit(lambda p, c, t, tbl, pos: model.paged_decode_step(
        p, model.cfg, t, c, tbl, pos))
    t0 = time.perf_counter()
    compiled = step.lower(
        params, cache, jax.ShapeDtypeStruct((batch, 1), i32),
        jax.ShapeDtypeStruct((batch, max_pages), i32),
        jax.ShapeDtypeStruct((batch,), i32)).compile()
    sec = time.perf_counter() - t0
    _require_kernel(compiled, "decode step")
    m = compiled.memory_analysis()
    gb = 1e-9
    log(f"decode step (batch {batch}, max_len {max_len}, page {page}, "
        f"{pages} pages): "
        f"compile {sec:.1f}s; memory_analysis arguments "
        f"{m.argument_size_in_bytes * gb:.2f} GB, output "
        f"{m.output_size_in_bytes * gb:.2f} GB, temporaries "
        f"{m.temp_size_in_bytes * gb:.2f} GB, aliased "
        f"{m.alias_size_in_bytes * gb:.2f} GB")


def log_peak_memory(label: str) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"{label}: device 0 peak_bytes_in_use="
        f"{'not reported' if peak is None else f'{peak / 1e9:.2f} GB'}")


def serving_phase(cfg, requests, *, batch=BATCH, max_len=MAX_LEN,
                  page=PAGE, pages=POOL_PAGES):
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine

    model = build_model(cfg)
    params = init_params(model)
    decode_step_memory(model, params, batch=batch, max_len=max_len,
                       page=page, pages=pages)
    engine = ContinuousBatchingEngine(model, params, max_len=max_len,
                                      batch_size=batch, page_size=page,
                                      num_pages=pages)
    serve_checked(engine, requests, f"serving {cfg.name} "
                  f"attn_impl={cfg.attn_impl} batch {batch}")
    log_peak_memory("serving")
    return params


# ---------------------------------------------------------------------------
# Phase 3: Pallas kernels against the XLA twins, at the logits
# ---------------------------------------------------------------------------


def _logits_error(label: str, got, want) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise RuntimeError(f"{label}: non-finite logits")
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got - want))) / scale
    log(f"logits {label}: pallas vs xla max|diff|/max|logit|={err:.3e} "
        f"(max|logit|={scale:.3f}), tol {LOGITS_TOL}")
    if not err <= LOGITS_TOL:
        raise RuntimeError(f"logits {label}: error {err:.3e} > "
                           f"{LOGITS_TOL}")


def logits_phase(cfg, params, *, page=PAGE, chunk=512, ctx=1024) -> None:
    """Prefill two chunks of one sequence (the second ragged), then decode
    one token; the second chunk and the decode step run through both
    backends on the same cache and must agree."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model

    models = {impl: build_model(dataclasses.replace(cfg, attn_impl=impl))
              for impl in ("pallas", "xla")}
    max_pages = ctx // page
    table = jnp.arange(1, max_pages + 1, dtype=jnp.int32)  # page 0: scratch
    cache = models["pallas"].make_cache(1, ctx, cache_layout="paged",
                                        page_size=page)
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1), (1, 2 * chunk),
                                3, cfg.vocab_size, jnp.int32)
    cp = chunk // page

    def chunk_fn(m):
        return jax.jit(lambda p, c, t, ids, q0, n: m.prefill_chunk(
            p, m.cfg, t, c, table, ids, q0, n))

    def decode_fn(m):
        return jax.jit(lambda p, c, t, pos: m.paged_decode_step(
            p, m.cfg, t, c, table[None], pos))

    first = chunk_fn(models["pallas"])
    _, cache = first(params, cache, tokens[:, :chunk], table[:cp],
                     jnp.int32(0), jnp.int32(chunk))
    live = chunk - chunk // 4  # ragged second chunk
    out = {impl: chunk_fn(m)(params, cache, tokens[:, chunk:],
                             table[cp:2 * cp], jnp.int32(chunk),
                             jnp.int32(live))
           for impl, m in models.items()}
    _logits_error(f"prefill chunk (rows {chunk}..{chunk + live})",
                  out["pallas"][0], out["xla"][0])
    cache = out["pallas"][1]
    pos = jnp.array([chunk + live], jnp.int32)
    tok = tokens[:, chunk + live:chunk + live + 1]
    dec = {impl: decode_fn(m)(params, cache, tok, pos)[0]
           for impl, m in models.items()}
    _logits_error(f"decode step (position {chunk + live})",
                  dec["pallas"], dec["xla"])


# ---------------------------------------------------------------------------
# --chips 4: the KV-head-sharded engine against the one-chip engine
# ---------------------------------------------------------------------------


def sharded_phase(cfg, requests, *, shard=4, batch=BATCH, max_len=MAX_LEN,
                  page=PAGE, pages=POOL_PAGES) -> None:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.models import build_model
    from repro.serving import (
        ContinuousBatchingEngine,
        ShardedContinuousBatchingEngine,
    )

    model = build_model(cfg)
    params = init_params(model)
    kw = dict(max_len=max_len, batch_size=batch, page_size=page,
              num_pages=pages)
    one = ContinuousBatchingEngine(model, params, **kw)
    want = serve_checked(one, requests, f"one-chip {cfg.name} "
                         f"attn_impl={cfg.attn_impl}")
    del one
    # Replicate before building the sharded engine, so chip 0 holds at
    # most two copies of the weights at once.
    mesh = Mesh(np.asarray(jax.devices()[:shard]), ("model",))
    params = jax.device_put(params, NamedSharding(mesh, P()))
    sharded = ShardedContinuousBatchingEngine(model, params, shard=shard,
                                              **kw)
    got = serve_checked(sharded, requests, f"sharded x{shard} {cfg.name} "
                        f"attn_impl={cfg.attn_impl}")
    log_peak_memory(f"sharded x{shard}")
    diverged = [r.rid for r in requests
                if not np.array_equal(got[r.rid], want[r.rid])]
    if diverged:
        first = {rid: int(np.argmax(got[rid] != want[rid]))
                 for rid in diverged}
        raise RuntimeError(f"sharded x{shard} tokens differ from the "
                           f"one-chip engine: rid -> first differing "
                           f"position {first}")
    log(f"sharded x{shard}: all {len(requests)} token streams equal the "
        f"one-chip engine's")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the KV-head-sharded serving path")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.configs import get_arch
    from repro.launch.cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    arch = get_arch(ARCH)
    requests = make_requests(arch.vocab_size)
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(dataclasses.replace(arch, attn_impl="xla"), requests)
    else:
        kernel_phase()
        paged_kernel_phase()
        latent_kernel_phase()
        log(f"phase kernels done at {time.perf_counter() - t0:.1f}s")
        cfg = dataclasses.replace(arch, attn_impl="pallas")
        params = serving_phase(cfg, requests)
        log(f"phase serving done at {time.perf_counter() - t0:.1f}s")
        logits_phase(cfg, params)
        log(f"phase logits done at {time.perf_counter() - t0:.1f}s")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
