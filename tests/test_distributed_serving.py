"""Multi-chip paged serving (DESIGN.md §11).

Four layers are pinned here:

* sharding rules: ``cache_specs(layout="paged")`` understands the
  Hkv-leading page pools + int8 scale side-tables, and the dense layout
  is unchanged;
* the collectives: ``ring_paged_prefill`` matches the single-chip XLA
  twin bitwise (fp32 AND int8, shard 2 and 4), and the sequence ring's
  partial-hop causal masking matches the dense oracle;
* the engine: the sharded continuous-batching engine is token-for-token
  the single-chip engine on GQA configs (fp32 + int8, through a §7
  injected preemption burst, with the pool auditor attached), traces
  the engine's step spans + shard.* metrics, resolves ``shard="auto"``,
  and the least-loaded router balances replicas;
* the search: ``Tiling.shard`` is the eighth factor of grid/MCTS/GA and
  its optimum moves with the interconnect bandwidth (interior at the
  default link, 1 when the link is dead), mirrored by the closed-form
  ``tune_shard_degree``.

Multi-device cases skip unless run under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (scripts/ci.sh
does); the sharding/search/tuner tests run everywhere.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, PartitionSpec as P

jax.config.update("jax_enable_x64", False)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")


def _smoke(arch):
    from repro.configs import get_smoke
    from repro.models import build_model

    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, lens, max_new=8, seed=0):
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(3, cfg.vocab_size,
                                        size=ln).astype(np.int32),
                    max_new_tokens=max_new)
            for i, ln in enumerate(lens)]


# ---------------------------------------------------------------------------
# sharding rules: cache_specs over both layouts
# ---------------------------------------------------------------------------


def test_cache_specs_understands_both_layouts():
    from repro.distributed.sharding import cache_specs

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    # paged pools (stacked): (U, Hkv, P, page, E) k/v + (U, Hkv, P) scales
    paged = {"units": {"b0": {
        "k": jnp.zeros((2, 4, 8, 4, 16), jnp.int8),
        "v": jnp.zeros((2, 4, 8, 4, 16), jnp.int8),
        "k_scale": jnp.zeros((2, 4, 8), jnp.float32),
        "v_scale": jnp.zeros((2, 4, 8), jnp.float32),
    }}}
    def axes(spec, ndim):
        # fit_spec trims trailing Nones; pad back for comparison
        return tuple(spec) + (None,) * (ndim - len(tuple(spec)))

    specs = cache_specs(paged, mesh, layout="paged")
    blk = specs["units"]["b0"]
    assert axes(blk["k"], 5) == (None, "model", None, None, None)
    assert axes(blk["v"], 5) == (None, "model", None, None, None)
    assert axes(blk["k_scale"], 3) == (None, "model", None)
    assert axes(blk["v_scale"], 3) == (None, "model", None)
    # dense wave caches (stacked): (U, B, Hkv, S, E) — SEQUENCE sharded,
    # the pre-§11 behavior, still the default layout
    dense = {"units": {"b0": {
        "k": jnp.zeros((2, 2, 4, 32, 16), jnp.float32),
        "v": jnp.zeros((2, 2, 4, 32, 16), jnp.float32),
    }}}
    dspecs = cache_specs(dense, mesh)
    assert axes(dspecs["units"]["b0"]["k"], 5) == (
        None, None, None, "model", None)
    # the two stacked k/v layouts are both ndim-5: without the kwarg the
    # paged pool would silently get the dense (seq-axis) spec
    wrong = cache_specs(paged, mesh)["units"]["b0"]["k"]
    assert axes(wrong, 5) != (None, "model", None, None, None)


def test_cache_specs_paged_on_real_cache():
    from repro.distributed.sharding import cache_specs
    from repro.models.transformer import make_paged_cache

    cfg, model, _ = _smoke("internlm2-1.8b")
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    cache = make_paged_cache(cfg, num_pages=8, page_size=4,
                             kv_dtype=jnp.int8)
    specs = cache_specs(cache, mesh, layout="paged")
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    assert flat, "no cache leaves"
    for kp, spec in flat:
        # every pool/scale leaf shards its Hkv axis (index 1, stacked)
        assert tuple(spec)[1] == "model", (kp, spec)


# ---------------------------------------------------------------------------
# search: Tiling.shard as the eighth factor, moved by the link model
# ---------------------------------------------------------------------------


def _sharded_workload():
    from repro.sim.workload import ShardedServingWorkload

    return ShardedServingWorkload("shard-w", heads=8, emb=64,
                                  kv_lens=(512,) * 4, group=4, n_steps=8)


def test_shard_factor_in_space_and_grid_interior():
    from repro.sim.hw import EDGE_HW
    from repro.sim.schedules import tiling_space
    from repro.sim.search import search_tiling

    w = _sharded_workload()
    space = tiling_space(w, EDGE_HW)
    shards = {t.shard for t in space}
    assert shards == {1, 2, 4, 8}
    best = search_tiling("sharded_serving", w, EDGE_HW, strategy="grid")
    # default link (16 GB/s): the optimum is INTERIOR — more than one
    # chip pays, but the per-chip core-split plateau stops the compute
    # win before the space's max degree
    assert best.tiling.shard == 4, best.tiling


def test_shard_optimum_moves_with_link_bandwidth():
    from repro.sim.hw import EDGE_HW
    from repro.sim.search import search_tiling

    w = _sharded_workload()
    prev = 0
    picks = {}
    for gbps in (1e-5, 0.05, 16.0, 1000.0):
        hw = dataclasses.replace(EDGE_HW, link_gbps=gbps)
        s = search_tiling("sharded_serving", w, hw, strategy="grid").tiling.shard
        assert s >= prev, f"not monotone at {gbps}: {s} < {prev}"
        prev = s
        picks[gbps] = s
    assert picks[1e-5] == 1          # dead link -> single chip
    assert picks[1000.0] >= 4        # free link -> many chips


@pytest.mark.parametrize("strategy", ["mcts", "ga"])
def test_shard_searchable_by_mcts_and_ga(strategy):
    from repro.sim.hw import EDGE_HW
    from repro.sim.search import search_tiling

    w = _sharded_workload()
    best = search_tiling("sharded_serving", w, EDGE_HW, strategy=strategy,
                         iters=300, seed=0)
    assert best.tiling.shard == 4, (strategy, best.tiling)


def test_sharded_schedule_charges_link_stream():
    from repro.sim.hw import EDGE_HW
    from repro.sim.schedules import Tiling, build_schedule

    w = _sharded_workload()
    t = Tiling(hh=1, nq=1, nkv=256, shard=4)
    tasks = build_schedule("sharded_serving", w, t, EDGE_HW)
    assert tasks is not None
    link = [tk for tk in tasks if tk.unit == "LINK"]
    # (shard - 1) serial hops per priced step
    assert len(link) == (4 - 1) * w.n_steps
    # a non-dividing degree is infeasible, not mis-built
    assert build_schedule("sharded_serving", w,
                          Tiling(hh=1, nq=1, nkv=256, shard=3),
                          EDGE_HW) is None


def test_tune_shard_degree_closed_form():
    from repro.core.autotune import tune_shard_degree

    long_kw = dict(heads_kv=8, group=4, n_ctx=32768, e=128)
    assert tune_shard_degree(**long_kw, link_gbps=1e-4) == 1
    assert tune_shard_degree(**long_kw) > 1
    # divisor rule: 6 kv heads never get degree 4
    assert tune_shard_degree(heads_kv=6, group=4, n_ctx=32768,
                             e=128) in (1, 2, 3, 6)
    # smoke scale: step overhead dominates -> sharding doesn't pay
    assert tune_shard_degree(heads_kv=2, group=2, n_ctx=112, e=16) == 1
    prev = 0
    for g in (1e-4, 1e-2, 1.0, 75.0, 1e3):
        s = tune_shard_degree(**long_kw, link_gbps=g)
        assert s >= prev
        prev = s


# ---------------------------------------------------------------------------
# router (host-side data parallelism; device-count agnostic)
# ---------------------------------------------------------------------------


def test_router_least_loaded_balance():
    from repro.serving import ContinuousBatchingEngine, LeastLoadedRouter

    cfg, model, params = _smoke("internlm2-1.8b")
    engines = [ContinuousBatchingEngine(model, params, max_len=64,
                                        batch_size=2, page_size=8)
               for _ in range(2)]
    router = LeastLoadedRouter(engines)
    reqs = _requests(cfg, [30, 5, 6, 7], max_new=4)
    shares, load = router.route(reqs)
    # the long prompt lands alone; the short ones fill the other replica
    assert len(shares[0]) == 1 and len(shares[1]) == 3
    out = router.serve(reqs)
    assert set(out) == {0, 1, 2, 3}
    assert all(len(v) > 0 for v in out.values())
    st = router.stats
    assert st["replicas"] == 2 and sum(st["requests"]) == 4
    assert st["balance"] >= 1.0
    # router output == one big engine's output per request (greedy
    # decode is per-request deterministic; batching composition differs
    # but tokens must not)
    solo = ContinuousBatchingEngine(model, params, max_len=64,
                                    batch_size=2, page_size=8)
    base = solo.serve(_requests(cfg, [30, 5, 6, 7], max_new=4))
    for rid in base:
        np.testing.assert_array_equal(base[rid], out[rid])
    with pytest.raises(ValueError):
        LeastLoadedRouter([])


# ---------------------------------------------------------------------------
# collectives (4 forced host devices)
# ---------------------------------------------------------------------------


@needs_mesh
@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("n_chips", [2, 4])
def test_ring_paged_prefill_matches_twin(quant, n_chips):
    from repro.distributed.paged import ring_paged_prefill
    from repro.kernels.common import quantize_q8
    from repro.models.attention import paged_prefill_attention

    rng = np.random.default_rng(0)
    hq, hkv, e, page, npages = 8, 4, 16, 8, 12
    chunk, kv_len, q_offset = 10, 30, 20
    mesh = Mesh(np.asarray(jax.devices()[:n_chips]), ("model",))
    q = jnp.asarray(rng.standard_normal((hq, chunk, e)), jnp.float32)
    kd = jnp.asarray(rng.standard_normal((hkv, npages, page, e)),
                     jnp.float32)
    vd = jnp.asarray(rng.standard_normal((hkv, npages, page, e)),
                     jnp.float32)
    table = jnp.asarray(rng.permutation(npages)[:6], jnp.int32)
    scales = {}
    if quant:
        kd, ks = quantize_q8(kd, (-2, -1))
        vd, vs = quantize_q8(vd, (-2, -1))
        scales = dict(k_scales=ks, v_scales=vs)
    ref = paged_prefill_attention(q, kd, vd, table, q_offset, kv_len,
                                  **scales)
    out = ring_paged_prefill(q, kd, vd, table, q_offset, kv_len, mesh,
                             **scales)
    # bitwise: identical ops per (head, row), hops fill disjoint slots
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@needs_mesh
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kv_len", [None, 13, 27])
def test_ring_attention_partial_hop_masking(causal, kv_len):
    from repro.distributed.ring_attention import ring_attention
    from repro.kernels import ref as kref

    rng = np.random.default_rng(1)
    b, h, s, e = 2, 4, 32, 16
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("model",))
    q = jnp.asarray(rng.standard_normal((b, h, s, e)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, e)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, e)), jnp.float32)
    ref_o = kref.attention(q, k, v, causal=causal, kv_len=kv_len)
    out = ring_attention(q, k, v, mesh, causal=causal, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the sharded engine (4 forced host devices)
# ---------------------------------------------------------------------------

ENGINE_KW = dict(max_len=96, batch_size=3, page_size=8, chunk_size=16)


def _parity_case(arch, shard, kv_dtype=None, lens=(5, 19, 33, 12, 26, 7),
                 injector=None, engine_kw=None):
    from repro.serving import (ContinuousBatchingEngine, PoolAuditor,
                               ShardedContinuousBatchingEngine)

    cfg, model, params = _smoke(arch)
    kw = dict(ENGINE_KW, kv_dtype=kv_dtype, **(engine_kw or {}))
    base_eng = ContinuousBatchingEngine(model, params, **kw)
    if injector is not None:
        base_eng.injector = injector()
    base = base_eng.serve(_requests(cfg, lens))
    sh_eng = ShardedContinuousBatchingEngine(model, params, shard=shard,
                                             **kw)
    sh_eng.auditor = PoolAuditor()   # pool accounting audited per shard run
    if injector is not None:
        sh_eng.injector = injector()
    out = sh_eng.serve(_requests(cfg, lens))
    assert set(out) == set(base)
    for rid in base:
        np.testing.assert_array_equal(
            base[rid], out[rid],
            err_msg=f"{arch} shard={shard} kv={kv_dtype} rid={rid}")
    return base_eng, sh_eng


@needs_mesh
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("arch,shard", [
    ("internlm2-1.8b", 2),       # GQA 4q/2kv
    ("qwen3-1.7b", 2),           # GQA + qk-norm
    ("deepseek-moe-16b", 4),     # 4 kv heads + MoE FFN
])
def test_sharded_engine_token_parity(arch, shard, kv_dtype):
    """Sharded output is token-for-token the single-chip output."""
    _, sh_eng = _parity_case(arch, shard, kv_dtype=kv_dtype)
    st = sh_eng.shard_stats
    assert st["degree"] == shard
    assert st["allgather_bytes"] > 0 and st["ring_hops"] > 0


@needs_mesh
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_sharded_engine_preemption_burst_parity(kv_dtype):
    """§7 injected exhaustion burst: preempt/recompute under sharding
    keeps greedy parity and the pool audits clean."""
    from repro.serving import ScriptedFaults

    inj = lambda: ScriptedFaults(exhaust_at_appends=frozenset({2, 5, 6}))
    base_eng, sh_eng = _parity_case("internlm2-1.8b", 2,
                                    kv_dtype=kv_dtype, injector=inj)
    assert sh_eng.preemption_count >= 1
    assert sh_eng.preemption_count == base_eng.preemption_count


@needs_mesh
def test_sharded_engine_speculative_parity():
    _, sh_eng = _parity_case("internlm2-1.8b", 2,
                             engine_kw=dict(spec_depth=3))
    assert sh_eng.spec_stats["drafted"] > 0


@needs_mesh
def test_sharded_engine_spans_and_metrics():
    from repro.obs import Tracer
    from repro.serving import ShardedContinuousBatchingEngine

    cfg, model, params = _smoke("internlm2-1.8b")
    tr = Tracer()
    eng = ShardedContinuousBatchingEngine(model, params, shard=2,
                                          tracer=tr, **ENGINE_KW)
    eng.serve(_requests(cfg, [5, 12]))
    trace = tr.export()
    tracks = {ev["args"]["name"] for ev in trace["traceEvents"]
              if ev.get("ph") == "M" and ev.get("name") == "thread_name"}
    # one engine track: the host step is not repeated per shard
    assert not any(t.startswith("shard") for t in tracks)
    steps = [ev for ev in trace["traceEvents"]
             if ev.get("ph") == "X" and ev["name"] == "step"]
    assert [ev["args"]["kind"] for ev in steps] == [
        ("decode" if not e["prefill_in_flight"]
         else "chunk+decode" if e["live_decode"] else "chunk")
        for e in eng.step_log]
    g = eng.metrics.gauge("shard.degree")
    assert len(g.series) == len(steps) and g.series[-1] == 2


def test_shard_auto_and_validation():
    from repro.serving import ShardedContinuousBatchingEngine

    cfg, model, params = _smoke("internlm2-1.8b")
    # auto at smoke scale: the closed form says sharding doesn't pay ->
    # degree 1 (and a 1-mesh engine must still serve correctly)
    eng = ShardedContinuousBatchingEngine(model, params, shard="auto",
                                          **ENGINE_KW)
    assert eng.shard == 1
    out = eng.serve(_requests(cfg, [5, 9], max_new=4))
    assert all(len(v) > 0 for v in out.values())
    with pytest.raises(ValueError):
        ShardedContinuousBatchingEngine(model, params, shard=3, **ENGINE_KW)
    with pytest.raises(ValueError):
        ShardedContinuousBatchingEngine(
            model, params, shard=2 * len(jax.devices()), **ENGINE_KW)
