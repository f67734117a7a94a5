"""Speculative decoding on the paged KV pool (DESIGN.md §9).

Four layers of the subsystem are pinned here:

* the multi-token verify kernel (pallas interpret mode) and its XLA
  gather twin must match the causal attention oracle for any depth /
  page size / ragged kv_lens / ragged per-slot row counts / pool
  permutation, fp32 and int8 (incl. a hypothesis sweep);
* the engine: speculative serving stays token-for-token equal to plain
  greedy decode — at k=1 (degenerate), at useful depths on draftable
  prompts, with an adversarial drafter whose candidates all lose, with
  int8 pools under the pool auditor, and through injected pool
  exhaustion (recompute preemption mid-speculation);
* the paged-cache batched append: ``ensure_capacity`` + ``append_n``
  land n tokens in one audited, exception-safe table update;
* the simulator/search: the speculative-decode schedule charges the
  page-granular KV DMA once per verify step while MXU/VEC scale with
  depth, and the depth is searched as a SIXTH tiling factor that k=1
  can win when acceptance is poor.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.common import quantize_q8
from repro.kernels.ops import paged_verify_attention
from repro.models.attention import paged_verify_attention as model_verify

jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# kernel parity: pallas vs XLA twin vs causal oracle
# ---------------------------------------------------------------------------


def _make_batched_pool(dense_k, dense_v, kv_lens, page_size, rng,
                       quantize=False):
    """Scatter per-seq dense (Hkv, S, E) K/V into one shuffled pool.

    Returns the pool pair, the (B, max_pages) table (scratch-padded)
    and the per-page scale side-tables (zeros when not quantized).
    """
    b = len(kv_lens)
    hkv, s, e = dense_k[0].shape
    n_pages = [-(-int(n) // page_size) for n in kv_lens]
    total = sum(n_pages)
    perm = list(rng.permutation(np.arange(1, total + 1)))
    mp = max(s // page_size for _ in range(b))
    table = np.zeros((b, mp), np.int32)
    dt = np.int8 if quantize else dense_k[0].dtype
    k_pool = np.zeros((hkv, total + 1, page_size, e), dt)
    v_pool = np.zeros((hkv, total + 1, page_size, e), dt)
    scales = {"k": np.zeros((hkv, total + 1), np.float32),
              "v": np.zeros((hkv, total + 1), np.float32)}
    for bi in range(b):
        for j in range(n_pages[bi]):
            pid = perm.pop()
            table[bi, j] = pid
            for which, pool, dense in (("k", k_pool, dense_k[bi]),
                                       ("v", v_pool, dense_v[bi])):
                blk = dense[:, j * page_size:(j + 1) * page_size]
                if quantize:
                    qq, sc = quantize_q8(jnp.asarray(blk), (-2, -1))
                    pool[:, pid] = np.asarray(qq)
                    scales[which][:, pid] = np.asarray(sc)
                else:
                    pool[:, pid] = blk
    return k_pool, v_pool, table, scales


def _check_verify_parity(seed, group, hkv, page_size, spec, kv_lens,
                         n_rows, quantize=False):
    """kv_lens INCLUDE the candidate rows; slot b verifies n_rows[b]
    <= spec rows ending at kv_lens[b] (rows past that are garbage)."""
    rng = np.random.default_rng(seed)
    b = len(kv_lens)
    hq, e = group * hkv, 16
    s = max(-(-int(n) // page_size) * page_size for n in kv_lens)
    q = jnp.asarray(rng.standard_normal((b, spec, hq, e)), jnp.float32)
    dense_k = [rng.standard_normal((hkv, s, e)).astype(np.float32)
               for _ in range(b)]
    dense_v = [rng.standard_normal((hkv, s, e)).astype(np.float32)
               for _ in range(b)]
    k_pool, v_pool, table, scales = _make_batched_pool(
        dense_k, dense_v, kv_lens, page_size, rng, quantize)
    q_starts = np.asarray([kv_lens[i] - n_rows[i] for i in range(b)],
                          np.int32)
    kw = {}
    if quantize:
        kw = dict(k_scales=jnp.asarray(scales["k"]),
                  v_scales=jnp.asarray(scales["v"]))
    args = (q, jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(table), jnp.asarray(kv_lens, np.int32),
            jnp.asarray(q_starts))
    out_pallas = np.asarray(paged_verify_attention(*args, **kw))
    out_xla = np.asarray(model_verify(*args, **kw))
    for bi in range(b):
        nr = n_rows[bi]
        np.testing.assert_allclose(
            out_pallas[bi, :nr], out_xla[bi, :nr], atol=2e-5, rtol=2e-5,
            err_msg=f"twin mismatch slot {bi}")
        kd, vd = dense_k[bi], dense_v[bi]
        if quantize:
            kd, vd = np.zeros_like(kd), np.zeros_like(vd)
            for j in range(-(-int(kv_lens[bi]) // page_size)):
                pid = table[bi, j]
                sl = slice(j * page_size, (j + 1) * page_size)
                kd[:, sl] = (k_pool[:, pid].astype(np.float32)
                             * scales["k"][:, pid, None, None])
                vd[:, sl] = (v_pool[:, pid].astype(np.float32)
                             * scales["v"][:, pid, None, None])
        want = np.asarray(ref.attention(
            jnp.asarray(np.moveaxis(np.asarray(q[bi]), 0, 1))[None],
            jnp.asarray(kd[None]), jnp.asarray(vd[None]), causal=True,
            kv_len=int(kv_lens[bi]), q_offset=int(q_starts[bi]),
        ))[0]  # (hq, spec, e)
        np.testing.assert_allclose(
            out_pallas[bi, :nr], np.moveaxis(want, 0, 1)[:nr],
            atol=2e-5, rtol=2e-5, err_msg=f"oracle mismatch slot {bi}")


@pytest.mark.parametrize("group,hkv", [(1, 2), (2, 2), (4, 1)])
@pytest.mark.parametrize("spec,kv_lens,n_rows", [
    (1, (9, 16), (1, 1)),          # degenerate: plain decode shape
    (4, (12, 27), (4, 4)),         # full-depth slots, ragged tails
    (4, (12, 27, 8), (4, 2, 1)),   # ragged per-slot row counts
    (8, (21, 32), (8, 5)),         # depth spanning multiple pages
])
def test_verify_kernel_matches_twin_and_oracle(group, hkv, spec, kv_lens,
                                               n_rows):
    _check_verify_parity(seed=group * 13 + spec, group=group, hkv=hkv,
                         page_size=8, spec=spec, kv_lens=kv_lens,
                         n_rows=n_rows)


@pytest.mark.parametrize("spec,kv_lens,n_rows", [
    (4, (12, 27), (4, 4)),
    (4, (12, 27, 8), (4, 2, 1)),
])
def test_verify_kernel_int8(spec, kv_lens, n_rows):
    _check_verify_parity(seed=spec, group=2, hkv=2, page_size=8, spec=spec,
                         kv_lens=kv_lens, n_rows=n_rows, quantize=True)


def test_verify_kernel_hypothesis():
    """Randomized sweep over depth / page size / ragged rows / pools."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    dims = st.tuples(
        st.sampled_from([(1, 1), (2, 1), (2, 2), (4, 2)]),  # (group, hkv)
        st.sampled_from([8, 16]),            # page_size
        st.integers(1, 6),                   # spec
        st.lists(st.integers(1, 40), min_size=1, max_size=3),  # kv_lens
        st.booleans(),                       # int8 pool
        st.integers(0, 2**31 - 1),           # seed
    )

    @given(dims)
    @settings(max_examples=12, deadline=None)
    def check(t):
        (group, hkv), ps, spec, lens, quantize, seed = t
        rng = np.random.default_rng(seed)
        kv_lens = tuple(max(int(n), spec) for n in lens)
        n_rows = tuple(int(rng.integers(1, spec + 1)) for _ in kv_lens)
        _check_verify_parity(seed, group, hkv, ps, spec, kv_lens, n_rows,
                             quantize)

    check()


# ---------------------------------------------------------------------------
# paged cache: batched append
# ---------------------------------------------------------------------------


def test_append_n_crosses_pages_and_is_exception_safe():
    from repro.serving import PagedKVCacheManager, PagePoolExhausted

    mgr = PagedKVCacheManager(6, 4, num_slots=2, max_pages_per_seq=4)
    mgr.admit(0, 3)                    # 1 page, 3 live rows
    mgr.append_n(0, 3)                 # crosses into a second page
    assert mgr.kv_lens()[0] == 6 and len(mgr.seq_pages(0)) == 2
    mgr.append_n(0, 0)                 # no-op
    assert mgr.kv_lens()[0] == 6
    # reserve ahead: the following append_n is alloc-free
    mgr.ensure_capacity(0, 5)
    assert len(mgr.seq_pages(0)) == 3 and mgr.kv_lens()[0] == 6
    free_before = mgr.available
    mgr.append_n(0, 5)
    assert mgr.available == free_before and mgr.kv_lens()[0] == 11
    # exhaustion: all-or-nothing, length AND capacity unchanged
    mgr.admit(1, 8)                    # drains the remaining pages
    with pytest.raises(PagePoolExhausted):
        mgr.append_n(0, 6)             # needs pages the pool lacks
    assert mgr.kv_lens()[0] == 11 and len(mgr.seq_pages(0)) == 3
    with pytest.raises(PagePoolExhausted):
        mgr.ensure_capacity(1, 99)     # exceeds max_pages_per_seq
    assert len(mgr.seq_pages(1)) == 2


def test_append_n_matches_serial_appends():
    from repro.serving import PagedKVCacheManager

    a = PagedKVCacheManager(10, 4, num_slots=1, max_pages_per_seq=8)
    b = PagedKVCacheManager(10, 4, num_slots=1, max_pages_per_seq=8)
    a.admit(0, 5)
    b.admit(0, 5)
    a.append_n(0, 7)
    for _ in range(7):
        b.append(0)
    assert a.kv_lens()[0] == b.kv_lens()[0]
    assert a.seq_pages(0) == b.seq_pages(0)
    np.testing.assert_array_equal(a.table(), b.table())


# ---------------------------------------------------------------------------
# drafter: deterministic prompt lookup
# ---------------------------------------------------------------------------


def test_ngram_drafter_prompt_lookup():
    from repro.serving import NgramDrafter

    d = NgramDrafter(ngram=3)
    # suffix (7, 8) last occurred before 9, 4 — the proposed continuation
    ctx = [1, 7, 8, 2, 3, 7, 8, 9, 4, 7, 8]
    assert d.draft(ctx, 2) == [9, 4]
    # most recent match wins over the earlier (7, 8) -> (2, 3)
    assert d.draft(ctx, 4) == [9, 4, 7, 8]
    # no recurrence anywhere: nothing proposed
    assert d.draft([1, 2, 3, 4, 5], 4) == []
    assert d.draft([5], 4) == []
    assert d.draft(ctx, 0) == []
    # deterministic
    assert d.draft(ctx, 3) == d.draft(ctx, 3)
    with pytest.raises(ValueError):
        NgramDrafter(ngram=0)


# ---------------------------------------------------------------------------
# engine: speculative serving == plain greedy decode, token for token
# ---------------------------------------------------------------------------


def _smoke_model():
    from repro.configs import get_smoke
    from repro.models import build_model

    cfg = get_smoke("internlm2-1.8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _draftable_requests(cfg, spec, period=4):
    """Prompts built from short repeating cycles: the n-gram drafter's
    best case, so verify steps actually accept multi-token prefixes."""
    from repro.serving import Request

    rng = np.random.default_rng(7)
    reqs = []
    for i, (n, m) in enumerate(spec):
        cycle = rng.integers(3, cfg.vocab_size, size=(period,))
        prompt = np.tile(cycle, -(-n // period))[:n].astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=m,
                            eos_id=-2))
    return reqs


SPEC = [(9, 6), (13, 5), (6, 8), (17, 4), (8, 6)]


def _plain_baseline(cfg, model, params, **kw):
    from repro.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(model, params, max_len=40, batch_size=2,
                                   page_size=4, chunk_size=8, **kw)
    return eng.serve(_draftable_requests(cfg, SPEC))


@pytest.mark.parametrize("depth", [1, 3, 4])
def test_speculative_matches_plain_greedy(depth):
    from repro.serving import ContinuousBatchingEngine

    cfg, model, params = _smoke_model()
    want = _plain_baseline(cfg, model, params)
    eng = ContinuousBatchingEngine(model, params, max_len=40, batch_size=2,
                                   page_size=4, chunk_size=8,
                                   spec_depth=depth)
    out = eng.serve(_draftable_requests(cfg, SPEC))
    assert set(out) == set(want)
    for rid in want:
        np.testing.assert_array_equal(want[rid], out[rid],
                                      err_msg=f"rid {rid} depth {depth}")
    st = eng.spec_stats
    if depth > 1:
        # repeating prompts: the drafter must land some multi-token steps
        assert st["drafted"] > 0 and st["accepted"] > 0
        assert 0.0 < st["acceptance_rate"] <= 1.0
    else:
        assert st["drafted"] == 0  # k=1 never drafts


def test_speculative_int8_pool_audited():
    from repro.serving import ContinuousBatchingEngine, PoolAuditor

    cfg, model, params = _smoke_model()
    want = _plain_baseline(cfg, model, params, kv_dtype="int8")
    eng = ContinuousBatchingEngine(model, params, max_len=40, batch_size=2,
                                   page_size=4, chunk_size=8,
                                   kv_dtype="int8", spec_depth=4)
    aud = PoolAuditor()
    eng.auditor = aud
    out = eng.serve(_draftable_requests(cfg, SPEC))
    for rid in want:
        np.testing.assert_array_equal(want[rid], out[rid],
                                      err_msg=f"rid {rid}")
    assert aud.steps_checked > 0


def test_speculative_survives_adversarial_drafter():
    """A drafter whose candidates always lose must cost only wasted
    verify rows, never correctness: stale candidate rows in the pool
    are overwritten or masked, and every step still emits the bonus
    token — plain greedy equality with acceptance pinned at zero."""
    from repro.serving import ContinuousBatchingEngine

    class BadDrafter:
        def draft(self, context, k):
            return [3] * k if k > 0 else []  # constant garbage tokens

    cfg, model, params = _smoke_model()
    want = _plain_baseline(cfg, model, params)
    eng = ContinuousBatchingEngine(model, params, max_len=40, batch_size=2,
                                   page_size=4, chunk_size=8, spec_depth=4)
    eng._drafter = BadDrafter()
    out = eng.serve(_draftable_requests(cfg, SPEC))
    for rid in want:
        np.testing.assert_array_equal(want[rid], out[rid],
                                      err_msg=f"rid {rid}")
    st = eng.spec_stats
    assert st["drafted"] > 0 and st["accepted"] == 0


def test_speculative_with_injected_preemption():
    """Recompute preemption fires mid-speculation (injected pool
    exhaustion on the batched append path); evicted requests replay via
    chunked re-prefill and the final tokens still match plain greedy."""
    from repro.serving import (
        ContinuousBatchingEngine,
        PoolAuditor,
        ScriptedFaults,
    )

    cfg, model, params = _smoke_model()
    want = _plain_baseline(cfg, model, params)
    eng = ContinuousBatchingEngine(model, params, max_len=40, batch_size=2,
                                   page_size=4, chunk_size=8, spec_depth=4)
    eng.injector = ScriptedFaults(exhaust_at_appends=frozenset({5, 11}))
    eng.auditor = PoolAuditor()
    out = eng.serve(_draftable_requests(cfg, SPEC))
    for rid in want:
        np.testing.assert_array_equal(want[rid], out[rid],
                                      err_msg=f"rid {rid}")
    assert eng.preemption_count >= 1


def test_speculative_trace_carries_verify_steps():
    """Verify steps are traced with kind="verify" (mapped to the
    compare phase), a draft span before each verify step's
    pack/dispatch/sync, then its commit, and speculation instants."""
    from repro.obs import DEFAULT_KIND_TO_PHASE, Tracer, validate_chrome_trace
    from repro.serving import ContinuousBatchingEngine

    cfg, model, params = _smoke_model()
    tr = Tracer()
    eng = ContinuousBatchingEngine(model, params, max_len=40, batch_size=2,
                                   page_size=4, chunk_size=8, spec_depth=4,
                                   tracer=tr)
    eng.serve(_draftable_requests(cfg, SPEC))
    trace = tr.export()
    assert validate_chrome_trace(trace) == []
    evs = trace["traceEvents"]
    kinds = {(e.get("args") or {}).get("kind")
             for e in evs if e.get("name") == "step" and e.get("ph") == "X"}
    assert "verify" in kinds
    assert DEFAULT_KIND_TO_PHASE["verify"] == "verify"
    names = [e.get("name") for e in evs if e.get("ph") == "X"
             and e.get("name") in ("draft", "pack", "dispatch",
                                   "host_sync", "commit")]
    # spans close in order: draft first in every verify step
    i = names.index("draft")
    assert names[i:i + 5] == ["draft", "pack", "dispatch", "host_sync",
                              "commit"]
    inst = [e for e in evs if e.get("ph") == "i"
            and e.get("name") == "speculation"]
    assert inst and all("accepted" in (e.get("args") or {}) for e in inst)
    # acceptance-rate series lands in the metrics registry
    assert eng.metrics.series("spec.acceptance_rate").by_key


def test_spec_depth_auto_is_searched_not_hardcoded():
    from repro.serving import ContinuousBatchingEngine

    cfg, model, params = _smoke_model()
    eng = ContinuousBatchingEngine(model, params, max_len=40, batch_size=2,
                                   page_size=4, chunk_size=8,
                                   spec_depth="auto")
    assert isinstance(eng.spec_depth, int) and eng.spec_depth >= 1
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(model, params, max_len=40, batch_size=2,
                                 page_size=4, spec_depth=0)


# ---------------------------------------------------------------------------
# simulator + search: speculation depth as the sixth tiling factor
# ---------------------------------------------------------------------------


def test_sim_verify_charges_page_dma_once_per_step():
    from repro.sim import (
        EDGE_HW,
        SpeculativeDecodeWorkload,
        Tiling,
        build_schedule,
        simulate,
    )

    # new_tokens=1 -> exactly one verify step at any depth: the page
    # gather must cost the same bytes while MXU work scales with k
    w = SpeculativeDecodeWorkload("v", heads=8, emb=64, group=2,
                                  kv_lens=(96, 80, 64), new_tokens=1)
    r1 = simulate(build_schedule("speculative_decode", w,
                                 Tiling(1, 1, 32, None, None, 1), EDGE_HW),
                  EDGE_HW)
    r4 = simulate(build_schedule("speculative_decode", w,
                                 Tiling(1, 1, 32, None, None, 4), EDGE_HW),
                  EDGE_HW)
    kv_read = w.kv_bytes(EDGE_HW.bytes_per_elem, 32)
    assert r1.dram_read_bytes >= kv_read
    # K/V page traffic identical; only the k-row Q reads grow
    assert (r4.dram_read_bytes - r1.dram_read_bytes
            < 0.05 * r1.dram_read_bytes)
    assert r4.mac_ops == 4 * r1.mac_ops
    assert r4.vec_ops > r1.vec_ops
    # int8 pages shrink the gather and add dequant VEC work
    wq = SpeculativeDecodeWorkload("v8", heads=8, emb=64, group=2,
                                   kv_lens=(96, 80, 64), new_tokens=1,
                                   kv_bpe=1)
    rq = simulate(build_schedule("speculative_decode", wq,
                                 Tiling(1, 1, 32, None, None, 4), EDGE_HW),
                  EDGE_HW)
    assert rq.dram_read_bytes < 0.6 * r4.dram_read_bytes
    assert rq.vec_ops > r4.vec_ops


def test_sim_spec_depth_search_tracks_acceptance():
    """High acceptance -> deep speculation wins; hopeless acceptance ->
    the search falls back to plain decode (k stays 1). Both via grid;
    MCTS and GA carry the sixth gene."""
    from repro.sim import EDGE_HW, SpeculativeDecodeWorkload, search_tiling

    good = SpeculativeDecodeWorkload("good", heads=8, emb=64, group=2,
                                     kv_lens=(96, 80, 64, 96),
                                     new_tokens=16, accept_rate=0.8)
    res = search_tiling("speculative_decode", good, EDGE_HW,
                        strategy="grid")
    assert res.tiling.spec is not None and res.tiling.spec > 1
    bad = SpeculativeDecodeWorkload("bad", heads=8, emb=64, group=2,
                                    kv_lens=(96, 80, 64, 96),
                                    new_tokens=16, accept_rate=0.0)
    rb = search_tiling("speculative_decode", bad, EDGE_HW, strategy="grid")
    assert rb.tiling.spec == 1
    for strategy, iters in (("mcts", 80), ("ga", 60)):
        r = search_tiling("speculative_decode", good, EDGE_HW,
                          strategy=strategy, iters=iters)
        assert r.tiling.spec is not None and r.tiling.spec >= 1, strategy
        assert r.result.cycles <= 2 * res.result.cycles, strategy


def test_sim_expected_tokens_model():
    from repro.sim import SpeculativeDecodeWorkload

    w = SpeculativeDecodeWorkload("e", heads=1, emb=8, kv_lens=(8,),
                                  new_tokens=12, accept_rate=0.5)
    assert w.expected_tokens_per_step(1) == 1.0
    assert w.expected_tokens_per_step(2) == pytest.approx(1.5)
    assert w.expected_tokens_per_step(3) == pytest.approx(1.75)
    # perfect acceptance: k tokens per step, ceil division on steps
    wp = SpeculativeDecodeWorkload("p", heads=1, emb=8, kv_lens=(8,),
                                   new_tokens=12, accept_rate=1.0)
    assert wp.expected_tokens_per_step(4) == 4.0
    assert wp.n_steps(4) == 3 and wp.n_steps(1) == 12
    # zero acceptance degenerates to one token per step
    wz = SpeculativeDecodeWorkload("z", heads=1, emb=8, kv_lens=(8,),
                                   new_tokens=12, accept_rate=0.0)
    assert wz.expected_tokens_per_step(8) == 1.0


def test_serving_phase_workloads_gain_verify_phase():
    from repro.sim.workload import serving_phase_workloads

    ph = serving_phase_workloads("x", [40, 32], 16, heads=8, emb=64,
                                 group=2, spec=4, accept_rate=0.6)
    assert set(ph) == {"decode", "prefill_chunk", "verify"}
    assert ph["verify"].spec == 4
    base = serving_phase_workloads("x", [40, 32], 16, heads=8, emb=64,
                                   group=2)
    assert "verify" not in base


def test_tune_spec_depth_analytical_default():
    from repro.core.autotune import tune_spec_depth

    k = tune_spec_depth(b_h=16, n_ctx=2048, e=128)
    assert 1 <= k <= 8
    # long contexts amortize the page gather over more drafts
    deep = tune_spec_depth(b_h=16, n_ctx=8192, e=128, accept_rate=0.9)
    shallow = tune_spec_depth(b_h=16, n_ctx=8192, e=128, accept_rate=0.05)
    assert deep > shallow
    assert tune_spec_depth(b_h=16, n_ctx=2048, e=128,
                           accept_rate=0.0) == 1
