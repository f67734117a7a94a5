"""Int8 KV-cache quantization end-to-end (DESIGN.md §5).

Four layers of the quantized serving path are pinned here:

* the int8 decode kernels (pallas interpret mode) against their
  op-identical XLA twins and against the dequantized fp32 oracle, for
  any page size / kv_len / GQA group (incl. a hypothesis sweep);
* the quantizer itself (symmetric absmax round-trips, zero handling,
  requant idempotence under an unchanged scale);
* the paged pool bookkeeping: quantized admit/append, and freed-page
  reuse where stale bytes and stale scales must never leak into a new
  sequence;
* end-to-end greedy decode agreement >= 99% vs the bf16 baseline on a
  small transformer, through BOTH serving engines;
* the sim/tuner view: kv_bpe charged on KV DMA + scales side-traffic,
  and the tiling search selecting int8 for long-context decode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.common import dequantize_q8, quantize_q8
from repro.kernels.ops import decode_attention, paged_decode_attention
from repro.models.attention import paged_decode_attention as model_paged
from repro.models.attention import sharded_decode_attention

jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# quantizer primitives
# ---------------------------------------------------------------------------


def test_quantize_q8_roundtrip_and_zero_groups():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 8, 16)), jnp.float32)
    q, sc = quantize_q8(x, (-2, -1))
    assert q.dtype == jnp.int8 and sc.shape == (4,)
    back = dequantize_q8(q, sc, (-2, -1))
    # half-LSB bound: |x - deq| <= scale / 2
    err = jnp.max(jnp.abs(back - x), axis=(1, 2))
    assert np.all(np.asarray(err) <= np.asarray(sc) / 2 + 1e-7)
    # absmax element is exactly representable
    assert np.asarray(jnp.max(jnp.abs(back))) == pytest.approx(
        float(jnp.max(jnp.abs(x))), rel=1e-6)
    # all-zero group: scale 0, values 0, exact round-trip
    qz, sz = quantize_q8(jnp.zeros((2, 8)), -1)
    assert np.all(np.asarray(sz) == 0) and np.all(np.asarray(qz) == 0)
    assert np.all(np.asarray(dequantize_q8(qz, sz, -1)) == 0)


def test_requant_unchanged_scale_is_exact():
    """round(v * s / s) == v: old rows survive a same-scale requant."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
    q1, s1 = quantize_q8(x, (-2, -1))
    q2, s2 = quantize_q8(dequantize_q8(q1, s1, (-2, -1)), (-2, -1))
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


# ---------------------------------------------------------------------------
# int8 kernel parity: pallas vs XLA twin vs dequantized oracle
# ---------------------------------------------------------------------------


def _quant_pool(kd, vd, page_size, rng):
    """Scatter dense (B, Hkv, S, E) caches into a shuffled int8 pool."""
    b, hkv, s, e = kd.shape
    mp = s // page_size
    n_pages = b * mp + 1  # + scratch page 0
    perm = rng.permutation(np.arange(1, n_pages))
    table = perm.reshape(b, mp).astype(np.int32)
    pools = {}
    for which, dense in (("k", kd), ("v", vd)):
        pool = np.zeros((hkv, n_pages, page_size, e), np.int8)
        psc = np.zeros((hkv, n_pages), np.float32)
        for i in range(b):
            for j in range(mp):
                blk = dense[i, :, j * page_size:(j + 1) * page_size]
                q, sc = quantize_q8(jnp.asarray(blk), (-2, -1))
                pool[:, table[i, j]] = np.asarray(q)
                psc[:, table[i, j]] = np.asarray(sc)
        pools[which] = (pool, psc)
    return pools["k"], pools["v"], table


def _check_int8_paged_parity(seed, b, group, hkv, page_size, mp, e,
                             kv_lens=None):
    rng = np.random.default_rng(seed)
    s = page_size * mp
    hq = group * hkv
    q = jnp.asarray(rng.standard_normal((b, hq, e)), jnp.float32)
    kd = rng.standard_normal((b, hkv, s, e)).astype(np.float32)
    vd = rng.standard_normal((b, hkv, s, e)).astype(np.float32)
    if kv_lens is None:
        kv_lens = rng.integers(0, s + 1, size=b).astype(np.int32)
        kv_lens[0] = s
    kv_lens = np.asarray(kv_lens, np.int32)
    (k_pool, k_sc), (v_pool, v_sc), table = _quant_pool(kd, vd, page_size,
                                                        rng)
    args = (q, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table),
            jnp.asarray(kv_lens))
    kw = dict(k_scales=jnp.asarray(k_sc), v_scales=jnp.asarray(v_sc))
    out_pallas = np.asarray(paged_decode_attention(*args, **kw))
    out_xla = np.asarray(model_paged(*args, **kw))

    for i in range(b):
        if kv_lens[i] == 0:
            continue
        # twin parity: the XLA twin applies the scales exactly where the
        # kernel does, so the two paths agree to fp32 tolerances
        np.testing.assert_allclose(out_pallas[i], out_xla[i],
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"seq={i} kv_len={kv_lens[i]}")
        # ... and both match the dequantized dense oracle
        kdq = np.zeros_like(kd[i])
        vdq = np.zeros_like(vd[i])
        for j in range(mp):
            pid = table[i, j]
            sl = slice(j * page_size, (j + 1) * page_size)
            kdq[:, sl] = (k_pool[:, pid].astype(np.float32)
                          * k_sc[:, pid, None, None])
            vdq[:, sl] = (v_pool[:, pid].astype(np.float32)
                          * v_sc[:, pid, None, None])
        want = ref.decode_attention(q[i:i + 1], jnp.asarray(kdq[None]),
                                    jnp.asarray(vdq[None]), int(kv_lens[i]))
        np.testing.assert_allclose(out_pallas[i:i + 1], np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


# As test_paged_cache's parity cases: int8 pages of the cells' widths
# (8 KV heads of 128, 128 rows) make blocks of 16 pages, so a table of
# 17 runs two blocks, with kv_len on the boundary (2048) and one past it.
INT8_PARITY_CASES = [
    (group, hkv, page_size, mp, 16, None)
    for group, hkv in [(1, 2), (2, 2), (4, 1), (8, 2)]
    for page_size, mp in [(8, 4), (16, 2), (32, 3)]
] + [
    (2, 8, 128, 17, 128, (2176, 0, 2048, 2049, 100)),
]


@pytest.mark.parametrize("group,hkv,page_size,mp,e,kv_lens",
                         INT8_PARITY_CASES)
def test_int8_paged_kernel_matches_twin_and_oracle(group, hkv, page_size,
                                                   mp, e, kv_lens):
    b = 3 if kv_lens is None else len(kv_lens)
    _check_int8_paged_parity(seed=group * 71 + page_size + mp, b=b,
                             group=group, hkv=hkv, page_size=page_size,
                             mp=mp, e=e, kv_lens=kv_lens)


def test_int8_paged_hypothesis():
    """Randomized sweep over page size / kv_len / GQA group widths."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    dims = st.tuples(
        st.integers(1, 3),                                  # b
        st.sampled_from([(1, 1), (2, 1), (2, 2), (4, 2)]),  # (group, hkv)
        st.sampled_from([8, 16]),                           # page_size
        st.integers(1, 4),                                  # pages per seq
        st.sampled_from([16, 32]),                          # e
        st.integers(0, 2**31 - 1),                          # seed
    )

    @given(dims)
    @settings(max_examples=12, deadline=None)
    def check(t):
        b, (group, hkv), page_size, mp, e, seed = t
        _check_int8_paged_parity(seed, b, group, hkv, page_size, mp, e)

    check()


def test_int8_flat_decode_matches_xla_and_oracle():
    rng = np.random.default_rng(7)
    b, hkv, group, e, s = 2, 2, 4, 32, 96
    hq = hkv * group
    q = jnp.asarray(rng.standard_normal((b, hq, e)), jnp.float32)
    kd = jnp.asarray(rng.standard_normal((b, hkv, s, e)), jnp.float32)
    vd = jnp.asarray(rng.standard_normal((b, hkv, s, e)), jnp.float32)
    kq, ks = quantize_q8(kd, -1)  # per-row scales (B, Hkv, S)
    vq, vs = quantize_q8(vd, -1)
    for kv_len in (s, 51, 1):
        out = decode_attention(q, kq, vq, kv_len, blk_kv=128,
                               k_scale=ks, v_scale=vs)
        twin = sharded_decode_attention(q, kq, vq, jnp.int32(kv_len),
                                        k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(twin),
                                   atol=2e-5, rtol=2e-5)
        want = ref.decode_attention(q, dequantize_q8(kq, ks, -1),
                                    dequantize_q8(vq, vs, -1), kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# paged pool: quantized admit / append / free-reuse
# ---------------------------------------------------------------------------


def test_paged_append_requant_masks_stale_rows():
    """A reused page's stale bytes/scale must not leak into new rows."""
    from repro.models.transformer import _paged_append_requant

    rng = np.random.default_rng(3)
    hkv, n_pages, page, e = 2, 4, 8, 16
    # pool full of huge stale garbage with huge stale scales
    pages = jnp.asarray(
        rng.integers(-127, 128, size=(hkv, n_pages, page, e)), jnp.int8)
    scales = jnp.full((hkv, n_pages), 1e6, jnp.float32)
    row = jnp.asarray(rng.standard_normal((hkv, 2, e)), jnp.float32)
    page_ids = jnp.asarray([1, 2], jnp.int32)
    slots = jnp.asarray([0, 3], jnp.int32)  # fresh page / partially live
    new_pages, new_scales = _paged_append_requant(pages, scales, page_ids,
                                                  slots, row)
    # slot 0 append: the new scale reflects ONLY the new row's absmax
    got = np.asarray(new_scales[:, 1])
    want = np.abs(np.asarray(row[:, 0])).max(-1) / 127.0
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the appended rows dequantize back to the input (half-LSB bound)
    deq0 = np.asarray(new_pages[:, 1, 0], np.float32) * got[:, None]
    assert np.abs(deq0 - np.asarray(row[:, 0])).max() <= got.max() / 2 + 1e-6


def test_continuous_engine_reuses_freed_quantized_pages():
    """More requests than the pool fits at once: admit -> free ->
    re-admit onto reused pages, quantized vs bf16 agreement intact."""
    cfg, model, params = _smoke_model()
    from repro.serving import ContinuousBatchingEngine

    def engines(kv_dtype):
        return ContinuousBatchingEngine(model, params, max_len=32,
                                        batch_size=2, page_size=8,
                                        kv_dtype=kv_dtype)

    out = engines(None).serve(_requests(cfg, 6))
    outq = engines("int8").serve(_requests(cfg, 6))
    assert set(out) == set(outq)
    assert _agreement(out, outq) >= 0.99


# ---------------------------------------------------------------------------
# end-to-end greedy agreement through both engines
# ---------------------------------------------------------------------------


def _smoke_model():
    from repro.configs import get_smoke
    from repro.models import build_model

    cfg = get_smoke("internlm2-1.8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n):
    from repro.serving import Request

    rng = np.random.default_rng(0)
    lens = [9, 13, 5, 21, 7, 16][:n]
    return [Request(rid=i,
                    prompt=rng.integers(3, cfg.vocab_size,
                                        size=(ln,)).astype(np.int32),
                    max_new_tokens=6, eos_id=-2)
            for i, ln in enumerate(lens)]


def _agreement(a, b):
    num = den = 0
    for rid in a:
        x, y = list(a[rid]), list(b[rid])
        den += max(len(x), len(y))
        num += sum(int(u == v) for u, v in zip(x, y))
    return num / den if den else 1.0


def test_e2e_greedy_agreement_wave_and_continuous():
    cfg, model, params = _smoke_model()
    from repro.serving import ContinuousBatchingEngine, ServingEngine

    reqs = _requests(cfg, 4)
    out_w = ServingEngine(model, params, max_len=48,
                          batch_size=2).serve(reqs)
    out_wq = ServingEngine(model, params, max_len=48, batch_size=2,
                           kv_dtype="int8").serve(reqs)
    assert _agreement(out_w, out_wq) >= 0.99

    out_c = ContinuousBatchingEngine(model, params, max_len=48,
                                     batch_size=2, page_size=8).serve(reqs)
    out_cq = ContinuousBatchingEngine(model, params, max_len=48,
                                      batch_size=2, page_size=8,
                                      kv_dtype="int8").serve(reqs)
    assert _agreement(out_c, out_cq) >= 0.99
    # bf16 engines agree exactly; occupancy stayed bounded by the pool
    assert _agreement(out_w, out_c) == 1.0


def test_paged_decode_step_int8_matches_bf16_argmax():
    """One decode step through the full model on an int8 paged cache."""
    cfg, model, params = _smoke_model()
    ps = 8
    plen, max_len = 11, 16
    rng = np.random.default_rng(3)
    prompts = rng.integers(3, cfg.vocab_size, size=(2, plen)).astype(np.int32)

    logits, _ = model.prefill(params, cfg, jnp.asarray(prompts), max_len)
    token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]

    def run(kv_dtype):
        cache = model.make_cache(2, max_len, cache_layout="paged",
                                 page_size=ps, kv_dtype=kv_dtype)
        table = np.zeros((2, 2), np.int32)
        for i, ids in enumerate([[1, 2], [3, 4]]):
            _, one_c = model.prefill(params, cfg,
                                     jnp.asarray(prompts[i:i + 1]), max_len)
            cache = model.write_prefill_pages(cache, one_c,
                                              jnp.asarray(ids, jnp.int32))
            table[i] = ids
        got, cache = model.paged_decode_step(
            params, cfg, token, cache, jnp.asarray(table),
            jnp.full((2,), plen, jnp.int32),
        )
        return got, cache

    want, _ = run(None)
    got, cache_q = run("int8")
    # int8 pools actually hold int8 + scale side-tables
    blk = cache_q["units"]["b0"]
    assert blk["k"].dtype == jnp.int8 and "k_scale" in blk
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=0.15, rtol=0.15)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got[:, -1], -1)),
                                  np.asarray(jnp.argmax(want[:, -1], -1)))


# ---------------------------------------------------------------------------
# simulator + search: precision as a tiling factor
# ---------------------------------------------------------------------------


def test_sim_charges_quantized_kv_dma_and_scales():
    from repro.sim import (
        EDGE_HW,
        PagedDecodeWorkload,
        Tiling,
        build_schedule,
        simulate,
    )

    w = PagedDecodeWorkload("d", heads=8, emb=64, group=4,
                            kv_lens=(100, 700, 33, 512))
    wq = PagedDecodeWorkload("dq", heads=8, emb=64, group=4,
                             kv_lens=(100, 700, 33, 512), kv_bpe=1)
    t = Tiling(1, 1, 64)
    r = simulate(build_schedule("paged_decode", w, t, EDGE_HW), EDGE_HW)
    rq = simulate(build_schedule("paged_decode", wq, t, EDGE_HW), EDGE_HW)
    hw_bpe = EDGE_HW.bytes_per_elem
    q_io = 2 * w.heads * w.group * w.emb * hw_bpe * w.batch
    for res, wl in ((r, w), (rq, wq)):
        kv = wl.kv_bytes(hw_bpe, 64)
        assert res.dram_read_bytes + res.dram_write_bytes == kv + q_io
    # int8 halves the KV stream (scales cost < 1%) and cuts cycles
    assert rq.dram_read_bytes < 0.55 * r.dram_read_bytes
    assert rq.cycles < r.cycles
    # the scales side-traffic is visible in the workload model
    n_pages = sum(-(-n // 64) for n in w.kv_lens)
    assert (wq.kv_bytes(hw_bpe, 64)
            == w.kv_bytes(hw_bpe, 64) // 2 + 2 * w.heads * n_pages * 4)


def test_search_selects_int8_for_long_context_decode():
    from repro.sim import EDGE_HW, PagedDecodeWorkload, search_tiling

    w = PagedDecodeWorkload("long", heads=8, emb=128, group=4,
                            kv_lens=(700, 123, 1500, 64, 2048, 9, 511,
                                     1024))
    res = search_tiling("paged_decode", w, EDGE_HW, strategy="grid")
    assert res.tiling.kv_bpe == 1  # precision searched like page size
    assert res.tiling.nq == 1 and 16 <= res.tiling.nkv < w.seq


def test_tuner_ranks_precisions():
    from repro.core.autotune import tune_attention

    kw = dict(b_h=16, n_q=128, n_kv=32768, e=128)
    native = tune_attention(**kw)
    swept = tune_attention(kv_itemsizes=(2, 1), **kw)
    # long-KV decode-like shape is HBM-bound: int8 KV wins the sweep
    assert swept.kv_itemsize == 1
    assert swept.est_seconds < native.est_seconds
    # memoization: same key returns the cached object
    assert tune_attention(**kw) is native
