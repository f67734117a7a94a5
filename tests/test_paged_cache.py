"""Paged KV-cache subsystem: kernel parity, pool bookkeeping, serving.

Three layers of the new subsystem (DESIGN.md §4) are pinned here:

* the paged decode kernel (pallas interpret mode) and its XLA gather
  twin must match the dense decode oracle per sequence, for any page
  size / per-sequence kv_len / GQA group / pool permutation;
* the host-side page-pool manager must enforce exhaustion, reuse freed
  pages, and grow sequences across page boundaries;
* the continuous-batching engine must reproduce the dense wave
  engine's greedy output on the same mixed-length request set.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import paged_decode_attention
from repro.models.attention import paged_decode_attention as model_paged
from repro.serving.paged_cache import (
    SCRATCH_PAGE,
    PagedKVCacheManager,
    PagePoolExhausted,
)

jax.config.update("jax_enable_x64", False)


def _scatter_pool(kd, vd, page_size, rng):
    """Scatter dense (B, Hkv, S, E) caches into a shuffled page pool."""
    b, hkv, s, e = kd.shape
    mp = s // page_size
    n_pages = b * mp + 1  # + scratch page 0
    perm = rng.permutation(np.arange(1, n_pages))
    table = perm.reshape(b, mp).astype(np.int32)
    k_pool = np.zeros((hkv, n_pages, page_size, e), kd.dtype)
    v_pool = np.zeros((hkv, n_pages, page_size, e), kd.dtype)
    for i in range(b):
        for j in range(mp):
            k_pool[:, table[i, j]] = kd[i, :, j * page_size:(j + 1) * page_size]
            v_pool[:, table[i, j]] = vd[i, :, j * page_size:(j + 1) * page_size]
    return k_pool, v_pool, table


def _check_paged_parity(seed, b, group, hkv, page_size, mp, e, path,
                        kv_lens=None):
    rng = np.random.default_rng(seed)
    s = page_size * mp
    hq = group * hkv
    q = jnp.asarray(rng.standard_normal((b, hq, e)), jnp.float32)
    kd = rng.standard_normal((b, hkv, s, e)).astype(np.float32)
    vd = rng.standard_normal((b, hkv, s, e)).astype(np.float32)
    if kv_lens is None:
        kv_lens = rng.integers(0, s + 1, size=b).astype(np.int32)
        kv_lens[0] = s  # always cover the full-cache edge
    kv_lens = np.asarray(kv_lens, np.int32)
    k_pool, v_pool, table = _scatter_pool(kd, vd, page_size, rng)

    fn = paged_decode_attention if path == "pallas" else model_paged
    out = np.asarray(fn(q, jnp.asarray(k_pool), jnp.asarray(v_pool),
                        jnp.asarray(table), jnp.asarray(kv_lens)))
    for i in range(b):
        if kv_lens[i] == 0:
            continue  # no live keys: output unspecified (engine masks it)
        want = ref.decode_attention(q[i:i + 1], jnp.asarray(kd[i:i + 1]),
                                    jnp.asarray(vd[i:i + 1]),
                                    int(kv_lens[i]))
        np.testing.assert_allclose(
            out[i:i + 1], np.asarray(want), atol=2e-5, rtol=2e-5,
            err_msg=f"path={path} seq={i} kv_len={kv_lens[i]}",
        )


# Small shapes: every slot's pages in one kernel block. At the cells'
# widths (8 KV heads of 128, fp32 pages of 128 rows) a block holds 4 of
# the 6 or 5 pages, so slots run 1-2 blocks: kv_len on a block boundary
# (512) and one past it, one live page, kv_len 0 first, last and next
# to full slots, and a table that is not a whole number of blocks.
PARITY_CASES = [
    (group, hkv, page_size, mp, 16, None)
    for group, hkv in [(1, 2), (2, 2), (4, 1), (8, 2)]
    for page_size, mp in [(8, 4), (16, 2), (32, 3)]
] + [
    (2, 8, 128, 6, 128, (768, 0, 768, 512, 513, 37)),
    (1, 8, 128, 5, 128, (0, 513, 1, 0)),
]


@pytest.mark.parametrize("path", ["pallas", "xla"])
@pytest.mark.parametrize("group,hkv,page_size,mp,e,kv_lens", PARITY_CASES)
def test_paged_decode_matches_dense(path, group, hkv, page_size, mp, e,
                                    kv_lens):
    b = 3 if kv_lens is None else len(kv_lens)
    _check_paged_parity(seed=group * 100 + page_size + mp, b=b, group=group,
                        hkv=hkv, page_size=page_size, mp=mp, e=e, path=path,
                        kv_lens=kv_lens)


def test_decode_block_size_from_shapes():
    from repro.kernels.paged_decode_attention import (
        DECODE_VMEM_BUDGET,
        decode_pages_per_block,
    )

    # the cells: 8 KV heads of 128, bf16 pages of 64 rows, 80 per slot
    ppb = decode_pages_per_block(8, 64, 128, 2, 80)
    assert ppb == 16
    assert 4 * ppb * 8 * 64 * 128 * 2 <= DECODE_VMEM_BUDGET < 16 * 2**20
    assert decode_pages_per_block(8, 64, 128, 1, 80) == 2 * ppb  # int8
    # the parity shapes above
    assert decode_pages_per_block(2, 32, 16, 4, 3) == 3
    assert decode_pages_per_block(8, 128, 128, 4, 6) == 4
    assert decode_pages_per_block(8, 2048, 128, 4, 6) == 1


def test_paged_decode_hypothesis():
    """Randomized sweep over page size / kv_len / GQA group / pool layout."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    dims = st.tuples(
        st.integers(1, 3),                  # b
        st.sampled_from([(1, 1), (2, 1), (2, 2), (4, 2)]),  # (group, hkv)
        st.sampled_from([8, 16]),           # page_size
        st.integers(1, 4),                  # pages per sequence
        st.sampled_from([16, 32]),          # e
        st.integers(0, 2**31 - 1),          # seed (drives kv_lens + pool)
    )

    @given(dims)
    @settings(max_examples=12, deadline=None)
    def check(t):
        b, (group, hkv), page_size, mp, e, seed = t
        _check_paged_parity(seed, b, group, hkv, page_size, mp, e,
                            path="pallas")

    check()


def test_paged_bf16():
    rng = np.random.default_rng(11)
    b, hkv, group, ps, mp, e = 2, 2, 2, 16, 3, 32
    s = ps * mp
    kd = rng.standard_normal((b, hkv, s, e)).astype(np.float32)
    vd = rng.standard_normal((b, hkv, s, e)).astype(np.float32)
    q = rng.standard_normal((b, hkv * group, e)).astype(np.float32)
    k_pool, v_pool, table = _scatter_pool(kd, vd, ps, rng)
    kv_lens = np.array([s, 20], np.int32)
    out = paged_decode_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k_pool, jnp.bfloat16),
        jnp.asarray(v_pool, jnp.bfloat16), jnp.asarray(table),
        jnp.asarray(kv_lens),
    )
    for i in range(b):
        want = ref.decode_attention(
            jnp.asarray(q[i:i + 1], jnp.bfloat16),
            jnp.asarray(kd[i:i + 1], jnp.bfloat16),
            jnp.asarray(vd[i:i + 1], jnp.bfloat16), int(kv_lens[i]),
        )
        np.testing.assert_allclose(
            np.asarray(out[i:i + 1], np.float32),
            np.asarray(want, np.float32), atol=2e-2, rtol=2e-2,
        )


# ---------------------------------------------------------------------------
# page-pool manager
# ---------------------------------------------------------------------------


def test_pool_exhaustion_and_realloc_reuse():
    mgr = PagedKVCacheManager(9, 4, num_slots=4, max_pages_per_seq=8)
    assert mgr.available == 8  # page 0 is the reserved scratch page
    a = mgr.admit(0, prompt_len=13)          # 4 pages
    b = mgr.admit(1, prompt_len=9, reserve=4)  # 4 pages (9 + 4 -> 13)
    assert SCRATCH_PAGE not in a + b
    assert len(set(a) | set(b)) == 8 and mgr.available == 0
    with pytest.raises(PagePoolExhausted):
        mgr.alloc(1)
    assert not mgr.can_admit(1)

    mgr.free(0)
    assert mgr.available == 4
    c = mgr.admit(2, prompt_len=16)
    assert set(c) == set(a)  # LIFO free list reissues the freed pages
    assert mgr.peak_pages_used == 8


def test_append_grows_across_page_boundary():
    mgr = PagedKVCacheManager(6, 4, num_slots=2, max_pages_per_seq=4)
    mgr.admit(0, prompt_len=4)            # exactly one full page
    assert mgr.pages_used == 1
    mgr.append(0)                         # token 5 crosses into page 2
    assert mgr.pages_used == 2
    for _ in range(3):
        mgr.append(0)                     # fill page 2
    assert mgr.pages_used == 2
    mgr.append(0)
    assert mgr.pages_used == 3
    assert mgr.kv_lens()[0] == 9

    # a reservation covers appends without further allocation
    mgr.admit(1, prompt_len=2, reserve=6)
    used = mgr.pages_used
    for _ in range(6):
        mgr.append(1)
    assert mgr.pages_used == used


def test_table_views_pad_with_scratch():
    mgr = PagedKVCacheManager(8, 4, num_slots=3, max_pages_per_seq=4)
    ids = mgr.admit(1, prompt_len=6)
    t = mgr.table()
    assert t.shape == (3, 4) and t.dtype == np.int32
    assert list(t[1, :2]) == ids
    assert (t[0] == SCRATCH_PAGE).all() and (t[1, 2:] == SCRATCH_PAGE).all()
    assert list(mgr.kv_lens()) == [0, 6, 0]
    with pytest.raises(ValueError):
        mgr.admit(0, prompt_len=100)  # > max_pages_per_seq


# ---------------------------------------------------------------------------
# serving: paged step + continuous batching vs the dense wave engine
# ---------------------------------------------------------------------------


def _smoke_model():
    from repro.configs import get_smoke
    from repro.models import build_model

    cfg = get_smoke("internlm2-1.8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_paged_decode_step_matches_dense_step():
    """One decode step through the full model: paged == dense logits."""
    cfg, model, params = _smoke_model()
    ps, n_pages = 8, 2
    plen, max_len = 11, 16
    rng = np.random.default_rng(3)
    prompts = rng.integers(3, cfg.vocab_size, size=(2, plen)).astype(np.int32)

    logits, dense_cache = model.prefill(params, cfg, jnp.asarray(prompts),
                                        max_len)
    token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    want, _ = model.decode_step(params, cfg, token, dense_cache,
                                jnp.int32(plen))

    cache = model.make_cache(2, max_len, cache_layout="paged", page_size=ps)
    table = np.zeros((2, n_pages), np.int32)
    for i, ids in enumerate([[1, 2], [3, 4]]):
        one_l, one_c = model.prefill(params, cfg,
                                     jnp.asarray(prompts[i:i + 1]), max_len)
        cache = model.write_prefill_pages(cache, one_c,
                                          jnp.asarray(ids, jnp.int32))
        table[i] = ids
    got, _ = model.paged_decode_step(
        params, cfg, token, cache, jnp.asarray(table),
        jnp.full((2,), plen, jnp.int32),
    )
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)
    assert int(jnp.argmax(got[0, -1])) == int(jnp.argmax(want[0, -1]))


def test_continuous_batching_matches_wave_engine():
    from repro.serving import ContinuousBatchingEngine, Request, ServingEngine

    cfg, model, params = _smoke_model()
    rng = np.random.default_rng(0)

    def reqs():
        return [Request(rid=i,
                        prompt=rng.integers(3, cfg.vocab_size,
                                            size=(n,)).astype(np.int32),
                        max_new_tokens=m, eos_id=-2)
                for i, (n, m) in enumerate([(9, 3), (9, 3), (5, 1), (13, 4)])]

    rng = np.random.default_rng(0)
    out_w = ServingEngine(model, params, max_len=32,
                          batch_size=2).serve(reqs())
    rng = np.random.default_rng(0)
    cont = ContinuousBatchingEngine(model, params, max_len=32, batch_size=2,
                                    page_size=8)
    out_c = cont.serve(reqs())
    assert set(out_c) == set(out_w)
    for rid in out_w:
        np.testing.assert_array_equal(out_w[rid], out_c[rid],
                                      err_msg=f"rid {rid}")
    # pages were freed: pool high-water stays below full residency
    assert cont.peak_pages_used <= cont.num_pages - 1


# ---------------------------------------------------------------------------
# simulator: page-granular KV DMA + page-size search
# ---------------------------------------------------------------------------


def test_sim_paged_decode_charges_page_granular_dma():
    from repro.sim import (
        EDGE_HW,
        PagedDecodeWorkload,
        Tiling,
        build_schedule,
        simulate,
    )

    w = PagedDecodeWorkload("d", heads=8, emb=64, group=4,
                            kv_lens=(100, 700, 33, 512))
    fine = simulate(build_schedule("paged_decode", w, Tiling(1, 1, 64),
                                   EDGE_HW), EDGE_HW)
    coarse = simulate(build_schedule("paged_decode", w, Tiling(1, 1, 512),
                                     EDGE_HW), EDGE_HW)
    # ragged tails waste more DMA at coarse pages; model and sim agree
    assert coarse.dram_read_bytes > fine.dram_read_bytes
    hw_bpe = EDGE_HW.bytes_per_elem
    for r, page in ((fine, 64), (coarse, 512)):
        kv = w.kv_bytes(hw_bpe, page)
        q_io = 2 * w.heads * w.group * w.emb * hw_bpe * w.batch
        assert r.dram_read_bytes + r.dram_write_bytes == kv + q_io
    # useful-MAC lower bound: tile padding never undercounts
    assert fine.mac_ops >= w.mac_ops


def test_sim_page_size_search_finds_interior_optimum():
    from repro.sim import EDGE_HW, PagedDecodeWorkload, search_tiling

    w = PagedDecodeWorkload("d", heads=8, emb=128, group=4,
                            kv_lens=(700, 123, 1500, 64, 2048, 9, 511, 1024))
    res = search_tiling("paged_decode", w, EDGE_HW, strategy="grid")
    assert res.tiling.nq == 1  # decode space: N_Q tier collapsed
    # descriptor overhead vs boundary waste: optimum away from the edges
    assert 16 < res.tiling.nkv < w.seq
    assert res.result.cycles > 0 and res.evals == len(res.history)
