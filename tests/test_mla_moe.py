"""Latent attention (MLA) and the dropless held-expert layer at SMOKE size
against a plain float32 reference of the layout, on seeded random
weights: the paged path (chunked prefill, then decode through the latent
pool), the routing rules, one chip's share of the expert layer, batch
independence, the latent decode kernel against its XLA twin, the
refusals, and the engine's expert-row count."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels import ops as kops
from repro.kernels.paged_decode_attention import paged_decode_attention_flat
from repro.models import attention as attn_mod
from repro.models import build_model
from repro.models.moe import route, routed_experts

F32 = jnp.float32


def _cfg(**kw):
    return dataclasses.replace(get_smoke("moonlight-16b-a3b"),
                               compute_dtype=F32, **kw)


def _params(cfg, seed=0):
    """Seeded random weights; norms, the correction bias and the scales
    away from their init values so that each does work."""
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for (path, leaf), key in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            leaf = 0.2 * jax.random.normal(key, leaf.shape, leaf.dtype)
        elif "score_bias" in name:
            leaf = 0.05 * jax.random.normal(key, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree.unflatten(tree, out)


# ---- the plain float32 reference (published, expanded form) ----

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def _rope(x, pos, theta):
    e = x.shape[-1]
    ang = pos[:, None] * theta ** (-jnp.arange(0, e, 2) / e)
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), -1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def _ref_attn(p, x, cfg):
    a, s, hh = cfg.mla, x.shape[0], cfg.num_heads
    h = _rms(x, p["norm"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(s, hh, -1)
    kv = h @ p["wkv_a"]
    c = _rms(kv[:, :a.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    pos = jnp.arange(s, dtype=F32)
    q_pe = _rope(q[..., a.qk_nope_head_dim:], pos, cfg.rope_theta)
    k_pe = _rope(kv[:, a.kv_lora_rank:], pos, cfg.rope_theta)
    k = (c @ p["w_uk"]).reshape(s, hh, -1)
    v = (c @ p["w_uv"]).reshape(s, hh, -1)
    sc = (jnp.einsum("qhn,khn->hqk", q[..., :a.qk_nope_head_dim], k)
          + jnp.einsum("qhe,ke->hqk", q_pe, k_pe)) * a.qk_head_dim ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("hqk,khv->qhv", jax.nn.softmax(sc, -1), v)
    return o.reshape(s, -1) @ p["wo"]


def _ref_experts(p, x, cfg):
    """The layer over every expert of the router (held or not), plus the
    shared experts: ``held`` picks which experts' part is summed."""
    m = cfg.moe
    lo, hi = m.held_range
    h = _rms(x, p["norm"], cfg.norm_eps)
    scores = jax.nn.sigmoid(h @ p["router"])
    _, ids = jax.lax.top_k(scores + p["score_bias"], m.top_k)
    w = jnp.take_along_axis(scores, ids, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * m.routed_scale
    y = _swiglu(h, p["shared"])
    for i in range(lo, hi):
        wi = jnp.sum(jnp.where(ids == i, w, 0.0), -1, keepdims=True)
        y = y + wi * _swiglu(h, jax.tree.map(lambda t: t[i - lo], {
            k: p[k] for k in ("w_gate", "w_up", "w_down")}))
    return y


def _ref_logits(params, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens] * jnp.sqrt(F32(cfg.d_model))
        for name, kind in (("lead", "mla"), ("units", "mla_moe")):
            stack = params[name]["b0"]
            for i in range(jax.tree.leaves(stack)[0].shape[0]):
                p = jax.tree.map(lambda t: t[i], stack)
                x = x + _ref_attn(p["attn"], x, cfg)
                if kind == "mla":
                    h = _rms(x, p["ffn"]["norm"], cfg.norm_eps)
                    x = x + _swiglu(h, p["ffn"])
                else:
                    x = x + _ref_experts(p["ffn"], x, cfg)
        return _rms(x, params["final_norm"], cfg.norm_eps) @ params[
            "unembed"]


# ---- the paged path against the reference ----

@pytest.mark.parametrize("attn_impl", ["pallas", "xla"])
def test_chunked_prefill_then_paged_decode_matches_reference(attn_impl):
    """Two prompt chunks into the latent pool (the second ragged), then
    teacher-forced decode steps of a batch whose other slot idles: every
    row's logits equal the reference's full forward, in float32."""
    cfg = _cfg(attn_impl=attn_impl)
    model = build_model(cfg)
    params = _params(cfg)
    page, chunk, n_prompt, n = 4, 8, 13, 20
    tokens = jax.random.randint(jax.random.PRNGKey(2), (n,), 0,
                                cfg.vocab_size)
    want = np.asarray(_ref_logits(params, tokens, cfg))
    cache = model.make_cache(2, 32, cache_layout="paged", page_size=page,
                             num_pages=12)
    assert all(leaf.shape[2] == 12 for leaf in jax.tree.leaves(cache))
    seq = jnp.arange(1, 9, dtype=jnp.int32)           # the sequence's pages
    for q0 in range(0, n_prompt, chunk):
        live = min(chunk, n_prompt - q0)
        toks = jnp.zeros((1, chunk), jnp.int32).at[0, :live].set(
            tokens[q0:q0 + live])
        logits, cache = model.prefill_chunk(
            params, cfg, toks, cache, seq, seq[q0 // page:(q0 + chunk)
                                               // page], q0, live)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   want[q0 + live - 1], atol=2e-4,
                                   rtol=2e-4)
    table = jnp.stack([seq, jnp.zeros_like(seq)])
    for pos in range(n_prompt, n):
        tok = jnp.stack([tokens[pos], 0])[:, None]
        logits, cache = model.paged_decode_step(
            params, cfg, tok, cache, table, jnp.asarray([pos, 0], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[pos],
                                   atol=2e-4, rtol=2e-4, err_msg=str(pos))


def test_forward_matches_reference():
    cfg = _cfg()
    params = _params(cfg, seed=3)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 12), 0,
                                cfg.vocab_size)
    logits, aux = build_model(cfg).forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(_ref_logits(params, tokens[0],
                                                      cfg)),
                               atol=2e-4, rtol=2e-4)
    assert float(aux) == 0.0


# ---- routing rules ----

def _layer(cfg, seed=5):
    return _params(cfg, seed)["units"]["b0"]["ffn"]


def test_bias_changes_the_choice_not_the_weights():
    cfg = _cfg()
    m = cfg.moe
    p = jax.tree.map(lambda t: t[0], _layer(cfg))
    h = jax.random.normal(jax.random.PRNGKey(6), (64, cfg.d_model))
    scores = jax.nn.sigmoid(h @ p["router"])
    no_bias = dict(p, score_bias=jnp.zeros_like(p["score_bias"]))
    bias = dict(p, score_bias=jnp.zeros_like(p["score_bias"]).at[3].set(1.0))
    ids0, w0 = route(no_bias, h, m)
    ids1, w1 = route(bias, h, m)
    assert (ids1 == 3).any(axis=1).all()          # expert 3 always chosen
    assert not (ids0 == 3).any(axis=1).all()
    for ids, w in ((ids0, w0), (ids1, w1)):       # weights: the raw scores
        s = jnp.take_along_axis(scores, ids, -1)
        np.testing.assert_allclose(
            np.asarray(w), np.asarray(s / s.sum(-1, keepdims=True)
                                      * m.routed_scale), rtol=1e-5)


@pytest.mark.parametrize("change", [{"scoring": "softmax"},
                                    {"norm_topk": False}])
def test_route_refuses_routings_it_does_not_implement(change):
    cfg = _cfg()
    p = jax.tree.map(lambda t: t[0], _layer(cfg))
    h = jnp.zeros((4, cfg.d_model), F32)
    with pytest.raises(NotImplementedError, match="sigmoid"):
        route(p, h, dataclasses.replace(cfg.moe, **change))


def test_renormalisation_counts_unheld_experts_and_scale_applies():
    """Holding experts [0, 2): the chosen weights are renormalised over
    all top_k chosen, held or not, and scaled; the layer sums only the
    held experts' part (plus the shared experts)."""
    cfg = _cfg()
    held = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                            held=(0, 2)))
    p = jax.tree.map(lambda t: t[0], _layer(cfg))
    p_held = dict(p, **{k: p[k][:2] for k in ("w_gate", "w_up", "w_down")})
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, cfg.d_model))
    y, rows = routed_experts(p_held, x, held)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda xi: _ref_experts(p_held, xi, held))(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4,
                               rtol=1e-4)
    h = _rms(x.reshape(-1, cfg.d_model), p["norm"], cfg.norm_eps)
    ids, w = route(p, h, cfg.moe)
    assert int(rows) == int(jnp.sum(ids < 2))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), cfg.moe.routed_scale,
                               rtol=1e-5)
    # the scaling factor multiplies the routed part only
    unscaled = dataclasses.replace(held, moe=dataclasses.replace(
        held.moe, routed_scale=1.0))
    no_shared = dict(p_held, shared=jax.tree.map(jnp.zeros_like,
                                                 p["shared"]))
    y1, _ = routed_experts(no_shared, x, unscaled)
    y2, _ = routed_experts(no_shared, x, held)
    np.testing.assert_allclose(np.asarray(y2),
                               np.asarray(y1) * cfg.moe.routed_scale,
                               rtol=1e-4, atol=1e-5)


def test_shares_add_up_to_the_uncut_layer():
    """One chip's share (model-configs guide §4): the 8 shares of one
    expert each, with the shared experts counted once, add up to the
    layer that holds all 8."""
    cfg = _cfg()
    p = jax.tree.map(lambda t: t[0], _layer(cfg))
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 8, cfg.d_model))
    whole, rows = routed_experts(p, x, cfg)
    assert int(rows) == 3 * 8 * cfg.moe.top_k      # dropless: every row
    parts = []
    for i in range(cfg.moe.num_experts):
        share = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=(i, i + 1)))
        ps = dict(p, **{k: p[k][i:i + 1]
                        for k in ("w_gate", "w_up", "w_down")})
        parts.append(routed_experts(ps, x, share)[0])
    h = _rms(x, p["norm"], cfg.norm_eps)
    shared = _swiglu(h, p["shared"])
    total = sum(parts) - (cfg.moe.num_experts - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-4, rtol=1e-4)


def test_logits_do_not_depend_on_the_batch():
    """Dropless: one slot's decode logits are the same alone and beside
    other sequences (bf16, the kernel's latent mode), to rounding."""
    cfg = dataclasses.replace(get_smoke("moonlight-16b-a3b"),
                              attn_impl="pallas")
    model = build_model(cfg)
    params = _params(cfg, seed=9)
    page, n_pages = 4, 40
    tokens = jax.random.randint(jax.random.PRNGKey(10), (4, 9), 0,
                                cfg.vocab_size)
    tables = jnp.arange(1, 1 + 4 * 8, dtype=jnp.int32).reshape(4, 8)

    def decode(batch):
        cache = model.make_cache(batch, 32, cache_layout="paged",
                                 page_size=page, num_pages=n_pages)
        for b in range(batch):
            _, cache = model.prefill_chunk(params, cfg, tokens[b:b + 1, :8],
                                           cache, tables[b], tables[b, :2],
                                           0, 8)
        logits, _ = model.paged_decode_step(
            params, cfg, tokens[:batch, 8:], cache, tables[:batch],
            jnp.full((batch,), 8, jnp.int32))
        return np.asarray(logits[:, 0], np.float32)

    alone, beside = decode(1), decode(4)
    np.testing.assert_allclose(alone[0], beside[0], atol=1e-6, rtol=0)


# ---- the latent decode kernel against its XLA twin ----

@pytest.mark.parametrize("lens", [
    (1, 4, 8, 9),       # one live page, a whole block, one past a block
    (7, 16, 17, 24),    # tails inside, on and past block boundaries
])
def test_latent_decode_kernel_matches_twin(lens):
    """Blocks of 2 pages of 4 rows: multi-block walks, tails on and one
    past a block boundary, one live page; V is the first 32 of 128."""
    b, hq, width, rank, page, max_pages = 4, 4, 128, 32, 4, 6
    rng = np.random.default_rng(sum(lens))
    table = jnp.asarray(rng.permutation(np.arange(1, 30))[:b * max_pages]
                        .reshape(b, max_pages), jnp.int32)
    kq, kp = jax.random.split(jax.random.PRNGKey(11))
    q = jax.random.normal(kq, (b, hq, width), F32)
    pool = jax.random.normal(kp, (1, 30, page, width), F32)
    kv_lens = jnp.asarray(lens, jnp.int32)
    got = paged_decode_attention_flat(
        q.reshape(b, 1, hq, width), pool, None, table, kv_lens,
        pages_per_block=2, sm_scale=0.1, v_dim=rank, interpret=True)
    want = attn_mod.paged_decode_attention(q, pool, None, table, kv_lens,
                                           impl="xla", sm_scale=0.1,
                                           v_dim=rank)
    np.testing.assert_allclose(np.asarray(got).reshape(b, hq, rank),
                               np.asarray(want), atol=3e-5, rtol=3e-5)
    wrapped = kops.paged_decode_attention(q, pool, None, table, kv_lens,
                                          sm_scale=0.1, v_dim=rank,
                                          interpret=True)
    np.testing.assert_allclose(np.asarray(wrapped), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


# ---- refusals and the engine's expert rows ----

@pytest.mark.parametrize("kw", [{"spec_depth": 2}, {"kv_dtype": "int8"},
                                {"prefix_cache": True}])
def test_engine_refuses_what_latent_attention_lacks(kw):
    from repro.serving import ContinuousBatchingEngine

    model = build_model(get_smoke("moonlight-16b-a3b"))
    with pytest.raises(NotImplementedError, match="latent attention"):
        ContinuousBatchingEngine(model, None, max_len=32, batch_size=2,
                                 page_size=4, **kw)


def test_model_refuses_verify_int8_pages_and_sharding():
    from repro.serving.sharded import ShardedContinuousBatchingEngine

    cfg = get_smoke("moonlight-16b-a3b")
    model = build_model(cfg)
    with pytest.raises(NotImplementedError, match="verify"):
        model.paged_verify_step(None, cfg, None, None, None, None, None)
    with pytest.raises(NotImplementedError, match="int8"):
        model.make_cache(2, 32, cache_layout="paged", page_size=4,
                         kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="sharded"):
        ShardedContinuousBatchingEngine(model, None, shard=1, max_len=32)


def test_engine_counts_expert_rows_in_its_step_spans():
    """With the tracer on, each step span carries the step's (token, held
    expert) rows, from the step's one transfer, and the counter
    ``moe.held_rows`` follows them; served tokens are unchanged."""
    from repro.obs import Tracer
    from repro.serving import ContinuousBatchingEngine, Request

    cfg = dataclasses.replace(get_smoke("moonlight-16b-a3b"),
                              attn_impl="pallas")
    model = build_model(cfg)
    params = _params(cfg, seed=12)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(3, 500, size=(n,))
                    .astype(np.int32), max_new_tokens=4, eos_id=-1)
            for i, n in enumerate((5, 11))]
    outs = []
    for tracer in (None, Tracer(enabled=True)):
        eng = ContinuousBatchingEngine(model, params, max_len=32,
                                       batch_size=2, page_size=4,
                                       chunk_size=8, tracer=tracer)
        outs.append(eng.serve(reqs))
    for r in reqs:
        np.testing.assert_array_equal(outs[0][r.rid], outs[1][r.rid])
    # a page holds one bf16 latent row (padded to the pool's width) a
    # token and layer
    assert eng.kv_bytes_per_page() == cfg.num_layers * 4 * 128 * 2
    events = tracer.export()["traceEvents"]
    steps = [e["args"] for e in events
             if e.get("name") == "step" and e.get("ph") == "X"]
    counts = [e["args"]["value"] for e in events
              if e.get("name") == "moe.held_rows"]
    assert steps and all("expert_rows" in a for a in steps)
    assert counts == [a["expert_rows"] for a in steps]
    # a decode-only step with both slots live: 2 tokens x top_k rows in
    # each of the expert layers (every expert is held)
    n_moe = cfg.num_layers - cfg.dense_lead
    both = [a["expert_rows"] for a in steps
            if a["kind"] == "decode" and a["live_decode"] == 2]
    assert both and set(both) == {2 * cfg.moe.top_k * n_moe}
