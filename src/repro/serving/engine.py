"""Serving engines: dense batched waves and paged continuous batching.

``ServingEngine`` is the baseline host loop around the serving-shape
step functions: it pads a wave of equal-length requests to a common
prompt, allocates a dense (batch, max_len) cache per wave, prefills
once, then decodes greedily, and cannot admit new work until the whole
wave retires.

``ContinuousBatchingEngine`` removes both restrictions with the paged
KV subsystem (serving/paged_cache.py, DESIGN.md §4) and admits prompts
in fixed-size CHUNKS co-scheduled with decode (DESIGN.md §6): one
long-lived decode batch over global page pools; finished sequences free
their pages, and each engine step packs up to ``chunk_size`` prompt
tokens from the head-of-queue request alongside all live decode slots —
prefill writes straight into the allocated pages (no dense batch-1
cache, no copy-on-admit scatter, one compile shape per step kind), and
long prompts no longer head-of-line-block decode.

Both engines run every request through the lifecycle state machine of
``serving/lifecycle.py`` (DESIGN.md §7): malformed requests become one
FAILED result instead of an exception that kills the wave, deadlines
and cancellation retire live slots mid-decode, a jitted finite-logit
guard isolates a NaN/inf step to its slot, and — on the paged engine —
mid-decode pool exhaustion preempts the youngest live request
(release + requeue + chunked re-prefill of prompt+generated, so greedy
determinism keeps the continuation token-for-token identical) instead
of crashing the batch. Fault injection (``serving/faults.py``) threads
through both engines behind a no-op default; ``engine.auditor`` runs
the page-pool invariant check after every step when set.

Both engines carry a ``MetricsRegistry`` (``engine.metrics``, fresh per
``serve()`` call) holding the per-token wall-clock timestamps, pool
occupancy, step-time histograms and preemption/NaN counters the
benchmarks read — ``token_walltimes`` / ``occupancy_log`` /
``preemption_count`` / ``recompute_tokens`` remain as thin read-only
views onto it — and an optional ``Tracer`` (DESIGN.md §8) that, when
enabled, records per-request lifecycle spans driven by the
``lifecycle.py`` state machine, per-step phase spans (``admit``, the
speculative ``draft``, then ``step``, annotated with batch composition —
compile-shape kind, chunk tokens, live decode slots — around ``pack``,
``dispatch`` and ``host_sync``, then ``commit``), pool-occupancy counter
tracks, and preemption/NaN instants; under a running ``jax.profiler``
trace the phase spans land in the device trace as well. The default
``NULL_TRACER`` costs one truthiness check per span.
"""

from __future__ import annotations

import functools
import itertools
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.autotune import (
    tune_cache_reserve,
    tune_pool_headroom,
    tune_prefill_chunk,
    tune_spec_depth,
)
from repro.models.api import Model
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER
from repro.serving.drafter import NgramDrafter
from repro.serving.faults import NO_FAULTS
from repro.serving.lifecycle import (
    Request,
    RequestRecord,
    RequestState,
    TERMINAL_STATES,
    validate_request,
)
from repro.serving.paged_cache import (
    SCRATCH_PAGE,
    PagedKVCacheManager,
    PagePoolExhausted,
    page_footprint_bytes,
)

__all__ = ["Request", "ServingEngine", "ContinuousBatchingEngine"]


def _finite_rows(logits):
    """(rows, V) -> (rows,) bool: the cheap jitted NaN/inf guard on a
    step's output logits. Runs inside the step dispatch, so detection
    costs one reduction — no extra host transfer."""
    return jnp.all(jnp.isfinite(logits), axis=-1)


# lifecycle states that open a nested phase span on the request's track
_PHASE_STATES = frozenset({
    RequestState.PREFILLING, RequestState.DECODING, RequestState.PREEMPTED,
})


def _trace_request(rec: RequestRecord, tracer) -> None:
    """Open a per-request lifecycle span and drive its nested phase
    spans off the state machine itself: every ``RequestRecord.to()``
    closes the span of the state it leaves and opens one for the state
    it enters (prefilling / decoding / preempted), so preemption +
    chunked re-prefill shows up as nested spans inside ONE request span
    — no emit sites scattered through the scheduler (DESIGN.md §8)."""
    if not tracer.enabled:
        return
    track = f"req{rec.rid}"
    tracer.begin("request", track=track, cat="lifecycle", args={
        "rid": rec.rid,
        "prompt_len": int(len(rec.request.prompt)),
        "max_new_tokens": int(rec.request.max_new_tokens),
    })

    def observe(r: RequestRecord, old: RequestState,
                new: RequestState) -> None:
        if old in _PHASE_STATES:
            tracer.end(old.value, track=track)
        if new in _PHASE_STATES:
            tracer.begin(new.value, track=track, cat="lifecycle")
        elif new in TERMINAL_STATES:
            tracer.end("request", track=track, args={
                "state": new.value,
                "tokens": len(r.tokens),
                "preemptions": r.preemptions,
                "error": r.error,
            })

    rec.observer = observe


class ServingEngine:
    def __init__(self, model: Model, params, *, max_len: int = 512,
                 batch_size: int = 4, kv_dtype=None, tracer=None):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.max_len = max_len
        self.batch_size = batch_size
        # kv_dtype="int8": prefill builds a quantized dense cache and
        # decode appends per-row quantized tokens (DESIGN.md §5).
        self.kv_dtype = jnp.dtype(kv_dtype) if kv_dtype is not None else None
        # telemetry (DESIGN.md §8): registry is fresh per serve() call;
        # the tracer defaults to the shared disabled instance
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = MetricsRegistry()
        self.serve_t0 = 0.0
        # lifecycle + fault harness (DESIGN.md §7); injector defaults to
        # the shared no-op, results hold one RequestRecord per rid
        self.injector = NO_FAULTS
        self.results: dict[int, RequestRecord] = {}
        self._step_idx = 0
        self._decode = jax.jit(
            lambda p, c, t, pos: model.decode_step(p, model.cfg, t, c, pos)
        )
        # jit'd with the wave's prompt length as a compile bucket —
        # unjitted prefill re-traces the whole stack every wave and
        # dominates serving wall time.
        self._prefill_fn = jax.jit(
            lambda p, t: model.prefill(p, model.cfg, t, self.max_len,
                                       kv_dtype=self.kv_dtype)
        )
        # argmax + finite-guard + dummy-row pad, jitted once per distinct
        # n_real (the static arg) instead of a fresh closure per wave
        batch = batch_size

        @functools.partial(jax.jit, static_argnums=1)
        def next_token(logits, n_real):
            # ``packed`` rides tokens + finite-guard flags in ONE int32
            # array so the host loop pays a single device sync per step
            last = logits[:n_real, -1]
            live = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
            packed = jnp.concatenate([live[:, 0],
                                      _finite_rows(last).astype(jnp.int32)])
            if n_real == batch:
                return live, packed
            pad = jnp.ones((batch - n_real, 1), jnp.int32)
            return jnp.concatenate([live, pad]), packed

        self._next_token = next_token

    def _prefill(self, tokens):
        return self._prefill_fn(self.params, tokens)

    @property
    def token_walltimes(self) -> dict:
        """Back-compat view: rid -> per-token wall-clock timestamps
        (now held by the metrics registry)."""
        return self.metrics.series("token_walltime_s").by_key

    def _record(self, r: Request) -> RequestRecord:
        rec = self.results.get(r.rid)
        if rec is None or rec.request is not r:
            rec = RequestRecord(r)
            self.results[r.rid] = rec
            _trace_request(rec, self.tracer)
        return rec

    def serve(self, requests: list[Request]) -> dict[int, np.ndarray]:
        """Bucket by prompt length, serve each bucket as batched waves.

        Malformed requests (empty prompt, budget past max_len) are
        rejected as FAILED results at admission — one bad request never
        raises out of the whole wave (``self.results`` carries the
        per-request lifecycle state next to the token dict).
        """
        self.metrics = MetricsRegistry()
        self.results = {}
        self._step_idx = 0
        self.serve_t0 = time.perf_counter()
        out: dict[int, np.ndarray] = {}
        buckets: dict[int, list[Request]] = {}
        for r in requests:
            rec = self._record(r)
            err = validate_request(r, max_len=self.max_len)
            if err:
                rec.fail(err)
                out[r.rid] = np.array([], np.int32)
                continue
            buckets.setdefault(len(r.prompt), []).append(r)
        for _, rs in sorted(buckets.items()):
            for i in range(0, len(rs), self.batch_size):
                wave = []
                for r in rs[i:i + self.batch_size]:
                    rec = self.results[r.rid]
                    dl = r.deadline_s
                    if dl is not None and \
                            time.perf_counter() - self.serve_t0 > dl:
                        rec.cancel("deadline expired")
                        out[r.rid] = np.array([], np.int32)
                    else:
                        wave.append(r)
                if wave:
                    out.update(self.serve_wave(wave))
        return out

    def serve_wave(self, requests: list[Request]) -> dict[int, np.ndarray]:
        """Serve up to batch_size same-length requests as one wave."""
        assert len(requests) <= self.batch_size
        plens = {len(r.prompt) for r in requests}
        assert len(plens) == 1, "serve_wave needs equal prompt lengths"
        plen = plens.pop()
        n_real = len(requests)
        recs = [self._record(r) for r in requests]
        for rec in recs:
            rec.to(RequestState.PREFILLING)
        reqs = list(requests)
        while len(reqs) < self.batch_size:  # pad with a dummy row
            reqs.append(Request(rid=-1,
                                prompt=np.ones((plen,), np.int32),
                                max_new_tokens=0))
        prompts = np.stack([r.prompt for r in reqs]).astype(np.int32)
        with self.tracer.span("prefill_dispatch", track="engine",
                              args={"plen": plen, "n_real": n_real}):
            logits, cache = self._prefill(jnp.asarray(prompts))

        # Dummy rows never decode tokens: real requests alone bound the
        # wave length, and the argmax + device->host transfer below run
        # on the live batch prefix only.
        max_new = max(r.max_new_tokens for r in requests)
        out = {r.rid: [] for r in requests}
        done = np.array([r.max_new_tokens == 0 for r in requests])
        for i, rec in enumerate(recs):
            if done[i]:
                rec.finish()          # zero budget: nothing to generate
            else:
                rec.to(RequestState.DECODING)

        m = self.metrics
        m_walltimes = m.series("token_walltime_s",
                               "per-token wall-clock stamps by rid")
        m_nan = m.counter("serving.nan_guard_trips",
                          "slots failed by the finite-logit guard")
        m_tokens = m.counter("serving.tokens_generated")
        m_step = m.histogram("engine.step_s.wave_decode",
                             "host sync + bookkeeping + decode dispatch")
        m_sync = m.histogram("engine.host_sync_s",
                             "device->host transfer wait per step")
        tr = self.tracer
        token, packed = self._next_token(logits, n_real)
        for step in range(max_new):
            t_step0 = time.perf_counter()
            self.injector.step_begin(self, self._step_idx)
            # One device->host transfer per step, live rows only;
            # per-row int() on the device array would sync the stream
            # once per request.
            raw = np.asarray(packed)
            t_sync = time.perf_counter()
            m_sync.observe(t_sync - t_step0)
            token_host = raw[:n_real]
            ok_host = np.asarray(
                self.injector.corrupt_step_ok(
                    self._step_idx, raw[n_real:].astype(bool)))
            self._step_idx += 1
            now = time.perf_counter()
            for i, r in enumerate(requests):
                if done[i]:
                    continue
                rec = recs[i]
                if not ok_host[i]:
                    # per-request failure isolation: the NaN/inf guard
                    # fails this slot; the rest of the wave decodes on
                    rec.fail("non-finite logits")
                    m_nan.inc()
                    done[i] = True
                    continue
                dl = r.deadline_s
                if dl is not None and now - self.serve_t0 > dl:
                    rec.cancel("deadline expired")
                    done[i] = True
                    continue
                t = int(token_host[i])
                out[r.rid].append(t)
                rec.tokens.append(t)
                m_walltimes.observe(r.rid, now)
                m_tokens.inc()
                if t == r.eos_id or len(out[r.rid]) >= r.max_new_tokens:
                    rec.finish()
                    done[i] = True
            if done.all():
                break
            logits, cache = self._decode(self.params, cache, token,
                                         jnp.int32(plen + step))
            token, packed = self._next_token(logits, n_real)
            t_end = time.perf_counter()
            m_step.observe(t_end - t_step0)
            if tr.enabled:
                tr.complete("step", tr.to_us(t_step0),
                            (t_end - t_step0) * 1e6, track="engine",
                            args={"kind": "wave_decode", "step": step,
                                  "n_real": n_real})
                tr.complete("host_sync", tr.to_us(t_step0),
                            (t_sync - t_step0) * 1e6, track="engine")
        for rec in recs:
            if rec.state not in TERMINAL_STATES:
                rec.finish()
        return {rid: np.array(v, np.int32) for rid, v in out.items()}


class ContinuousBatchingEngine:
    """Paged-KV continuous batching with chunked prefill admission.

    ``batch_size`` decode slots share page pools of ``num_pages`` pages.
    Admission is reservation-based FIFO (DESIGN.md §4): the head-of-
    queue request takes a free slot as soon as pages for its prompt AND
    its decode reservation are available. Its prompt is then prefilled
    ``chunk_size`` tokens per engine step (DESIGN.md §6) — each chunk
    writes its K/V straight into the allocated pages through
    ``prefill_chunk`` and rides the SAME jitted step as the live decode
    slots, so decode advances while a long prompt is mid-admission, all
    prompts share one compile shape, and the first token comes out of
    the last chunk's logits in the step's single host transfer (no
    per-admit argmax sync, no dense batch-1 cache, no copy-on-admit
    scatter). Retiring sequences free their pages between steps.

    ``decode_reserve_frac`` < 1 runs the pool hot: admission reserves
    only that fraction of a request's decode budget, so ``append`` can
    hit pool exhaustion mid-decode — the scheduler then preempts the
    youngest live request (audited release, requeue at the head, chunked
    re-prefill of prompt+generated; DESIGN.md §7) instead of crashing.
    ``headroom_pages`` free pages are held back from FRESH admissions so
    preempted requests can always re-admit (resumed requests bypass the
    headroom); the default is the analytical
    ``core/autotune.tune_pool_headroom`` when overcommitted, 0 when
    fully reserved.

    ``spec_depth`` switches pure-decode steps to speculative decoding
    (DESIGN.md §9): a host-side prompt-lookup drafter proposes up to
    k-1 continuation tokens per live slot, ONE batched verify dispatch
    scores all candidate positions against the paged pool, and the
    engine accepts each slot's longest greedy-matching draft prefix
    plus one bonus token — >= 1 token per step, token-for-token
    identical to plain greedy decode. ``spec_depth="auto"`` takes the
    analytical ``core/autotune.tune_spec_depth`` default; per-request
    acceptance EMAs adaptively throttle how many drafts each slot
    requests (the dispatch shape stays at the static k). Chunked
    prefill admission is unchanged — mixed chunk+decode steps decode
    one token, so speculation never adds a compile shape to the
    admission path.
    """

    def __init__(self, model: Model, params, *, max_len: int = 512,
                 batch_size: int = 4, page_size: int = 16,
                 num_pages: int | None = None, kv_dtype=None,
                 chunk_size: int | None = None,
                 decode_reserve_frac: float = 1.0,
                 headroom_pages: int | None = None,
                 max_preemptions: int = 32, tracer=None,
                 spec_depth: int | str | None = None,
                 spec_ngram: int = 3,
                 prefix_cache: bool = False,
                 cache_reserve_frac: float | str = "auto"):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        if self.cfg.mla is not None:
            # latent attention (DESIGN.md §12) runs plain bf16 paged decode
            # and chunked prefill only
            for feature, on in (
                    ("speculative verify", spec_depth is not None),
                    ("int8 latent pages", kv_dtype is not None
                     and jnp.dtype(kv_dtype) == jnp.int8),
                    ("prefix reuse", prefix_cache)):
                if on:
                    raise NotImplementedError(
                        f"{feature} with latent attention (MLA)")
        self.max_len = max_len
        self.batch_size = batch_size
        self.page_size = page_size
        # kv_dtype="int8": the pools store quantized pages + per-page
        # fp32 scales; chunk writes quantize whole pages (DESIGN.md §5).
        self.kv_dtype = (jnp.dtype(kv_dtype) if kv_dtype is not None
                         else jnp.dtype(model.cfg.compute_dtype))
        self.max_pages = -(-max_len // page_size)
        if num_pages is None:
            num_pages = batch_size * self.max_pages + 1  # + scratch page
        self.num_pages = num_pages
        if chunk_size is None:
            # analytical default (core/autotune): the largest chunk
            # whose worst-case step keeps decode ITL bounded
            chunk_size = tune_prefill_chunk(
                b_h=self.cfg.num_heads, n_ctx=max_len, e=self.cfg.hd,
                itemsize=jnp.dtype(self.cfg.compute_dtype).itemsize,
                page=page_size,
                kv_itemsize=self.kv_dtype.itemsize,
            )
        # chunks are page-aligned and never exceed the page-rounded
        # prompt capacity (one compile shape per step kind)
        chunk_size = max(page_size, min(chunk_size,
                                        self.max_pages * page_size))
        chunk_size = -(-chunk_size // page_size) * page_size
        self.chunk_size = chunk_size
        self.chunk_pages = chunk_size // page_size
        if not 0.0 < decode_reserve_frac <= 1.0:
            raise ValueError(
                f"decode_reserve_frac must be in (0, 1], got "
                f"{decode_reserve_frac}")
        self.decode_reserve_frac = float(decode_reserve_frac)
        if headroom_pages is None:
            headroom_pages = (
                tune_pool_headroom(num_slots=batch_size,
                                   chunk_pages=self.chunk_pages)
                if self.decode_reserve_frac < 1.0 else 0)
        self.headroom_pages = headroom_pages
        self.max_preemptions = max_preemptions
        if spec_depth == "auto":
            spec_depth = tune_spec_depth(
                b_h=self.cfg.num_heads, n_ctx=max_len, e=self.cfg.hd,
                itemsize=jnp.dtype(self.cfg.compute_dtype).itemsize,
                page=page_size, kv_itemsize=self.kv_dtype.itemsize,
            )
        if spec_depth is not None and spec_depth < 1:
            raise ValueError(f"spec_depth must be >= 1, got {spec_depth}")
        self.spec_depth = spec_depth
        self._drafter = (NgramDrafter(ngram=spec_ngram)
                         if spec_depth is not None else None)
        # shared-prefix KV reuse (DESIGN.md §10): admission maps resident
        # prompt pages, chunked prefill resumes at the first non-resident
        # page, and a full hit skips prefill entirely behind one
        # copy-on-write page copy. Off by default: the cold path is
        # byte-identical to a cacheless engine.
        self.prefix_cache = bool(prefix_cache)
        if cache_reserve_frac == "auto":
            # analytical default; the searched seventh tiling factor
            # (sim/schedules.py) owns the workload-specific answer
            cache_reserve_frac = tune_cache_reserve(
                pool_pages=num_pages - 1, page=page_size,
                slots=batch_size, pages_per_seq=self.max_pages,
                prefix_tokens=max_len // 4, hit_rate=0.5,
            ) if self.prefix_cache else 0.0
        if not 0.0 <= float(cache_reserve_frac) <= 1.0:
            raise ValueError(
                f"cache_reserve_frac must be in [0, 1], got "
                f"{cache_reserve_frac}")
        self.cache_reserve_frac = float(cache_reserve_frac)
        # single-page copy-on-write: the page axis is axis 2 in every
        # pool leaf ((U, Hkv, P, page, E) values, (U, Hkv, P) scales),
        # so one tree-map copies K, V and the int8 scale side-tables of
        # the divergence page in one fused donated dispatch
        self._cow = jax.jit(
            lambda c, src, dst: jax.tree.map(
                lambda a: a.at[:, :, dst].set(a[:, :, src]), c),
            donate_argnums=0)
        self.peak_pages_used = 0  # across serve() calls, for benchmarks
        # per-step scheduler trace of the LAST serve() call: whether a
        # prompt chunk was packed and how many decode slots were live
        self.step_log: list[dict] = []
        # telemetry (DESIGN.md §8): the registry is recreated per
        # serve() call (occupancy_log / token_walltimes /
        # preemption_count / recompute_tokens read through it); the
        # tracer defaults to the shared disabled instance
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = MetricsRegistry()
        self.serve_t0 = 0.0
        # lifecycle + fault harness (DESIGN.md §7): injector/auditor are
        # plain attributes so tests/benchmarks swap them between serve()
        # calls without recompiling the jitted steps
        self.injector = NO_FAULTS
        self.auditor = None
        self.results: dict[int, RequestRecord] = {}
        self._cancel_req: set[int] = set()

        # Host<->device protocol: each step kind takes the host state as
        # ONE packed int32 array per direction. Inbound, ``hs`` carries
        # tokens | positions | page table (and ``ch`` the chunk's tokens
        # | pages | seq table | q0 | len), unpacked by static slicing
        # inside the jit — one device_put per step instead of 3-7, which
        # is a large slice of small-model serving wall time. Outbound,
        # the return packs argmax tokens then the finite-guard flags, so
        # the step's single device->host sync carries both (a second
        # sync for the NaN guard would cost as much as the guard saves).
        B_, MP = batch_size, self.max_pages
        CS, CP = self.chunk_size, self.chunk_pages
        # a held-expert layer's (token, held expert) rows of the step ride
        # the same transfer, as its last value (``expert_rows``)
        self.counts_expert_rows = "mla_moe" in self.cfg.layer_kinds
        rows_kw = {"with_rows": True} if self.counts_expert_rows else {}

        def with_rows(out, *rows):
            if not self.counts_expert_rows:
                return out
            return jnp.concatenate([out, sum(rows)[None]])

        def unpack_hs(hs):
            return (hs[:B_][:, None], hs[2 * B_:].reshape(B_, MP),
                    hs[B_:2 * B_])

        def unpack_ch(ch):
            return (ch[:CS][None, :], ch[CS:CS + CP],
                    ch[CS + CP:CS + CP + MP], ch[-2], ch[-1])

        def decode_step(p, c, hs):
            t, table, pos = unpack_hs(hs)
            logits, c, *rows = model.paged_decode_step(
                p, model.cfg, t, c, table, pos, **rows_kw)
            last = logits[:, -1]
            return with_rows(jnp.concatenate([
                jnp.argmax(last, axis=-1).astype(jnp.int32),
                _finite_rows(last).astype(jnp.int32),
            ]), *rows), c

        def chunk_step(p, c, hs, ch):
            # one mixed step: the prompt chunk and ALL decode slots in a
            # single dispatch; both argmaxes (and both finite-guard
            # flags) land in one host transfer
            t, table, pos = unpack_hs(hs)
            ctokens, cpages, seq_table, q_offset, chunk_len = unpack_ch(ch)
            first_logits, c, *chunk_rows = model.prefill_chunk(
                p, model.cfg, ctokens, c, seq_table, cpages, q_offset,
                chunk_len, **rows_kw,
            )
            logits, c, *rows = model.paged_decode_step(
                p, model.cfg, t, c, table, pos, **rows_kw)
            last = logits[:, -1]
            return with_rows(jnp.concatenate([
                jnp.argmax(last, axis=-1).astype(jnp.int32),
                jnp.argmax(first_logits, axis=-1).astype(jnp.int32),
                _finite_rows(last).astype(jnp.int32),
                _finite_rows(first_logits).astype(jnp.int32),
            ]), *chunk_rows, *rows), c

        def chunk_only(p, c, ch):
            # no live decode slots: don't pay a dead full-batch decode
            # pass just to move the prefill along
            ctokens, cpages, seq_table, q_offset, chunk_len = unpack_ch(ch)
            first_logits, c, *rows = model.prefill_chunk(
                p, model.cfg, ctokens, c, seq_table, cpages, q_offset,
                chunk_len, **rows_kw,
            )
            return with_rows(jnp.concatenate([
                jnp.argmax(first_logits, axis=-1).astype(jnp.int32),
                _finite_rows(first_logits).astype(jnp.int32),
            ]), *rows), c

        self._decode = jax.jit(decode_step)
        self._chunk_step = jax.jit(chunk_step)
        self._chunk_only = jax.jit(chunk_only)

        self._verify = None
        if self.spec_depth is not None:
            K = int(self.spec_depth)

            def unpack_vs(vs):
                # tokens (B, k) | positions (B,) | n_rows (B,) | table
                return (vs[:B_ * K].reshape(B_, K),
                        vs[B_ * K + 2 * B_:].reshape(B_, MP),
                        vs[B_ * K:B_ * K + B_],
                        vs[B_ * K + B_:B_ * K + 2 * B_])

            def verify_step(p, c, vs):
                # one dispatch verifies every live slot's draft block;
                # the k per-position argmaxes and k finite-guard flags
                # per slot ride the step's single host transfer
                t, table, pos, nrows = unpack_vs(vs)
                logits, c = model.paged_verify_step(p, model.cfg, t, c,
                                                    table, pos, nrows)
                return jnp.concatenate([
                    jnp.argmax(logits, axis=-1).astype(jnp.int32).ravel(),
                    _finite_rows(logits.reshape(B_ * K, -1))
                    .astype(jnp.int32),
                ]), c

            self._verify = jax.jit(verify_step)

    def _make_cache(self):
        """Build the serve() paged cache. The sharded engine overrides
        this to place the page pools onto its mesh (DESIGN.md §11)."""
        return self.model.make_cache(
            self.batch_size, self.max_len, cache_layout="paged",
            page_size=self.page_size, num_pages=self.num_pages,
            kv_dtype=self.kv_dtype)

    def _observe_step(self, chunk_tokens: int, live: int) -> None:
        """Per-step observability hook, called once per engine step
        after the host sync. No-op here; the sharded engine records its
        shard.* metrics from it."""

    def kv_bytes_per_page(self) -> int:
        cfg = self.cfg
        if cfg.mla is not None:  # one latent row a token and layer
            return (cfg.num_layers * self.page_size * cfg.mla.pool_dim
                    * self.kv_dtype.itemsize)
        return page_footprint_bytes(
            num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
            page_size=self.page_size, head_dim=cfg.hd,
            kv_dtype=self.kv_dtype,
        )

    def cancel(self, rid: int) -> None:
        """Request cancellation of ``rid``; honored at the next step
        boundary (queued, mid-prefill, or mid-decode — pages freed)."""
        self._cancel_req.add(rid)

    # -- back-compat views onto the metrics registry (DESIGN.md §8) ------

    @property
    def occupancy_log(self) -> list:
        """Pages in use per engine step of the last serve() call."""
        return self.metrics.gauge("pool.pages_used").series

    @property
    def token_walltimes(self) -> dict:
        """rid -> per-token wall-clock timestamps, last serve() call."""
        return self.metrics.series("token_walltime_s").by_key

    @property
    def preemption_count(self) -> int:
        return int(self.metrics.counter("serving.preemptions").value)

    @property
    def recompute_tokens(self) -> int:
        return int(
            self.metrics.counter("serving.recompute_tokens").value)

    @property
    def spec_stats(self) -> dict:
        """Speculation summary of the last serve() call: drafted /
        accepted totals and the overall acceptance rate (DESIGN.md §9).
        All zeros when speculation is off."""
        drafted = int(self.metrics.counter("spec.tokens_drafted").value)
        accepted = int(self.metrics.counter("spec.tokens_accepted").value)
        return {"drafted": drafted, "accepted": accepted,
                "acceptance_rate": accepted / drafted if drafted else 0.0}

    @property
    def prefix_stats(self) -> dict:
        """Shared-prefix summary of the last serve() call (DESIGN.md
        §10): hit/miss admissions, prompt tokens served from cache,
        copy-on-write copies, LRU evictions and deduped pages. All
        zeros when the prefix cache is off."""
        c = self.metrics.counter
        hits = int(c("prefix.hits").value)
        misses = int(c("prefix.misses").value)
        return {
            "hits": hits,
            "misses": misses,
            "hit_rate": hits / (hits + misses) if hits + misses else 0.0,
            "hit_tokens": int(c("prefix.hit_tokens").value),
            "cow_copies": int(c("prefix.cow_copies").value),
            "evictions": int(c("prefix.evictions").value),
            "pages_deduped": int(c("prefix.pages_deduped").value),
        }

    def serve(self, requests: list[Request]) -> dict[int, np.ndarray]:
        B, ps = self.batch_size, self.page_size
        mgr = PagedKVCacheManager(self.num_pages, ps, num_slots=B,
                                  max_pages_per_seq=self.max_pages,
                                  kv_dtype=self.kv_dtype,
                                  prefix_cache=self.prefix_cache,
                                  cache_reserve_frac=self.cache_reserve_frac)
        self._mgr = mgr  # auditable by tests while serve() is live
        cache = self._make_cache()
        self.step_log = []
        self.results = {}
        self._cancel_req = set()
        self.metrics = m = MetricsRegistry()
        m_occ = m.gauge("pool.pages_used",
                        "paged pool pages in use per engine step")
        m_walltimes = m.series("token_walltime_s",
                               "per-token wall-clock stamps by rid")
        m_preempt = m.counter("serving.preemptions",
                              "mid-decode evictions (pool exhaustion)")
        m_recompute = m.counter("serving.recompute_tokens",
                                "prompt+prefix tokens re-prefilled")
        m_nan = m.counter("serving.nan_guard_trips",
                          "slots failed by the finite-logit guard")
        m_tokens = m.counter("serving.tokens_generated")
        m_sync = m.histogram("engine.host_sync_s",
                             "device->host transfer wait per step")
        # "verify" only when speculation is on — a non-speculative serve
        # must not export an empty verify histogram (CI's metrics
        # cross-check treats empty step histograms as a pipeline bug)
        step_kinds = ("decode", "chunk", "chunk+decode") + (
            ("verify",) if self.spec_depth is not None else ())
        m_step_kind = {
            k: m.histogram(f"engine.step_s.{k}",
                           "step walltime (pack+dispatch+sync) by kind")
            for k in step_kinds
        }
        # speculative decoding telemetry (DESIGN.md §9): global draft /
        # accept counters plus the per-request acceptance-rate series
        # the adaptive-k throttle is driven by
        m_drafted = m.counter("spec.tokens_drafted",
                              "draft candidates sent to verify steps")
        m_accepted = m.counter("spec.tokens_accepted",
                               "draft candidates matching greedy argmax")
        m_accept_rate = m.series("spec.acceptance_rate",
                                 "per-verify-step draft acceptance by rid")
        # shared-prefix telemetry (DESIGN.md §10): the counters mirror
        # the manager's own stats (synced by delta once per step, so
        # mid-serve reads are live) and the gauge tracks the index's
        # resident pages per step next to pool occupancy
        m_px_counters = [
            (m.counter("prefix.hits", "admissions served a resident prefix"),
             "prefix_hits"),
            (m.counter("prefix.misses",
                       "prefix-cache admissions with no resident prefix"),
             "prefix_misses"),
            (m.counter("prefix.hit_tokens",
                       "prompt tokens satisfied from shared pages"),
             "prefix_hit_tokens"),
            (m.counter("prefix.cow_copies",
                       "divergence pages copied on write"), "cow_copies"),
            (m.counter("prefix.evictions",
                       "cached prefix entries dropped (LRU / reserve cap)"),
             "prefix_evictions"),
            (m.counter("prefix.pages_deduped",
                       "page allocations avoided by mapping shared pages"),
             "pages_deduped"),
        ]
        m_px_resident = m.gauge("prefix.resident_cache_pages",
                                "pages retained by the prefix index")
        m_admit = m.series("admit_walltime_s",
                           "admission wall-clock stamp by rid")

        def sync_prefix_metrics():
            for c, attr in m_px_counters:
                d = getattr(mgr, attr) - int(c.value)
                if d > 0:
                    c.inc(d)

        spec_state: dict[int, dict] = {}  # rid -> {"ema", "k"}
        tr = self.tracer
        tracing = tr.enabled
        self.serve_t0 = time.perf_counter()
        queue: deque[RequestRecord] = deque()
        for r in requests:
            rec = RequestRecord(r)
            self.results[r.rid] = rec
            _trace_request(rec, tr)
            err = validate_request(r, max_len=self.max_len,
                                   pool_pages=self.num_pages - 1,
                                   page_size=ps)
            if err:
                rec.fail(err)  # one bad request, not a dead wave
            else:
                queue.append(rec)
        active: dict[int, RequestRecord] = {}
        tokens = np.zeros((B, 1), np.int32)
        positions = np.zeros((B,), np.int32)
        pending: list | None = None  # [rec, slot, q_offset, rprompt]
        admit_seq = itertools.count()
        n_append = 0    # global append counter (fault-injection index)
        step_idx = 0

        def idle(slot: int) -> None:
            tokens[slot, 0] = 0
            positions[slot] = 0

        def retire(slot: int) -> None:
            mgr.release(slot)
            idle(slot)

        def preempt(slot: int) -> None:
            """Evict a live decode slot: audited page release, requeue
            at the HEAD of the wait queue (age preserved — re-admission
            re-prefills prompt+generated through the chunk path)."""
            rec = active.pop(slot)
            retire(slot)
            rec.to(RequestState.PREEMPTED)
            rec.preemptions += 1
            m_preempt.inc()
            if tracing:
                tr.instant("preempt", track="engine",
                           args={"rid": rec.rid,
                                 "tokens": len(rec.tokens)})
            if rec.preemptions > self.max_preemptions:
                rec.fail(f"preempted > {self.max_preemptions} times "
                         f"(pool thrashing)")
            else:
                rec.to(RequestState.QUEUED)
                queue.appendleft(rec)

        def recover_exhaustion(requester: int) -> bool:
            """Mid-decode pool exhaustion: evict the youngest live
            request and retry until the append lands or the requester
            itself was the victim. Returns False when the requester was
            preempted (its pending token survives on the record)."""
            while True:
                victim = max(active, key=lambda s: active[s].admit_seq)
                preempt(victim)
                if victim == requester:
                    return False
                try:
                    mgr.append(requester)
                    return True
                except PagePoolExhausted:
                    continue

        def plan_speculation():
            """Draft + page reservation for one verify step (§9).

            For every live slot: pick how many candidate rows to verify
            — the adaptive per-request k, capped by the slot's remaining
            token budget so the reservation can never outgrow
            ``max_pages_per_seq`` — draft via prompt lookup, and
            pre-allocate the pages the candidate rows land in (the
            device writes them, so the table must name them BEFORE
            dispatch). Reservation exhaustion preempts the youngest
            live request, possibly the reserving slot itself.
            """
            K = int(self.spec_depth)
            vs_tokens = np.zeros((B, K), np.int32)
            n_rows = np.zeros((B,), np.int32)
            drafts: dict[int, list[int]] = {}
            for slot_i in list(active):
                if slot_i not in active:
                    continue  # evicted by an earlier slot's reservation
                rec_i = active[slot_i]
                st = spec_state.setdefault(rec_i.rid, {"ema": 1.0, "k": K})
                want = min(st["k"], rec_i.remaining, K)
                d = self._drafter.draft(
                    np.concatenate([
                        np.asarray(rec_i.request.prompt, np.int64),
                        np.asarray(rec_i.tokens, np.int64)]),
                    want - 1) if want > 1 else []
                nr = 1 + len(d)
                while slot_i in active:
                    try:
                        mgr.ensure_capacity(slot_i, nr)
                        break
                    except PagePoolExhausted:
                        victim = max(active,
                                     key=lambda s: active[s].admit_seq)
                        preempt(victim)
                if slot_i not in active:
                    continue  # the reserving slot was the victim
                drafts[slot_i] = d
                vs_tokens[slot_i, 0] = tokens[slot_i, 0]
                if d:
                    vs_tokens[slot_i, 1:1 + len(d)] = d
                n_rows[slot_i] = nr
            return vs_tokens, n_rows, drafts

        has_deadlines = any(r.deadline_s is not None for r in requests)

        def sweep_kills(now: float) -> None:
            """Cancellation + deadline enforcement at step granularity,
            for queued, mid-prefill and mid-decode requests alike.
            Fast path: nothing to kill -> two truthiness checks, no
            per-step scan of the queue."""
            nonlocal pending
            if not self._cancel_req and not has_deadlines:
                return

            def kill_reason(rec: RequestRecord) -> str | None:
                if rec.rid in self._cancel_req:
                    return "cancelled"
                dl = rec.request.deadline_s
                if dl is not None and now - self.serve_t0 > dl:
                    return "deadline expired"
                return None

            for slot in list(active):
                reason = kill_reason(active[slot])
                if reason:
                    active.pop(slot).cancel(reason)
                    retire(slot)
            if pending is not None:
                reason = kill_reason(pending[0])
                if reason:
                    pending[0].cancel(reason)
                    retire(pending[1])
                    pending = None
            for rec in [q for q in queue if kill_reason(q)]:
                rec.cancel(kill_reason(rec))
                queue.remove(rec)

        def start_prefill():
            """Admit the head-of-queue request into a free slot (FIFO:
            reservation-based, one prefill stream at a time). Preempted
            requests sit at the head and re-prefill prompt+generated;
            fresh admissions leave ``headroom_pages`` free for them.
            With the prefix cache on, admission maps the longest
            resident prefix: chunked prefill resumes at the first
            non-resident page, and a FULL hit never enters the prefill
            stream at all — the divergence page is copied on device
            (copy-on-write) and the slot goes straight to DECODING, so
            several full hits can admit in one call (DESIGN.md §10)."""
            nonlocal pending, cache
            while queue:
                rec = queue[0]
                if rec.remaining <= 0:  # nothing (left) to generate
                    queue.popleft()
                    rec.finish()
                    continue
                rprompt = rec.resume_prompt()
                plen = len(rprompt)
                # resumed requests get their FULL remaining budget (no
                # second self-inflicted exhaustion); fresh ones reserve
                # the configured fraction and may grow into free pages
                reserve = rec.remaining if rec.resumed else min(
                    rec.remaining,
                    max(1, int(np.ceil(rec.remaining
                                       * self.decode_reserve_frac))))
                match = (mgr.match_prefix(rprompt)
                         if self.prefix_cache else None)
                need_total, need_new = mgr.admit_plan(plen, reserve, match)
                headroom = 0 if rec.resumed else max(
                    0, min(self.headroom_pages,
                           (self.num_pages - 1) - need_total))
                free = [s for s in range(B) if s not in active]
                # the gate draws only the NON-resident pages from the
                # free list (plus cold cache ``alloc`` can reclaim)
                if (not free or need_total > mgr.max_pages_per_seq
                        or need_new > mgr.free_capacity
                        or mgr.free_capacity - need_new < headroom):
                    return  # FIFO: wait for slot/pages, don't starve
                if self.injector.admit_fault(step_idx, rec.rid):
                    return  # injected admission rejection: retry later
                queue.popleft()
                slot = free[0]
                res = mgr.admit_prefix(slot, plen, reserve=reserve,
                                       match=match)
                if rec.admit_seq is None:
                    rec.admit_seq = next(admit_seq)
                m_admit.observe(rec.rid, time.perf_counter())
                rec.prefix_hit_tokens += res.prefix_tokens
                if rec.resumed:
                    # only the tokens actually re-prefilled count as
                    # recompute — a resident prefix (often the victim's
                    # own published pages) shrinks the preemption bill
                    redo = plen - res.prefix_tokens
                    rec.recompute_tokens += redo
                    m_recompute.inc(redo)
                rec.to(RequestState.PREFILLING)
                self.peak_pages_used = max(self.peak_pages_used,
                                           mgr.peak_pages_used)
                if res.full_hit:
                    # whole prompt resident: copy the divergence page
                    # (K, V and scale side-tables move together), then
                    # start decode at plen-1 — the next decode step
                    # re-feeds the last prompt token through the shared
                    # KV and emits the first generated token, exactly
                    # the logits the cold path reads off its last chunk
                    src, dst = res.cow
                    cache = self._cow(cache, jnp.int32(src),
                                      jnp.int32(dst))
                    if tracing:
                        tr.instant("prefix_hit", track="engine",
                                   args={"rid": rec.rid, "tokens": plen,
                                         "cow_src": src, "cow_dst": dst})
                    rec.to(RequestState.DECODING)
                    active[slot] = rec
                    tokens[slot, 0] = int(rprompt[-1])
                    positions[slot] = plen - 1
                    continue  # the prefill stream is still free
                if tracing and res.prefix_tokens:
                    tr.instant("prefix_hit", track="engine",
                               args={"rid": rec.rid,
                                     "tokens": res.prefix_tokens})
                pending = [rec, slot, res.prefix_tokens, rprompt]
                return

        def pack_step(spec_plan, clen: int):
            """The step program and its packed host inputs: the page
            table, and the decode batch, the prompt chunk or the verify
            rows, each as one array."""
            dec_table = mgr.table()
            if pending is not None:
                _, slot, q0, rprompt = pending
                # mid-admission the slot must not decode into (or read
                # from) its half-written pages: point it at scratch
                # (the prefill keeps the real row, captured first)
                seq_table = dec_table[slot].copy()
                dec_table[slot] = SCRATCH_PAGE
                ctokens = np.ones((1, self.chunk_size), np.int32)
                ctokens[0, :clen] = rprompt[q0:q0 + clen]
                # the chunk's page span; padded-tail pages past the
                # allocation land on the scratch page
                seq_pages = mgr.seq_pages(slot)
                p0 = q0 // ps
                cpages = [seq_pages[p] if p < len(seq_pages)
                          else SCRATCH_PAGE
                          for p in range(p0, p0 + self.chunk_pages)]
                ch = jnp.asarray(np.concatenate([
                    ctokens[0], np.asarray(cpages, np.int32), seq_table,
                    np.asarray([q0, clen], np.int32),
                ]))
                if active:
                    hs = np.concatenate([tokens[:, 0], positions,
                                         dec_table.ravel()])
                    return self._chunk_step, (jnp.asarray(hs), ch)
                return self._chunk_only, (ch,)
            if spec_plan is not None:
                vs_tokens, n_rows, _ = spec_plan
                vs = np.concatenate([vs_tokens.ravel(), positions,
                                     n_rows, dec_table.ravel()])
                return self._verify, (jnp.asarray(vs),)
            hs = np.concatenate([tokens[:, 0], positions,
                                 dec_table.ravel()])
            return self._decode, (jnp.asarray(hs),)

        def commit_step(raw, now: float, spec_plan, clen: int) -> None:
            """Commit one step's transfer on the host: per-slot tokens,
            page appends, retirement and lifecycle, then the tail of the
            prompt chunk (the admitted request's first token)."""
            nonlocal pending, n_append
            if pending is not None:
                rec, slot, q0, rprompt = pending
                plen = len(rprompt)
            half = raw.shape[0] // 2
            token_host = raw[:half]
            ok_host = np.asarray(
                self.injector.corrupt_step_ok(step_idx,
                                              raw[half:].astype(bool)))
            if spec_plan is not None:
                # accept rule (§9): per slot, take the longest prefix of
                # drafts matching the model's own greedy argmax, plus
                # ONE bonus token — logits at position i condition on
                # candidates 0..i, so the match guarantees the emitted
                # stream is token-for-token the plain greedy one.
                K = int(self.spec_depth)
                vs_tokens, n_rows, drafts = spec_plan
                am = token_host.reshape(B, K)
                okm = ok_host.reshape(B, K)
                step_drafted = step_accepted = 0
                for slot_i in list(active.keys()):
                    if slot_i not in active:
                        continue  # preempted by an earlier slot's fault
                    rec_i = active[slot_i]
                    nr = int(n_rows[slot_i])
                    if not okm[slot_i, :nr].all():
                        rec_i.fail("non-finite logits")
                        m_nan.inc()
                        del active[slot_i]
                        retire(slot_i)
                        continue
                    d = drafts.get(slot_i, [])
                    a = 0
                    while a < len(d) and int(am[slot_i, a]) == d[a]:
                        a += 1
                    emit = [int(t) for t in d[:a]] + [int(am[slot_i, a])]
                    if d:
                        st = spec_state[rec_i.rid]
                        rate = a / len(d)
                        # EMA-driven adaptive k: a slot whose drafts
                        # keep missing stops paying for dead verify rows
                        st["ema"] = 0.5 * st["ema"] + 0.5 * rate
                        st["k"] = 1 + int(round(st["ema"] * (K - 1)))
                        m_drafted.inc(len(d))
                        m_accepted.inc(a)
                        m_accept_rate.observe(rec_i.rid, rate)
                        step_drafted += len(d)
                        step_accepted += a
                    emit = emit[:rec_i.remaining]
                    kept = 0
                    fin = False
                    for t in emit:
                        rec_i.tokens.append(t)
                        m_walltimes.observe(rec_i.rid, now)
                        m_tokens.inc()
                        kept += 1
                        if (t == rec_i.request.eos_id
                                or rec_i.remaining <= 0):
                            fin = True
                            break
                    # capacity was reserved pre-dispatch, so the commit
                    # cannot exhaust the pool organically — only the
                    # injected per-append faults fire, swept at the same
                    # global ``n_append`` granularity as plain decode
                    evicted = False
                    for _ in range(kept):
                        if self.injector.alloc_fault(step_idx, n_append,
                                                     slot_i):
                            victim = max(
                                active,
                                key=lambda s: active[s].admit_seq)
                            preempt(victim)
                            if victim == slot_i:
                                evicted = True
                                n_append += 1
                                break
                        n_append += 1
                    if evicted:
                        continue  # emitted tokens survive on the record
                    mgr.append_n(slot_i, kept)  # ONE page-table commit
                    positions[slot_i] += kept
                    self.peak_pages_used = max(self.peak_pages_used,
                                               mgr.peak_pages_used)
                    if fin:
                        rec_i.finish()
                        del active[slot_i]
                        retire(slot_i)
                    else:
                        tokens[slot_i, 0] = emit[kept - 1]
                if tracing:
                    tr.instant("speculation", track="engine",
                               args={"drafted": step_drafted,
                                     "accepted": step_accepted})
            else:
                for slot_i in list(active.keys()):
                    if slot_i not in active:
                        continue  # preempted by an earlier slot's recovery
                    rec_i = active[slot_i]
                    if not ok_host[slot_i]:
                        # NaN/inf isolation: fail THIS slot, free its
                        # pages, let the rest of the batch decode on
                        rec_i.fail("non-finite logits")
                        m_nan.inc()
                        del active[slot_i]
                        retire(slot_i)
                        continue
                    t = int(token_host[slot_i])
                    rec_i.tokens.append(t)
                    m_walltimes.observe(rec_i.rid, now)
                    m_tokens.inc()
                    positions[slot_i] += 1
                    try:
                        if self.injector.alloc_fault(step_idx, n_append,
                                                     slot_i):
                            raise PagePoolExhausted(
                                f"injected exhaustion at append {n_append}")
                        mgr.append(slot_i)
                    except PagePoolExhausted:
                        if not recover_exhaustion(slot_i):
                            n_append += 1
                            continue  # requester itself was preempted
                    finally:
                        self.peak_pages_used = max(self.peak_pages_used,
                                                   mgr.peak_pages_used)
                    n_append += 1
                    if t == rec_i.request.eos_id or rec_i.remaining <= 0:
                        rec_i.finish()
                        del active[slot_i]
                        retire(slot_i)
                    else:
                        tokens[slot_i, 0] = t
            if pending is not None:
                q0 += clen
                if self.prefix_cache:
                    # publish the freshly-written FULL prompt pages at
                    # chunk-write time: the next identical prompt maps
                    # them instead of re-prefilling (DESIGN.md §10)
                    mgr.publish_prefix(slot, rprompt[:q0])
                if q0 >= plen:  # prefill complete: first token is out
                    if not ok_host[-1]:
                        rec.fail("non-finite logits")
                        m_nan.inc()
                        retire(slot)
                    else:
                        t = int(token_host[-1])
                        rec.tokens.append(t)
                        m_walltimes.observe(rec.rid, now)
                        m_tokens.inc()
                        if t == rec.request.eos_id or rec.remaining <= 0:
                            rec.finish()  # done straight out of prefill
                            retire(slot)
                        else:
                            rec.to(RequestState.DECODING)
                            active[slot] = rec
                            tokens[slot, 0] = t
                            positions[slot] = plen
                    pending = None
                else:
                    pending[2] = q0

        stalls = 0
        while True:
            self.injector.step_begin(self, step_idx)
            with tr.span("admit", track="engine"):
                sweep_kills(time.perf_counter())
                if pending is None:
                    start_prefill()
            if pending is None and not active:
                if not queue:
                    break
                # nothing live but requests still queued: admission
                # backpressure (injected rejection) with an idle engine.
                # Spin the scheduler without dispatching a dead step —
                # and refuse to spin forever if the injector never
                # relents (a fault-script bug, not a serving condition).
                stalls += 1
                if stalls > 10_000:
                    rec = queue.popleft()
                    rec.fail("admission stalled (injected rejection)")
                    stalls = 0
                step_idx += 1
                continue
            stalls = 0
            spec_plan = None
            t_step0 = time.perf_counter()
            if pending is None and self._verify is not None:
                # speculative decode step: draft + reserve BEFORE the
                # table snapshot, so reservation pages (and any
                # reservation-driven preemption) are visible to it
                with tr.span("draft", track="engine"):
                    spec_plan = plan_speculation()
                if not active:
                    step_idx += 1
                    continue  # reservation churn evicted every slot
            # read when the span closes: filled in once the batch is known
            step_args = {"step": step_idx}
            with tr.span("step", track="engine", args=step_args):
                m_occ.record(mgr.pages_used)
                if self.prefix_cache:
                    m_px_resident.record(len(mgr.cached_pages()))
                    sync_prefix_metrics()
                # live prompt rows of this step's chunk (the rest is pad)
                clen = (min(self.chunk_size, len(pending[3]) - pending[2])
                        if pending is not None else 0)
                self.step_log.append({"prefill_in_flight": pending is not None,
                                      "live_decode": len(active),
                                      "chunk_tokens": clen})
                kind = (("verify" if spec_plan is not None else "decode")
                        if pending is None
                        else ("chunk+decode" if active else "chunk"))
                step_args.update(kind=kind, live_decode=len(active),
                                 chunk_tokens=clen,
                                 pages_used=mgr.pages_used)
                if tracing:
                    tr.counter("pool.pages_used", mgr.pages_used,
                               track="pool")
                    # pages the decode attention reads: each live slot's
                    # keys through the token this step writes
                    step_args["kv_pages_live"] = sum(
                        int(positions[s]) // ps + 1 for s in active)
                with tr.span("pack", track="engine"):
                    step_fn, step_in = pack_step(spec_plan, clen)
                with tr.span("dispatch", track="engine"):
                    packed, cache = step_fn(self.params, cache, *step_in)
                t_disp = time.perf_counter()
                # the step's single device->host transfer carries decode
                # tokens, (on the final chunk) the admitted request's first
                # token, AND the finite-guard flags — no per-admit argmax
                # sync, no second sync for the NaN guard
                with tr.span("host_sync", track="engine"):
                    raw = np.asarray(packed)
                now = time.perf_counter()
                if self.counts_expert_rows:
                    raw, rows = raw[:-1], int(raw[-1])
                    if tracing:
                        step_args["expert_rows"] = rows
                        tr.counter("moe.held_rows", rows, track="moe")
                m_sync.observe(now - t_disp)
                m_step_kind[kind].observe(now - t_step0)
                self._observe_step(clen, len(active))
            with tr.span("commit", track="engine"):
                commit_step(raw, now, spec_plan, clen)
                if self.auditor is not None:
                    expected = {s: int(positions[s]) for s in active}
                    if pending is not None:
                        expected[pending[1]] = len(pending[3])
                    self.auditor.check(mgr, expected_lens=expected)
            step_idx += 1
        self.peak_pages_used = max(self.peak_pages_used,
                                   mgr.peak_pages_used)
        if self.prefix_cache:
            sync_prefix_metrics()
            m_px_resident.set(len(mgr.cached_pages()))
        if self.auditor is not None:
            self.auditor.final_check(mgr)
        return {rid: np.array(rec.tokens, np.int32)
                for rid, rec in self.results.items()}
