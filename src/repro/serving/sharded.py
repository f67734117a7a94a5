"""Multi-chip paged serving (DESIGN.md §11).

``ShardedContinuousBatchingEngine`` runs the continuous-batching engine
across a ``jax`` mesh: the page pools are KV-HEAD-sharded over a
'model' axis (the (Hkv, P, page, E) layout makes Hkv the shard dim;
block tables and kv_lens replicate as host-side step arguments), model
parameters replicate (forward-only serving of weights that fit HBM —
the sharding.py "sp_rep" rationale), decode/verify steps run
shard-local under the ``ctx.kv_shard`` dispatch constraints with one
pure-data-movement output all-gather per unit, and chunked prefill runs
as the head-block ring (``distributed.paged.ring_paged_prefill``). The
host-side scheduler — admission, preemption, speculation, auditing —
is INHERITED UNCHANGED: sharding lives entirely below the jitted step
closures, which is what keeps the sharded token stream bitwise the
single-chip stream.

``LeastLoadedRouter`` adds the data-parallel tier on top: N engine
replicas (each its own mesh or a plain single-chip engine), requests
routed to the replica with the least pending estimated work.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.autotune import tune_shard_degree
from repro.distributed import ctx
from repro.distributed.sharding import cache_specs, named
from repro.models.transformer import unit_layout
from repro.serving.engine import ContinuousBatchingEngine

import jax.numpy as jnp


def _largest_divisor_leq(n: int, cap: int) -> int:
    for s in range(min(n, cap), 0, -1):
        if n % s == 0:
            return s
    return 1


class ShardedContinuousBatchingEngine(ContinuousBatchingEngine):
    """KV-head-sharded continuous batching over ``shard`` devices.

    ``shard="auto"`` resolves through the closed-form
    ``core/autotune.tune_shard_degree`` (then clamps to the device
    count and the KV-head divisors); an int is validated strictly.
    All other knobs are the base engine's.
    """

    def __init__(self, model, params, *, shard: int | str = "auto",
                 mesh_axis: str = "model", **kw):
        super().__init__(model, params, **kw)
        cfg = self.cfg
        ndev = len(jax.devices())
        if shard == "auto":
            itemsize = jnp.dtype(cfg.compute_dtype).itemsize
            kv_itemsize = jnp.dtype(self.kv_dtype).itemsize \
                if self.kv_dtype is not None else itemsize
            want = tune_shard_degree(
                heads_kv=cfg.num_kv_heads,
                group=cfg.num_heads // cfg.num_kv_heads,
                n_ctx=self.max_len, e=cfg.hd, batch=self.batch_size,
                itemsize=itemsize, page=self.page_size,
                kv_itemsize=kv_itemsize)
            shard = _largest_divisor_leq(cfg.num_kv_heads,
                                         min(want, ndev))
        if not isinstance(shard, int) or shard < 1:
            raise ValueError(f"bad shard degree {shard!r}")
        if cfg.num_kv_heads % shard:
            raise ValueError(
                f"shard degree {shard} does not divide "
                f"num_kv_heads={cfg.num_kv_heads}")
        if shard > ndev:
            raise ValueError(f"shard degree {shard} > {ndev} devices")
        self.shard = shard
        self.mesh_axis = mesh_axis
        self.mesh = Mesh(np.asarray(jax.devices()[:shard]), (mesh_axis,))
        # replicated weights: forward-only serving, no grads -> the
        # replication costs no collective traffic (sharding.py sp_rep)
        self.params = jax.device_put(
            self.params, NamedSharding(self.mesh, P()))
        _, self._num_units, _ = unit_layout(cfg)
        self._out_bytes_per_row = (
            cfg.num_heads * cfg.hd * jnp.dtype(cfg.compute_dtype).itemsize)

    def _make_cache(self):
        cache = super()._make_cache()
        specs = cache_specs(cache, self.mesh, layout="paged")
        return jax.device_put(cache, named(self.mesh, specs))

    def serve(self, requests):
        # the dispatch seam consults kv_shard at TRACE time; tracing
        # happens on the step closures' first call inside serve()
        with ctx.kv_shard(self.mesh, self.mesh_axis):
            return super().serve(requests)

    def _observe_step(self, chunk_tokens, live):
        m = self.metrics
        m.gauge("shard.degree", "active mesh shard degree").record(
            self.shard)
        if self.shard > 1:
            # analytic interconnect accounting: each unit's attention
            # output all-gathers (shard-1)/shard of its bytes per chip
            rows = live + (1 if chunk_tokens else 0)
            gather = (self._num_units * rows * self._out_bytes_per_row
                      * (self.shard - 1) // self.shard)
            m.counter("shard.allgather_bytes",
                      "per-chip output all-gather bytes (analytic)"
                      ).inc(gather)
            if chunk_tokens:
                m.counter("shard.ring_hops",
                          "head-block ring ppermute hops (prefill)").inc(
                    (self.shard - 1) * self._num_units)

    @property
    def shard_stats(self) -> dict:
        """Sharding summary of the last serve() call."""
        c = self.metrics.counter
        return {
            "degree": self.shard,
            "allgather_bytes": int(c("shard.allgather_bytes").value),
            "ring_hops": int(c("shard.ring_hops").value),
        }


class LeastLoadedRouter:
    """Data-parallel request router over engine replicas.

    Requests are assigned (in arrival order, deterministically) to the
    replica with the least pending ESTIMATED tokens — prompt length
    plus the decode budget, the same unit the admission planner
    reserves pages in. ``serve`` then drives each replica's serve()
    over its share and merges the result dicts (rids are globally
    unique). Replica shares run sequentially here — the host scheduler
    is single-threaded — so the router's win in this repo is capacity
    (N pools) and the load-balance accounting, not wall-clock overlap.
    """

    def __init__(self, engines):
        if not engines:
            raise ValueError("router needs at least one engine")
        self.engines = list(engines)
        self.stats: dict | None = None

    def route(self, requests):
        """-> (shares, est_tokens): per-replica request lists/loads."""
        load = [0] * len(self.engines)
        shares = [[] for _ in self.engines]
        for r in requests:
            i = min(range(len(load)), key=lambda j: load[j])
            shares[i].append(r)
            load[i] += len(r.prompt) + r.max_new_tokens
        return shares, load

    def serve(self, requests):
        shares, load = self.route(requests)
        out = {}
        for eng, share in zip(self.engines, shares):
            if share:
                out.update(eng.serve(share))
        mean = sum(load) / len(load)
        self.stats = {
            "replicas": len(self.engines),
            "requests": [len(s) for s in shares],
            "est_tokens": load,
            "balance": (max(load) / mean) if mean else 1.0,
        }
        return out
