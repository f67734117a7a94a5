"""Trace-time sharding-policy context.

Models stay mesh-agnostic; the launcher (dryrun/train/serve) activates a
policy around tracing and the model code calls ``constrain`` at the
documented cut points. With no active policy every call is a no-op, so
unit tests and single-device runs are untouched.

The default policy implements the §Perf iteration-1 scheme: activations
sequence-sharded over 'model' (the MAS Q-row-block stream mapped onto
the TP axis — every device owns a row-block stream and the full softmax
row stays local, exactly the paper's row-granularity invariant), with
FSDP weight gathers instead of head-splitting — this removes the fp32
score all-reduces that dominate the GQA baselines (kv_heads don't divide
model=16).
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def pvary(x, axes: tuple[str, ...]):
    """Mark ``x`` device-varying over ``axes`` inside shard_map, so a
    fresh constant and a per-device value give loop carries one type.
    Shared by the shard_map-based collectives."""
    return jax.lax.pcast(x, axes, to="varying")


def _axes() -> dict[str, int] | None:
    return getattr(_state, "axes", None)


def policy_kind() -> str:
    return getattr(_state, "kind", "tp_sp")


@contextlib.contextmanager
def sharding_policy(mesh, kind: str = "tp_sp"):
    """kind: "tp_sp" (seq-sharded activations over 'model') or "fsdp"
    (the model axis is extra data parallelism; no activation constraints
    beyond the batch — right for small-dense archs where TP=16 would
    trade matmul locality for gathers; see §Perf iter 5)."""
    prev, prev_kind = _axes(), policy_kind()
    _state.axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    _state.kind = kind
    try:
        yield
    finally:
        _state.axes = prev
        _state.kind = prev_kind


@contextlib.contextmanager
def kv_shard(mesh, axis: str = "model"):
    """Activate KV-head sharding for the paged serving dispatchers.

    While active, ``models.attention.paged_*`` constrain the page pools
    and per-head intermediates onto ``axis`` of ``mesh`` (decode /
    verify) and route chunked prefill through the head-block ring
    (``distributed.paged.ring_paged_prefill``). The state is consulted
    at TRACE time, so the serving engine wraps its jitted step closures'
    first call (i.e. ``serve()``) in this context (DESIGN.md §11). With
    no active state every dispatch is the stock single-chip path.
    """
    prev = getattr(_state, "kv_shard", None)
    _state.kv_shard = (mesh, axis)
    try:
        yield
    finally:
        _state.kv_shard = prev


def kv_shard_state():
    """(mesh, axis) while inside ``kv_shard``; None otherwise."""
    return getattr(_state, "kv_shard", None)


def batch_axes() -> tuple[str, ...]:
    axes = _axes() or {}
    names = ("pod", "data", "model") if policy_kind() == "fsdp" else (
        "pod", "data")
    return tuple(a for a in names if a in axes)


def constrain(x, spec_builder):
    """Apply with_sharding_constraint if a policy is active and the spec
    divides x's shape evenly; else identity.

    spec_builder: callable(axes: dict) -> PartitionSpec | None
    """
    axes = _axes()
    if axes is None:
        return x
    spec = spec_builder(axes)
    if spec is None:
        return x
    for dim, names in zip(x.shape, tuple(spec)):
        if names is None:
            continue
        size = 1
        for a in (names,) if isinstance(names, str) else names:
            size *= axes.get(a, 1)
        if size == 0 or dim % size != 0:
            return x
    return jax.lax.with_sharding_constraint(x, spec)


def seq_sharded_activations(x):
    """(B, S, D) hidden: batch over (pod, data), seq over model."""
    if policy_kind() == "fsdp":
        return constrain(x, lambda axes: P(batch_axes()))
    return constrain(
        x, lambda axes: P(batch_axes(), "model" if "model" in axes else None)
    )


def seq_sharded_heads(x):
    """(B, H, S, E): batch over (pod, data), SEQ over model (row-block
    stream parallelism — heads stay whole so GQA ratios never split)."""
    if policy_kind() == "fsdp":
        return constrain(x, lambda axes: P(batch_axes()))
    return constrain(
        x,
        lambda axes: P(batch_axes(), None,
                       "model" if "model" in axes else None, None),
    )


def replicated_heads(x):
    """(B, H, S, E) K/V: gathered once per layer (batch-sharded only).
    One all-gather beats the per-chunk fp32 partial-sum all-reduces XLA
    otherwise emits for the PV contraction (§Perf iter 7)."""
    return constrain(x, lambda axes: P(batch_axes()))
