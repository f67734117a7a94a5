"""Shared model substrate: configs, norms, rotary embeddings, init."""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 64
    top_k: int = 6
    d_expert: int = 1408
    num_shared: int = 0          # shared experts (deepseek-moe style)
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    # router of the dropless held-expert layer (kind "mla_moe",
    # models/moe.py ``routed_experts``); the capacity layer above
    # ignores these
    scoring: str = "softmax"     # routed_experts takes "sigmoid" only
    score_bias: bool = False     # per-layer correction bias (E,): it
    #                              chooses the experts, never weights them
    norm_topk: bool = True       # renormalise the chosen weights to sum 1
    #                              (routed_experts takes True only)
    routed_scale: float = 1.0    # routed_scaling_factor
    held: tuple[int, int] | None = None  # experts [lo, hi) held; None: all

    @property
    def held_range(self) -> tuple[int, int]:
        return self.held or (0, self.num_experts)

    @property
    def num_held(self) -> int:
        lo, hi = self.held_range
        return hi - lo


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (deepseek_v3, no q compression): K and V
    come from one latent of ``kv_lora_rank`` values per token plus one
    shared rope key of ``qk_rope_head_dim``; the cache holds those
    ``latent_dim`` values a token and layer."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_dim(self) -> int:
        """Width of a latent pool row: ``latent_dim`` zero-padded to the
        TPU's 128-lane tile, which the decode kernel's page copies need
        (Mosaic refuses a 576-wide slice); the zeros add nothing to a
        score."""
        return -(-self.latent_dim // 128) * 128

    @property
    def sm_scale(self) -> float:
        return self.qk_head_dim ** -0.5


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    n_groups: int = 1
    chunk: int = 256
    conv_width: int = 4


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None  # default d_model // num_heads
    qk_norm: bool = False
    mlp: str = "swiglu"          # swiglu | gelu
    rope_theta: float = 10000.0
    rope: bool = True
    causal: bool = True
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    # moe
    moe: MoEConfig | None = None
    # latent attention: every layer is kind "mla" (dense MLP of d_ff) or
    # "mla_moe"; the first ``dense_lead`` layers of a MoE stack are dense
    mla: MLAConfig | None = None
    dense_lead: int = 0
    # hybrid (recurrentgemma): repeating unit of block kinds + tail
    block_pattern: tuple[str, ...] | None = None   # e.g. ("rec","rec","attn")
    pattern_tail: tuple[str, ...] = ()
    window: int | None = None    # sliding window for "attn" blocks in hybrids
    lru_width: int | None = None
    # ssm
    ssm: SSMConfig | None = None
    # encoder-decoder (audio) / frontends (vlm, audio)
    encoder_layers: int = 0      # > 0 => enc-dec; decoder uses num_layers
    frontend: str | None = None  # "vision" | "audio" -> stub embeddings input
    num_frontend_tokens: int = 0
    # compute
    attn_impl: str = "xla"       # xla | xla_full | pallas
    attn_chunk: int = 1024       # q-chunk of the MAS-dataflow XLA attention
    remat: bool = True
    # two-level scan remat (§Perf iter 9): outer_scan o splits the unit
    # scan into o x (units/o); only o carries are saved for the backward
    # (peak ~ o + units/o hiddens instead of units)
    outer_scan: int | None = None
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        """Flat list of block kinds for the decoder stack."""
        if self.family == "ssm":
            return ("ssd",) * self.num_layers
        if self.mla is not None:
            body = "mla_moe" if self.moe is not None else "mla"
            return (("mla",) * self.dense_lead
                    + (body,) * (self.num_layers - self.dense_lead))
        if self.block_pattern is None:
            return ("attn",) * self.num_layers
        kinds: list[str] = []
        while len(kinds) < self.num_layers - len(self.pattern_tail):
            kinds.extend(self.block_pattern)
        kinds = kinds[: self.num_layers - len(self.pattern_tail)]
        kinds.extend(self.pattern_tail)
        return tuple(kinds)

    # ---- analytic parameter / FLOP accounting (for roofline §) ----
    def param_count(self) -> int:
        d, v = self.d_model, self.vocab_size
        hq, hkv, e = self.num_heads, self.num_kv_heads, self.hd
        n = v * d  # embedding (tied unembed)
        if not self.tie_embeddings:
            n += v * d

        def attn_p():
            p = d * hq * e + 2 * d * hkv * e + hq * e * d + d
            if self.qk_norm:
                p += 2 * e
            return p

        def mla_p():
            a = self.mla
            return (d * hq * a.qk_head_dim + d * a.latent_dim
                    + a.kv_lora_rank
                    + a.kv_lora_rank * hq * (a.qk_nope_head_dim
                                             + a.v_head_dim)
                    + hq * a.v_head_dim * d + d)

        def mlp_p():
            return (3 if self.mlp == "swiglu" else 2) * d * self.d_ff + d

        def moe_p():
            m = self.moe
            p = d * m.num_experts  # router
            if m.score_bias:
                p += m.num_experts
            p += m.num_held * 3 * d * m.d_expert
            p += m.num_shared * 3 * d * m.d_expert
            return p + d

        def ssd_p():
            s = self.ssm
            di = s.expand * d
            nh = di // s.head_dim
            in_p = d * (2 * di + 2 * s.n_groups * s.d_state + nh)
            return in_p + di * s.conv_width + di * d + 2 * nh + d

        def rec_p():
            w = self.lru_width or d
            return 2 * d * w + w * 4 + w * d + 3 * w + d

        total = 0
        for kind in self.layer_kinds:
            if kind == "attn":
                total += attn_p() + (moe_p() if self.moe else mlp_p())
            elif kind == "mla":
                total += mla_p() + mlp_p()
            elif kind == "mla_moe":
                total += mla_p() + moe_p()
            elif kind == "rec":
                total += rec_p() + mlp_p()
            elif kind == "ssd":
                total += ssd_p()
        for _ in range(self.encoder_layers):
            total += attn_p() + mlp_p()          # encoder self-attn block
        if self.encoder_layers:
            total += self.num_layers * attn_p()  # decoder cross-attn
        return n + total + d  # final norm

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        full_experts = m.num_held * 3 * self.d_model * m.d_expert
        active_experts = (m.top_k + m.num_shared) * 3 * self.d_model * m.d_expert
        n_attn_layers = sum(k in ("attn", "mla_moe") for k in self.layer_kinds)
        return (self.param_count()
                - n_attn_layers * (full_experts
                                   + m.num_shared * 3 * self.d_model * m.d_expert)
                + n_attn_layers * active_experts)


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., N, E) with positions (..., N) or (N,)."""
    e = x.shape[-1]
    freqs = rope_frequencies(e, theta)                      # (E/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(angles), jnp.cos(angles)             # (..., N, E/2)
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    y = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return y.astype(x.dtype)


def sinusoidal_positions(positions, d: int) -> jax.Array:
    """(N,) positions (int, may be traced) -> (N, D) sinusoidal table."""
    pos = jnp.asarray(positions, jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    angle = pos / (10000 ** (dim / d))
    out = jnp.zeros((pos.shape[0], d), jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(angle))
    out = out.at[:, 1::2].set(jnp.cos(angle))
    return out


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis=0, dtype=jnp.float32):
    fan_in = shape[in_axis] if isinstance(in_axis, int) else math.prod(
        shape[a] for a in in_axis
    )
    std = fan_in**-0.5
    return (std * jax.random.truncated_normal(key, -2, 2, shape)).astype(dtype)


def split_keys(key, names):
    keys = jax.random.split(key, len(names))
    return dict(zip(names, keys))
