"""moonlight-16b-a3b [moe] — deepseek_v3 layout: latent attention (MLA),
one leading dense layer, then 26 layers of 64 sigmoid-routed experts
(top-6, noaux_tc correction bias, renormalised, x2.446) with 2 shared.
[hf:moonshotai/Moonlight-16B-A3B config.json]
"""

from repro.models.common import ArchConfig, MLAConfig, MoEConfig

ARCH = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,                  # intermediate_size: the dense layer's
    vocab_size=163840,
    mlp="swiglu",
    rope_theta=50000.0,
    tie_embeddings=False,
    norm_eps=1e-5,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    dense_lead=1,
    moe=MoEConfig(num_experts=64, top_k=6, d_expert=1408, num_shared=2,
                  scoring="sigmoid", score_bias=True, norm_topk=True,
                  routed_scale=2.446),
)

SMOKE = ArchConfig(
    name="moonlight-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    d_ff=96,
    vocab_size=512,
    mlp="swiglu",
    tie_embeddings=False,
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    dense_lead=1,
    moe=MoEConfig(num_experts=8, top_k=2, d_expert=32, num_shared=2,
                  scoring="sigmoid", score_bias=True, norm_topk=True,
                  routed_scale=2.446),
    attn_impl="xla_full",
)
