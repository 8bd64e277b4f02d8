"""deepseek-v2-lite [moe]: 27L d_model=2048 16H, multi-head latent
attention (kv_lora_rank 512, q/k heads of 128 + 64 rope lanes, values of
128, no q_lora), YaRN rope (factor 40 over 4096), layer 0 dense
(d_ff=10944), layers 1-26 DeepSeek-MoE: 64 routed experts of 1408, top-6
softmax without renormalisation, 2 shared experts; vocab=102400, untied.
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]

The full preset is one chip's share of an 8-way expert-parallel
deployment with data-parallel attention: every layer's attention, shared
experts and router whole, and 8 of the 64 routed experts (0-7).  The
router scores all 64; tokens routed elsewhere get nothing from the
absent experts, here and in the plain reference alike.
"""

from repro.config.base import (
    ArchConfig,
    AttentionKind,
    FFNKind,
    LayerSpec,
    MLAConfig,
    MoEConfig,
    YarnScaling,
    register_arch,
)

DENSE = LayerSpec(attention=AttentionKind.LATENT, ffn=FFNKind.DENSE)
MOE = LayerSpec(attention=AttentionKind.LATENT, ffn=FFNKind.MOE)

FULL = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    lead=(DENSE,),
    pattern=(MOE,),
    moe=MoEConfig(num_experts=64, top_k=6, capacity_factor=0.0,
                  expert_ff=1408, shared_experts=2, renormalize=False,
                  routed_scale=1.0, held_experts=8, first_held=0),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_scaling=YarnScaling(factor=40.0, original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    max_seq_len=163840,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    tie_embeddings=False,
    notes="one chip of EP 8: 8 of 64 routed experts held, dropless; "
    "latent page pool, paged latent decode kernel.",
)

# Every mechanism at a small size: a leading dense layer, two MoE layers
# holding experts 2-5 of 8 (top-3, two shared), latent and rope widths,
# and YaRN with positions past its original length.
SMOKE = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    d_ff=128,
    vocab_size=512,
    lead=(DENSE,),
    pattern=(MOE,),
    moe=MoEConfig(num_experts=8, top_k=3, capacity_factor=0.0,
                  expert_ff=32, shared_experts=2, renormalize=False,
                  routed_scale=1.0, held_experts=4, first_held=2),
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    rope_scaling=YarnScaling(factor=4.0, original_max_position_embeddings=32,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    max_seq_len=256,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    tie_embeddings=False,
)

register_arch(FULL, SMOKE)
