"""deepseek-v2-lite [moe] — latent attention (MLA) + 2 shared and 64
routed experts, top-6.

27L d_model=2048 16H, MLA without query compression (kv_lora_rank 512,
qk_nope 128, qk_rope 64, v 128), YaRN rope (factor 40 over 4096
positions, mscale = mscale_all_dim = 0.707), layer 0 a dense MLP of
10944, layers 1..26 MoE (64 x 1408, top-6 softmax gates, not
renormalised, 2 shared experts), vocab 102400, untied
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434].
``head_dim`` is the query/key head width, qk_nope + qk_rope.
Pure full attention -> long_500k skipped.
"""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv=16,
    head_dim=192,
    d_ff=1408,          # per-expert hidden, as deepseek-moe-16b
    vocab=102_400,
    layer_pattern=("mla",),
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    yarn_factor=40.0,
    yarn_original_len=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale_all_dim=0.707,
    ffn_kind="swiglu",
    ffn_pattern=("moe",),
    first_k_dense=1,
    dense_d_ff=10944,
    n_experts=64,
    experts_per_token=6,
    n_shared_experts=2,
    moe_d_ff=1408,
    moe_norm_topk=False,
    tie_embeddings=False,
    norm_eps=1e-6,
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke",
    family="moe",
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv=4,
    head_dim=24,
    d_ff=32,
    vocab=512,
    layer_pattern=("mla",),
    kv_lora_rank=32,
    qk_nope_dim=16,
    qk_rope_dim=8,
    v_head_dim=16,
    yarn_factor=4.0,    # ramp over the rope slice at this width
    yarn_original_len=32,
    yarn_mscale_all_dim=0.707,
    ffn_kind="swiglu",
    ffn_pattern=("moe",),
    first_k_dense=1,
    dense_d_ff=96,
    n_experts=8,
    experts_per_token=2,
    n_shared_experts=2,
    moe_d_ff=32,
    moe_norm_topk=False,
    tie_embeddings=False,
    compute_dtype="float32",
)
