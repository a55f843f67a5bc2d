"""DeepSeek-V2-Lite — MLA without query compression, YaRN, one leading dense
layer, then MoE layers of 64 routed experts (top-6, softmax gates, not
renormalised) plus 2 shared experts
Source: https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json
(DeepSeek-V2, arXiv:2405.04434)

Assumed: the repo's half-split RoPE convention. The published model
applies RoPE to an interleaved layout of the rope columns; with weights
drawn at random the two are the same function up to a fixed permutation
of the rope columns of ``wq`` and ``wkv_a``.
"""
from repro.models.transformer import ArchConfig

# (factor, original_max_positions, beta_fast, beta_slow, mscale,
#  mscale_all_dim) of the published rope_scaling
YARN = (40.0, 4096, 32.0, 1.0, 0.707, 0.707)

FULL = ArchConfig(
    name='deepseek-v2-lite',
    family='moe',
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102400,
    mla=True,
    q_rank=None,
    kv_rank=512,
    d_nope=128,
    d_rope=64,
    d_v=128,
    n_experts=64,
    top_k=6,
    moe_d_ff=1408,
    first_k_dense=1,
    n_shared_experts=2,
    norm_topk_prob=False,
    routed_scaling=1.0,
    rope_theta=10000.0,
    yarn=YARN,
    tie_embeddings=False,
)

SMOKE = ArchConfig(
    name='deepseek-v2-lite-smoke',
    family='moe',
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    vocab=128,
    mla=True,
    q_rank=None,
    kv_rank=16,
    d_nope=8,
    d_rope=8,
    d_v=8,
    n_experts=8,
    top_k=3,
    moe_d_ff=32,
    first_k_dense=1,
    n_shared_experts=2,
    norm_topk_prob=False,
    routed_scaling=1.0,
    yarn=(40.0, 64, 32.0, 1.0, 0.707, 0.707),
    tie_embeddings=False,
)
