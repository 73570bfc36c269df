"""deepseek-v2-236b — MLA attention + fine-grained MoE [arXiv:2405.04434].

As published (huggingface.co/deepseek-ai/DeepSeek-V2, config.json):
MLA with kv_lora_rank=512, q_lora_rank=1536, a decoupled RoPE key of 64
under YaRN (factor 40 over 4096 positions, beta 32/1, mscale 0.707 on
both the rotary and the softmax scale); one dense prologue layer
(``first_k_dense_replace=1``) of width 12288, then 59 MoE layers of 160
routed experts of width 1536 and 2 shared experts, 6 experts a token by
group-limited greedy routing (the best 3 of 8 groups), softmax scores,
gates not renormalised and scaled by 16.  ``d_ff`` is the expert width
(the assignment's 1536); the prologue's width is its LayerSpec's.
"""
from repro.configs.base import (ArchConfig, LayerSpec, MLAConfig, MoEConfig,
                                Stage, YarnConfig)

CONFIG = ArchConfig(
    name="deepseek-v2-236b",
    family="moe",
    citation="arXiv:2405.04434 (DeepSeek-V2)",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,
    head_dim=128,
    d_ff=1536,
    vocab_size=102400,
    stages=(
        Stage((LayerSpec(kind="attn", ffn="dense", d_ff=12288),), 1),
        Stage((LayerSpec(kind="attn", ffn="moe"),), 59),
    ),
    rope_theta=10000.0,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=1536,
                  qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128),
    yarn=YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=160, top_k=6, d_expert=1536, n_shared=2,
                  capacity_factor=1.25, n_group=8, topk_group=3,
                  norm_topk=False, routed_scale=16.0),
    norm="rmsnorm",
    act="silu",
    tie_embeddings=False,
    moment_dtype="bfloat16",
)
