"""deepseek-v2-lite [moe] — 27L d_model=2048 16H MLA (kv_lora 512, qk 128
nope + 64 rope, v 128, no q_lora), YaRN RoPE x40; layer 0 a dense MLP of
10944, layers 1-26 DeepSeekMoE: 64 routed experts of 1408, top-6 by softmax
without renormalisation, 2 shared experts; vocab 102400.
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]

``CONFIG`` is one rank of 8-way expert parallelism: rank 0 holds routed
experts 0-7 of every expert layer. The router keeps its 64 outputs and its
top-6; a claim on an expert held elsewhere adds nothing here. Attention,
the shared experts, the dense layer, all 27 layers and the vocabulary are
whole (3.11 B parameters; 15.7 B uncut).
"""

import dataclasses

from repro.configs.base import ModelConfig

#: the published expert count, and the expert-parallel deployment CONFIG is
#: one rank of
PUBLISHED_EXPERTS = 64
EP_SIZE = 8
EP_RANK = 0

UNCUT = ModelConfig(
    name="deepseek-v2-lite", family="moe", num_layers=27, d_model=2048,
    num_heads=16, num_kv_heads=16, d_ff=10944, vocab_size=102400,
    block_pattern=("moe",), first_dense_layers=1,
    num_experts=PUBLISHED_EXPERTS, num_experts_per_tok=6, expert_d_ff=1408,
    moe_dropless=True, norm_topk_prob=False, shared_expert_d_ff=2 * 1408,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=10000.0, rope_factor=40.0,
    rope_original_max_pos=4096, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
    yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
)

CONFIG = dataclasses.replace(
    UNCUT, experts_held=PUBLISHED_EXPERTS // EP_SIZE,
    expert_offset=EP_RANK * (PUBLISHED_EXPERTS // EP_SIZE))


def smoke_config() -> ModelConfig:
    """Every kind at a CPU size: a leading dense layer and two expert
    layers, a router over 8 experts of which 4 are held, top-3, a shared
    expert, MLA, and YaRN whose ramp spans rope pairs 1-3 (of 4)."""
    return dataclasses.replace(
        UNCUT, name="deepseek-v2-lite-smoke", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=4, d_ff=96, vocab_size=512,
        num_experts=8, num_experts_per_tok=3, expert_d_ff=32,
        experts_held=4, expert_offset=0, shared_expert_d_ff=64,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, dtype="float32", remat=False)
