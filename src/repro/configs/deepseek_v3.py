"""deepseek-v3 [moe]: latent attention (MLA), 256 routed experts top-8 with
one shared, sigmoid router with a correction bias over 8 groups (top 4),
3 leading dense layers.

[hf:deepseek-ai/DeepSeek-V3 config.json]. 61L d_model=7168 128H;
q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
qk_rope_head_dim=64, v_head_dim=128; YaRN rope (factor 40 over 4096
positions, beta 32/1, mscale 1/1); d_ff=18432 (dense layers),
moe_intermediate_size=2048; routed_scaling_factor=2.5; vocab=129280. The
multi-token-prediction module (num_nextn_predict_layers=1) serves only
speculative decoding and is not built. ``MoEConfig.held`` cuts a layer
to one expert-parallel device's experts (the benchmark holds chip 0 of
EP32: experts 0-7).
"""
from repro.models.config import ModelConfig, MoEConfig, YarnConfig

YARN = YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                  beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)

CONFIG = ModelConfig(
    name="deepseek-v3", family="moe",
    num_layers=61, d_model=7168, num_heads=128, num_kv_heads=128,
    head_dim=128, d_ff=18432, vocab_size=129280, rope_theta=10_000.0,
    yarn=YARN, q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128,
    qk_rope_dim=64, v_head_dim=128, first_k_dense=3,
    moe=MoEConfig(num_experts=256, top_k=8, d_ff_expert=2048,
                  num_shared_experts=1, router="sigmoid", n_group=8,
                  topk_group=4, routed_scaling=2.5),
    optimizer="adafactor", grad_accum=8, logits_chunk=512,
)

# every mechanism at tiny widths: q_lora, nope and rope parts, YaRN, one
# dense and two MoE layers, 4 groups of which 2 stay, a shared expert;
# all 32 experts held (dropless)
SMOKE = ModelConfig(
    name="deepseek-v3-smoke", family="moe",
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
    d_ff=96, vocab_size=128, rope_theta=10_000.0, yarn=YARN,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
    v_head_dim=16, first_k_dense=1,
    moe=MoEConfig(num_experts=32, top_k=4, d_ff_expert=16,
                  num_shared_experts=1, router="sigmoid", n_group=4,
                  topk_group=2, routed_scaling=2.5, held=(0, 32)),
    remat=False, logits_chunk=32,
)
