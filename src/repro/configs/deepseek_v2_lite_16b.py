"""deepseek-v2-lite-16b — MLA kv_lora=512, 2 shared + 64 routed top-6. [arXiv:2405.04434; hf]

27L d_model=2048 16H vocab=102400.  Layer 0 keeps a dense SwiGLU MLP of
width 10944 (first_k_dense_replace 1); layers 1-26 are mixture-of-experts:
64 routed experts of width 1408, top-6 by softmax score, the top-k weights
not renormalised (norm_topk_prob false, routed_scaling_factor 1), plus 2
shared experts, and a sequence-wise balance loss (seq_aux; alpha 0.001).
MLA: full-rank queries, kv_lora_rank 512, nope head dim 128, decoupled rope
head dim 64, v head dim 128; YaRN rope scaling (factor 40 over 4096
positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707).
Training runs dropless.
"""
from repro.config import (
    FAMILY_MOE, MLAConfig, ModelConfig, MoEConfig, YarnConfig, replace,
)

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family=FAMILY_MOE,
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,  # MLA: kv heads == q heads after latent up-projection
    head_dim=128,  # nope head dim
    d_ff=10944,  # the leading dense layer's MLP
    vocab_size=102400,
    mlp_kind="swiglu",
    rope_scaling=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    first_dense_layers=1,
    moe=MoEConfig(num_experts=64, top_k=6, expert_ff=1408,
                  num_shared_experts=2, shared_ff=1408,
                  norm_topk_prob=False, dropless=True, aux_loss_alpha=0.001),
    mla=MLAConfig(kv_lora_rank=512, rope_head_dim=64, v_head_dim=128),
    notes="MLA compresses the KV cache 512-dim latent; attention still quadratic -> long_500k skipped",
)


def smoke_config() -> ModelConfig:
    return replace(
        CONFIG, name="dsv2-smoke", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, head_dim=16, d_ff=32, vocab_size=256,
        moe=replace(CONFIG.moe, num_experts=8, top_k=2, expert_ff=32,
                    num_shared_experts=1, shared_ff=32),
        mla=MLAConfig(kv_lora_rank=32, rope_head_dim=8, v_head_dim=16),
        remat=False)
