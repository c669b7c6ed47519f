"""deepseek-v2-lite [mla + moe] — DeepSeek-V2-Lite (arXiv:2405.04434).

27L d_model=2048 16H vocab=102400 [hf:deepseek-ai/DeepSeek-V2-Lite
config.json]. MLA without a query LoRA (q_lora_rank null): qk_nope=128,
qk_rope=64, v_head=128, kv_lora_rank=512. YaRN: factor 40 over 4096
original positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim =
0.707, rope_theta 10000. The first layer is dense (intermediate 10944);
the other 26 route greedy top-6 of 64 experts (width 1408, softmax
scores, not renormalised, routed_scaling_factor 1) beside 2 shared
experts, with the sequence-wise balance loss (seq_aux; its coefficient,
absent from config.json, is DeepSeek-V2's 0.001). rms_norm_eps 1e-6,
untied embeddings.
"""
from ..models.config import ModelConfig

ARCH_ID = "deepseek-v2-lite"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, family="mla",
        n_layers=27, d_model=2048, vocab=102400,
        n_heads=16, n_kv_heads=16, head_dim=128,
        q_lora_rank=0, kv_lora_rank=512,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        rope_theta=10000.0, yarn_factor=40.0, yarn_original_len=4096,
        yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707, rope_interleave=True,
        d_ff=10944, act="swiglu", first_k_dense=1,
        n_experts=64, n_routed_experts=64, top_k=6, d_ff_expert=1408,
        n_shared_experts=2, routed_scaling=1.0, router_aux_coef=0.001,
    )


def smoke_config() -> ModelConfig:
    """1 dense + 4 MoE layers, 4 of 8 routed experts held."""
    return config().replace(n_layers=5, d_model=64, vocab=199, n_heads=4,
                            n_kv_heads=4, head_dim=16, kv_lora_rank=16,
                            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                            d_ff=128, n_experts=4, n_routed_experts=8,
                            top_k=3, d_ff_expert=32)
