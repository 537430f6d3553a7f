"""DeepSeek-V2-Lite [arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite]: 27L d2048
16H multi-head latent attention (kv_lora_rank 512, qk nope 128 + rope 64,
v 128, no q_lora), YaRN RoPE x40 over 4096; layer 0 dense (d_ff 10944),
layers 1-26 MoE with 64 routed experts top-6 (softmax over all 64, not
renormalised) and 2 shared experts, each 1408 wide; vocab 102400; RMSNorm
eps 1e-6. The whole model: every routed expert held."""

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102400,
    norm_eps=1e-6,
    rope_theta=10000.0,
    first_dense_layers=1,
    mla=MLAConfig(
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_factor=40.0,
        rope_original_max=4096,
        beta_fast=32.0,
        beta_slow=1.0,
        mscale=0.707,
        mscale_all_dim=0.707,
    ),
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        d_ff_expert=1408,
        n_shared_experts=2,
        norm_topk_prob=False,
    ),
    param_dtype="bfloat16",
)
