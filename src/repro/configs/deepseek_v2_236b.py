"""DeepSeek-V2 (236B total / 21B active) [moe] — arXiv:2405.04434,
hf:deepseek-ai/DeepSeek-V2 config.json.

60 layers, d_model=5120, 128 heads, vocab=102400, untied, SiLU, RMSNorm
eps 1e-6.  Every layer attends with MLA: kv_lora_rank=512,
q_lora_rank=1536, decoupled RoPE head dim 64, per-head qk_nope/v dims 128,
rotary positions under YaRN (factor 40, original context 4096, beta_fast
32, beta_slow 1, mscale = mscale_all_dim = 0.707).  The first layer
(first_k_dense_replace=1) has a dense SwiGLU MLP of width 12288; the 59
after it are MoE: 160 routed experts of width 1536, 6 per token, plus 2
shared experts, routed by group_limited_greedy (8 groups, the best 3 kept)
with softmax scores, no top-k renormalisation and routed_scaling_factor 16.
Full (latent) attention is still quadratic -> long_500k skipped; the MLA
compressed cache is what makes decode_32k cheap.
"""

from repro.configs.base import LayerSpec, ModelConfig, YarnScaling, register


@register("deepseek-v2-236b")
def deepseek_v2() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-236b",
        family="moe",
        num_layers=60,
        first_k_dense=1,
        d_model=5120,
        num_heads=128,
        num_kv_heads=128,        # MLA: heads share the latent cache
        head_dim=128,
        d_ff=12288,              # the leading dense layer's MLP
        vocab_size=102400,
        block_pattern=(LayerSpec("attn", "moe"),),
        use_mla=True,
        kv_lora_rank=512,
        q_lora_rank=1536,
        rope_head_dim=64,
        v_head_dim=128,
        moe_num_experts=160,
        moe_top_k=6,
        moe_num_shared=2,
        moe_d_ff=1536,
        moe_n_group=8,
        moe_topk_group=3,
        moe_norm_topk=False,
        moe_routed_scale=16.0,
        rope_theta=10000.0,
        rope_scaling=YarnScaling(factor=40.0, original_max_position=4096,
                                 beta_fast=32.0, beta_slow=1.0,
                                 mscale=0.707, mscale_all_dim=0.707),
        norm_eps=1e-6,
    )
