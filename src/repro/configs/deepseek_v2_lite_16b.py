"""DeepSeek-V2-Lite-16B [arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite
config.json]: MLA kv_lora=512 with no q compression, YaRN rope scaling
(factor 40 over 4096 original positions), one dense layer of 10944, then
64 routed experts of 1408 (softmax greedy top-6, no renormalisation) and
2 shared experts a layer.  (The assignment line's "160 routed" is the
236B config; 64 routed experts is the HF config of the Lite model.)"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe", num_layers=27, d_model=2048,
    num_heads=16, num_kv_heads=16, d_ff=1408, vocab_size=102400,
    head_dim=128, mlp_type="swiglu", norm_eps=1e-6,
    rope_scaling=RopeScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_nope_dim=128,
                  qk_rope_dim=64, v_dim=128),
    moe=MoEConfig(num_experts=64, num_shared=2, top_k=6, d_expert=1408,
                  first_dense=1, dense_ff=10944, router_width=64))
