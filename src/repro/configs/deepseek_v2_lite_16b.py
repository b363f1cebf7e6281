"""DeepSeek-V2-Lite 16B [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite
config.json].

27 layers, d_model 2048, 16 heads; MLA with kv_lora_rank 512 and no
q-LoRA (qk_nope 128, qk_rope 64, v_head 128) under YaRN rope (factor 40
over an original 4096 positions, beta_fast 32, beta_slow 1, mscale and
mscale_all_dim 0.707). Layer 0 has a dense SwiGLU FFN of 10944; layers
1-26 are MoE: 64 routed experts of 1408, top-6 of a softmax (greedy), gate
weights not renormalized (``norm_topk_prob`` false), routed scaling 1, and
2 shared experts. Vocabulary 102400, untied head.
"""
from repro.configs.base import LayerKind, ModelConfig, YarnRope

YARN = YarnRope(factor=40.0, original_max_position=4096, beta_fast=32.0,
                beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def full():
    return ModelConfig(
        arch="deepseek-v2-lite-16b", family="moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
        d_ff=10944, d_ff_expert=1408, vocab_size=102400,
        n_experts=64, n_shared_experts=2, top_k=6,
        norm_topk_prob=False, routed_scaling_factor=1.0,
        kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        rope_yarn=YARN,
        prefix=(LayerKind("mla", "dense"),),
        pattern=(LayerKind("mla", "moe"),),
    )


def smoke():
    return ModelConfig(
        arch="deepseek-v2-lite-smoke", family="moe",
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, d_ff_expert=48, vocab_size=512,
        n_experts=8, n_shared_experts=1, top_k=2,
        norm_topk_prob=False, routed_scaling_factor=1.0,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        rope_yarn=YarnRope(factor=40.0, original_max_position=16,
                           beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                           mscale_all_dim=0.707),
        prefix=(LayerKind("mla", "dense"),),
        pattern=(LayerKind("mla", "moe"),), dtype="float32",
        q_chunk=64, kv_chunk=64,
    )
