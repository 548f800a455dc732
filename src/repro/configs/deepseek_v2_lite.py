"""DeepSeek-V2-Lite [moe] — latent attention (MLA) + DeepSeekMoE
[hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434].

27L d_model=2048 16H d_ff=10944 (the one leading dense layer) vocab=102400,
untied. MLA with no query low-rank projection: q 16 x (128 nope + 64 rope),
a 512-wide key/value latent with its own RMSNorm and one 64-wide rope key
shared by all heads, values 16 x 128. YaRN rope (factor 40 over 4096
positions, mscale 0.707 on both sides, so cos/sin keep their scale and the
softmax scale is 192^-0.5 (0.1 * 0.707 * ln 40 + 1)^2). Layers 1-26: 64
routed experts of width 1408, softmax scores, greedy top-6 kept
unnormalised, and 2 ungated shared experts (one SwiGLU of width 2816).

``experts_held`` cuts the routed experts to one chip's share of an expert-
parallel deployment; the default holds all 64.
"""
from repro.configs.base import (LayerDef, MLAConfig, ModelConfig, MoEConfig,
                                YarnScaling)


def make_config(experts_held: int = 0, expert_offset: int = 0) -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        arch_type="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=10944,
        vocab_size=102400,
        head_dim=192,
        prefix=(LayerDef("mla", dense=True),),
        pattern=(LayerDef("mla"),),
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        rope_theta=10000.0,
        rope_scaling=YarnScaling(factor=40.0, original_max_position=4096,
                                 beta_fast=32.0, beta_slow=1.0,
                                 mscale=0.707, mscale_all_dim=0.707),
        max_position=163840,
        moe=MoEConfig(
            num_experts=64,
            top_k=6,
            expert_ff=1408,
            num_shared_experts=2,
            norm_topk_prob=False,
            routed_scaling_factor=1.0,
            shared_gate=False,
            experts_held=experts_held,
            expert_offset=expert_offset,
        ),
        tie_embeddings=False,
        norm_eps=1e-6,
        source="hf:deepseek-ai/DeepSeek-V2-Lite",
    )
