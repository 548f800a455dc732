"""Architecture registry: --arch <id> resolution + reduced smoke variants."""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

from repro.configs.base import (
    LayerDef,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    SSMConfig,
    XLSTMConfig,
)

from repro.configs import (  # noqa: E402
    arctic_480b,
    command_r_35b,
    deepseek_v2_lite,
    gemma3_27b,
    qwen1p5_4b,
    qwen2_moe_a2p7b,
    qwen2_vl_2b,
    qwen3_1p7b,
    whisper_base,
    xlstm_350m,
    zamba2_2p7b,
)

_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {
    "xlstm-350m": xlstm_350m.make_config,
    "arctic-480b": arctic_480b.make_config,
    "zamba2-2.7b": zamba2_2p7b.make_config,
    "command-r-35b": command_r_35b.make_config,
    "qwen1.5-4b": qwen1p5_4b.make_config,
    "gemma3-27b": gemma3_27b.make_config,
    "whisper-base": whisper_base.make_config,
    "qwen2-moe-a2.7b": qwen2_moe_a2p7b.make_config,
    "qwen3-1.7b": qwen3_1p7b.make_config,
    "qwen2-vl-2b": qwen2_vl_2b.make_config,
    "deepseek-v2-lite": deepseek_v2_lite.make_config,
}

ARCH_IDS = tuple(_REGISTRY)


def get_config(arch: str) -> ModelConfig:
    if arch not in _REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[arch]()


def list_archs():
    return list(ARCH_IDS)


# ---------------------------------------------------------------------------
# Reduced variants for CPU smoke tests: <=2-ish layers (one of each block
# kind in the family), d_model<=512, <=4 experts, small vocab. A model with
# leading dense layers keeps one of them and one layer of its pattern.
# ---------------------------------------------------------------------------


def reduced_config(arch: str) -> ModelConfig:
    cfg = get_config(arch)
    if cfg.prefix:
        return _reduced_with_prefix(cfg)
    # Keep one instance of every distinct layer kind (max 2 layers).
    kinds = []
    pat = []
    for ld in cfg.layer_defs:
        key = (ld.kind, ld.window is None)
        if key not in kinds:
            kinds.append(key)
            pat.append(LayerDef(ld.kind, window=64 if ld.window else None))
        if len(pat) == 2:
            break
    if len(pat) == 1:
        pat = pat * 2  # always 2 layers
    d_model = 256
    num_heads = 4
    num_kv = max(1, num_heads // cfg.q_per_kv) if cfg.num_kv_heads < cfg.num_heads else num_heads
    moe = None
    if cfg.moe is not None:
        moe = MoEConfig(
            num_experts=4,
            top_k=min(2, cfg.moe.top_k),
            expert_ff=128,
            num_shared_experts=min(2, cfg.moe.num_shared_experts),
            dense_residual_ff=128 if cfg.moe.dense_residual_ff else 0,
        )
    ssm = None
    if cfg.ssm is not None:
        ssm = SSMConfig(state_dim=16, head_dim=32, expand=2, conv_width=4,
                        chunk_size=32)
    xl = None
    if cfg.xlstm is not None:
        xl = XLSTMConfig(num_heads=2)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-reduced",
        num_layers=len(pat),
        d_model=d_model,
        num_heads=num_heads,
        num_kv_heads=num_kv,
        head_dim=64,
        d_ff=0 if cfg.d_ff == 0 else 512,
        vocab_size=512,
        pattern=tuple(pat),
        repeats=1,
        suffix=(),
        moe=moe,
        ssm=ssm,
        xlstm=xl,
        encoder_layers=min(cfg.encoder_layers, 2),
        encoder_seq=32 if cfg.encoder_seq else 0,
        vision_tokens=16 if cfg.vision_tokens else 0,
        mrope_sections=(8, 12, 12) if cfg.mrope_sections else (),
        max_position=1 << 14,
        dtype="float32",
        param_dtype="float32",
        remat=False,
    )


def _reduced_with_prefix(cfg: ModelConfig) -> ModelConfig:
    """One leading dense layer and one pattern layer at small widths; MLA,
    YaRN and the MoE options (scores, gating, held share) kept."""
    mla = cfg.mla and MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32,
                                qk_rope_head_dim=16, v_head_dim=32)
    moe = cfg.moe and dataclasses.replace(
        cfg.moe, num_experts=4, top_k=min(2, cfg.moe.top_k), expert_ff=128,
        num_shared_experts=min(2, cfg.moe.num_shared_experts),
        padded_experts=0, experts_held=0, expert_offset=0)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-reduced",
        num_layers=2,
        d_model=256,
        num_heads=4,
        num_kv_heads=4,
        head_dim=mla.qk_head_dim if mla else 64,
        d_ff=512,
        vocab_size=512,
        prefix=cfg.prefix[:1],
        pattern=cfg.pattern[:1],
        repeats=1,
        suffix=(),
        moe=moe,
        mla=mla,
        max_position=1 << 14,
        dtype="float32",
        param_dtype="float32",
        remat=False,
    )
