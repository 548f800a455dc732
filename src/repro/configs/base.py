"""Config dataclasses for the repro framework.

Every assigned architecture is expressed as a ``ModelConfig``; the paper's
own CNN test models are ``CNNConfig``. Configs are frozen dataclasses so
they are hashable and usable as jit static args.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""

    num_experts: int
    top_k: int
    expert_ff: int
    # qwen2-moe style always-on shared experts (implemented as one fused MLP
    # of width num_shared_experts * expert_ff).
    num_shared_experts: int = 0
    # arctic style dense residual MLP running in parallel with the MoE.
    dense_residual_ff: int = 0
    router_aux_weight: float = 0.01
    router_jitter: float = 0.0
    # Expert-parallel padding: expert weight arrays are padded to this count
    # so the expert axis divides the `model` mesh axis (padded experts are
    # router-masked and unreachable — pure deployment layout, no semantic
    # change). 0 = num_experts.
    padded_experts: int = 0
    # DeepSeekMoE options: renormalise the top-k weights (qwen2-moe) or
    # keep the softmax scores (DeepSeek-V2, ``norm_topk_prob`` false);
    # scale the routed sum; put the shared experts behind a sigmoid gate.
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    shared_gate: bool = True
    # Expert parallelism without the mesh: this chip holds experts
    # [expert_offset, expert_offset + experts_held) of ``num_experts``; the
    # router still scores all of them and the layer adds only the held
    # experts' part (plus the shared experts). 0 = all of ``e_pad``.
    experts_held: int = 0
    expert_offset: int = 0

    @property
    def e_pad(self) -> int:
        return max(self.num_experts, self.padded_experts)

    @property
    def n_held(self) -> int:
        """Experts whose weights this chip holds (the expert axis of the
        weight arrays)."""
        return self.experts_held or self.e_pad


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1)
    without a query low-rank projection: keys and values come from a
    ``kv_lora_rank`` latent plus one ``qk_rope_head_dim`` rope key shared
    by all heads."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Width of one cached position: the normed latent and the roped
        shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling (arXiv:2309.00071; DeepSeek-V2's appendix)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block configuration."""

    state_dim: int = 64          # N
    head_dim: int = 64           # P
    expand: int = 2              # inner = expand * d_model
    conv_width: int = 4
    chunk_size: int = 256
    num_groups: int = 1          # B/C groups (GVA)


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block configuration (mLSTM + sLSTM cells)."""

    num_heads: int = 4
    conv_width: int = 4
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 4.0 / 3.0


@dataclass(frozen=True)
class LayerDef:
    """One layer in the stack pattern.

    kind: "attn" | "mla" | "mamba2" | "mlstm" | "slstm"
    window: sliding-window size for attention layers (None = global/full).
    dense: in a model with experts, this layer's feed-forward is the dense
    SwiGLU of width ``d_ff`` instead (DeepSeek's leading dense layers).
    """

    kind: str = "attn"
    window: Optional[int] = None
    dense: bool = False


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str               # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 -> d_model // num_heads

    # Layer stack: `prefix`, then `pattern` repeated `repeats` times, then
    # `suffix`. len(prefix) + len(pattern) * repeats + len(suffix) must
    # equal num_layers. Each of prefix and suffix is one kind of layer.
    pattern: Tuple[LayerDef, ...] = (LayerDef("attn"),)
    repeats: int = 0             # 0 -> the rest (pattern must be length 1)
    suffix: Tuple[LayerDef, ...] = ()
    prefix: Tuple[LayerDef, ...] = ()

    # Attention details.
    qkv_bias: bool = False
    qk_norm: bool = False
    attn_logit_softcap: float = 0.0
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnScaling] = None
    pos_emb: str = "rope"        # rope | learned | none
    mrope_sections: Tuple[int, ...] = ()   # qwen2-vl M-RoPE (sums to head_dim/2)
    max_position: int = 1 << 20  # for learned pos-emb sizing

    # Sub-blocks.
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None

    # Encoder-decoder (whisper): encoder consumes stub frame embeddings.
    encoder_layers: int = 0
    encoder_seq: int = 0
    cross_attention: bool = False

    # VLM: stub patch embeddings prepended to the token sequence.
    vision_tokens: int = 0

    # Norm / activation / misc.
    norm_type: str = "rmsnorm"   # rmsnorm | layernorm
    act: str = "silu"            # silu | gelu
    mlp_gated: bool = True       # SwiGLU-style gated MLP
    tie_embeddings: bool = False
    norm_eps: float = 1e-6

    # Numerics / runtime.
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    remat: bool = True

    # Source citation for the assigned-architecture pool.
    source: str = ""

    def __post_init__(self):
        # Resolve head_dim.
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        # Resolve repeats.
        if self.repeats == 0:
            if len(self.pattern) != 1:
                raise ValueError(f"{self.name}: repeats=0 needs len(pattern)==1")
            object.__setattr__(self, "repeats", self.num_layers
                               - len(self.prefix) - len(self.suffix))
        n = len(self.prefix) + len(self.pattern) * self.repeats + len(self.suffix)
        if n != self.num_layers:
            raise ValueError(
                f"{self.name}: prefix+pattern*repeats+suffix = {n} != "
                f"num_layers {self.num_layers}"
            )
        if self.num_heads % max(self.num_kv_heads, 1):
            raise ValueError(f"{self.name}: heads {self.num_heads} not divisible "
                             f"by kv heads {self.num_kv_heads}")
        if self.mrope_sections and sum(self.mrope_sections) != self.head_dim // 2:
            raise ValueError(f"{self.name}: mrope sections must sum to head_dim/2")

    # -- derived ----------------------------------------------------------
    @property
    def layer_defs(self) -> Tuple[LayerDef, ...]:
        return self.prefix + self.pattern * self.repeats + self.suffix

    def is_moe_layer(self, ld: LayerDef) -> bool:
        """Whether layer ``ld``'s feed-forward is the expert layer."""
        return self.moe is not None and ld.kind in ("attn", "mla") and not ld.dense

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    def with_overrides(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def with_attention_window(self, window: int) -> "ModelConfig":
        """SWA override used by the long_500k variant for full-attention archs."""

        def w(ld: LayerDef) -> LayerDef:
            if ld.kind != "attn":
                return ld
            if ld.window is not None and ld.window <= window:
                return ld
            return dataclasses.replace(ld, window=window)

        return dataclasses.replace(
            self,
            pattern=tuple(w(ld) for ld in self.pattern),
            suffix=tuple(w(ld) for ld in self.suffix),
            prefix=tuple(w(ld) for ld in self.prefix),
        )

    # -- parameter counting (analytic; used by partitioner & roofline) ----
    def param_count(self) -> int:
        from repro.core.costmodel import model_param_count

        return model_param_count(self)

    def active_param_count(self) -> int:
        from repro.core.costmodel import model_active_param_count

        return model_active_param_count(self)


# ---------------------------------------------------------------------------
# CNN config (the paper's own test models)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ConvLayerDef:
    """One CNN layer; drives both the model and the paper's Eq.5 cost model.

    kind: conv | dwconv | linear | pool | act | bn
    """

    kind: str
    cin: int = 0
    cout: int = 0
    k: int = 1
    stride: int = 1


@dataclass(frozen=True)
class CNNConfig:
    name: str
    layers: Tuple[ConvLayerDef, ...]
    num_classes: int = 1000
    input_size: int = 224
    input_channels: int = 3
    source: str = ""

    def with_overrides(self, **kw) -> "CNNConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}
