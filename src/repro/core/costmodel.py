"""Layer cost models.

Paper Eq. 5 (CNNs):
    Cost(l) = kh*kw*Cin*Cout   (Conv2D)
              Nin*Nout         (Linear)
              params_count     (others)

plus the transformer/MoE/SSM generalisation that the green partitioner and
the carbon monitor use for the assigned architectures: per-block parameter
counts, FLOPs and boundary-activation bytes.
"""
from __future__ import annotations

from typing import List

from repro.configs.base import CNNConfig, ConvLayerDef, LayerDef, ModelConfig


# ---------------------------------------------------------------------------
# Paper Eq. 5 — CNN layer cost
# ---------------------------------------------------------------------------


def cnn_layer_cost(layer: ConvLayerDef) -> float:
    if layer.kind == "conv":
        return float(layer.k * layer.k * layer.cin * layer.cout)
    if layer.kind == "dwconv":
        # Depthwise = Conv2D with Cout channels of 1-in-group: kh*kw*Cin.
        return float(layer.k * layer.k * layer.cin)
    if layer.kind == "linear":
        return float(layer.cin * layer.cout)
    if layer.kind == "se":
        return float(2 * layer.cin * layer.cout + layer.cin + layer.cout)  # params_count
    return 0.0  # pool / act: negligible ("others" with ~0 params)


def cnn_costs(cfg: CNNConfig) -> List[float]:
    return [cnn_layer_cost(layer) for layer in cfg.layers]


# ---------------------------------------------------------------------------
# Transformer block costs (generalisation for the assigned architectures)
# ---------------------------------------------------------------------------


def _attn_params(cfg: ModelConfig) -> int:
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    n = D * H * hd + 2 * D * K * hd + H * hd * D
    if cfg.qkv_bias:
        n += H * hd + 2 * K * hd
    if cfg.qk_norm:
        n += 2 * hd
    return n


def _mla_params(cfg: ModelConfig) -> int:
    D, H, m = cfg.d_model, cfg.num_heads, cfg.mla
    return (D * H * m.qk_head_dim + D * m.latent_dim + m.kv_lora_rank
            + m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
            + H * m.v_head_dim * D)


def _mlp_params(cfg: ModelConfig, d_ff: int, gated: bool = True) -> int:
    return cfg.d_model * d_ff * (3 if gated else 2)


def expert_params(cfg: ModelConfig) -> int:
    """One routed expert's SwiGLU weights."""
    return 3 * cfg.d_model * cfg.moe.expert_ff


def experts_held(cfg: ModelConfig) -> int:
    """Real (not padding) experts whose weights this chip holds."""
    m = cfg.moe
    return max(0, min(m.n_held, m.num_experts - m.expert_offset))


def routed_per_token(cfg: ModelConfig) -> float:
    """Expected held experts one token is routed to: top_k of the router's
    num_experts, scaled by the share held here."""
    m = cfg.moe
    return m.top_k * experts_held(cfg) / m.num_experts


def experts_touched(cfg: ModelConfig, tokens: int) -> float:
    """Expected held experts that at least one of ``tokens`` tokens is
    routed to (each token picks top_k of num_experts)."""
    m = cfg.moe
    return experts_held(cfg) * (1.0 - (1.0 - m.top_k / m.num_experts) ** tokens)


def _moe_params(cfg: ModelConfig, active_only: bool = False) -> float:
    m = cfg.moe
    e = routed_per_token(cfg) if active_only else experts_held(cfg)
    n = e * expert_params(cfg) + cfg.d_model * m.num_experts
    if m.num_shared_experts:
        n += _mlp_params(cfg, m.num_shared_experts * m.expert_ff)
        if m.shared_gate:
            n += cfg.d_model
    if m.dense_residual_ff:
        n += _mlp_params(cfg, m.dense_residual_ff)
    return n


def _ffn_params(cfg: ModelConfig, ld: LayerDef, active_only: bool) -> float:
    if cfg.is_moe_layer(ld):
        return _moe_params(cfg, active_only)
    if cfg.d_ff > 0:
        return _mlp_params(cfg, cfg.d_ff, cfg.mlp_gated)
    return 0


def _mamba2_params(cfg: ModelConfig) -> int:
    from repro.models import ssm

    inner, H, conv_dim = ssm.dims(cfg)
    s = cfg.ssm
    proj_out = 2 * inner + 2 * s.num_groups * s.state_dim + H
    return (cfg.d_model * proj_out + s.conv_width * conv_dim + conv_dim
            + 3 * H + inner + inner * cfg.d_model)


def _mlstm_params(cfg: ModelConfig) -> int:
    from repro.models import xlstm

    inner, H, hd = xlstm.mlstm_dims(cfg)
    return (cfg.d_model * 2 * inner + cfg.xlstm.conv_width * inner + inner
            + 3 * inner * inner + 2 * inner * H + 2 * H + inner
            + inner * cfg.d_model)


def _slstm_params(cfg: ModelConfig) -> int:
    from repro.models import xlstm

    H, hd = xlstm.slstm_dims(cfg)
    D = cfg.d_model
    ff = int(cfg.xlstm.slstm_proj_factor * D)
    gates = 4 * (D * H * hd + H * hd * hd + H * hd)
    return gates + D + 3 * D * ff


def block_params(cfg: ModelConfig, ld: LayerDef, active_only: bool = False) -> float:
    """Parameters of one block; ``active_only``: those one token multiplies
    through (an expert layer's routed experts in expectation over the
    experts held here)."""
    D = cfg.d_model
    if ld.kind == "attn":
        n = _attn_params(cfg) + 2 * D  # + norms
        if cfg.cross_attention:
            n += _attn_params(cfg) + D
        return n + _ffn_params(cfg, ld, active_only)
    if ld.kind == "mla":
        return _mla_params(cfg) + 2 * D + _ffn_params(cfg, ld, active_only)
    if ld.kind == "mamba2":
        return _mamba2_params(cfg) + D
    if ld.kind == "mlstm":
        return _mlstm_params(cfg) + D
    if ld.kind == "slstm":
        return _slstm_params(cfg) + D
    raise ValueError(ld.kind)


def model_param_count(cfg: ModelConfig) -> int:
    n = cfg.vocab_size * cfg.d_model  # embedding
    if not cfg.tie_embeddings:
        n += cfg.d_model * cfg.vocab_size
    n += sum(block_params(cfg, ld) for ld in cfg.layer_defs)
    if cfg.encoder_layers:
        n += cfg.encoder_layers * (_attn_params(cfg)
                                   + _mlp_params(cfg, cfg.d_ff, cfg.mlp_gated)
                                   + 2 * cfg.d_model)
    return int(round(n))


def model_active_param_count(cfg: ModelConfig) -> int:
    """Parameters one token multiplies through on this chip: everything
    outside the routed experts in full, and the routed experts held here in
    expectation (top_k of num_experts, times the held share)."""
    n = cfg.vocab_size * cfg.d_model
    if not cfg.tie_embeddings:
        n += cfg.d_model * cfg.vocab_size
    n += sum(block_params(cfg, ld, active_only=True) for ld in cfg.layer_defs)
    if cfg.encoder_layers:
        n += cfg.encoder_layers * (_attn_params(cfg)
                                   + _mlp_params(cfg, cfg.d_ff, cfg.mlp_gated)
                                   + 2 * cfg.d_model)
    return int(round(n))


def block_flops(cfg: ModelConfig, ld: LayerDef, seq: int, batch: int,
                kind: str = "fwd", kv_len: int = 0) -> float:
    """Approximate forward FLOPs per block.

    kind: "fwd" (full sequence) or "decode" (one token, cache kv_len).
    Matmul FLOPs = 2*m*n*k; attention quadratic term included (window-aware).
    """
    tokens = batch * (1 if kind == "decode" else seq)
    f = 2.0 * tokens * block_params(cfg, ld, active_only=True)
    if ld.kind == "mla":
        m = cfg.mla
        if kind == "decode":   # absorbed: scores over the latent, values over c
            f += 2.0 * batch * cfg.num_heads * kv_len * (m.latent_dim + m.kv_lora_rank)
        else:                  # expanded, causal
            f += (2.0 * batch * cfg.num_heads * (m.qk_head_dim + m.v_head_dim)
                  * seq * seq / 2.0)
    elif ld.kind == "attn":
        ctx = kv_len if kind == "decode" else seq
        if ld.window is not None:
            ctx = min(ctx, ld.window)
        if kind == "decode":
            f += 4.0 * batch * cfg.num_heads * cfg.head_dim * ctx
        else:
            # causal: ~S*ctx/2 scores per head
            eff = ctx if ld.window is not None else seq / 2.0
            f += 4.0 * batch * cfg.num_heads * cfg.head_dim * seq * eff
    elif ld.kind == "mamba2":
        s = cfg.ssm
        inner, H, _ = __import__("repro.models.ssm", fromlist=["dims"]).dims(cfg)
        L = s.chunk_size if kind != "decode" else 1
        f += 2.0 * tokens * H * (L * s.state_dim + 2 * s.state_dim * s.head_dim)
    elif ld.kind == "mlstm":
        from repro.models import xlstm

        inner, H, hd = xlstm.mlstm_dims(cfg)
        ctx = 1 if kind == "decode" else seq / 2.0
        f += 4.0 * tokens * H * hd * ctx if kind != "decode" else 4.0 * batch * H * hd * hd
    return f


def boundary_bytes(cfg: ModelConfig, seq: int, batch: int, dtype_bytes: int = 2) -> int:
    """Activation bytes crossing a partition boundary between blocks."""
    return batch * seq * cfg.d_model * dtype_bytes


# ---------------------------------------------------------------------------
# Analytic HBM traffic model (TPU-fused pipeline)
#
# The CPU-backend cost_analysis() reports *unfused* bytes — every convert /
# broadcast / multiply billed at full tensor size — which overstates HBM
# traffic by ~10-30x vs a fused TPU pipeline. The roofline memory term
# therefore uses this structural model: weights + optimizer traffic,
# fusion-boundary activation tensors, KV/state cache traffic. The HLO
# number is kept alongside as an upper bound.
# ---------------------------------------------------------------------------

_ACT_B = 2          # bf16 activations
_F32_B = 4
_Q_BLOCK = 1024     # attention kv re-read granularity (flash q-block)


def _block_act_bytes(cfg: ModelConfig, ld: LayerDef, tokens: int, seq: int,
                     kind: str) -> float:
    """Fusion-boundary activation traffic (read+write) for one block, fwd."""
    D = cfg.d_model
    b = 0.0
    rw = 2 * _ACT_B  # write + read back
    if ld.kind == "attn":
        H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        b += tokens * D * rw * 2                 # block in/out residual
        b += tokens * (H + 2 * K) * hd * rw      # q, k, v
        b += tokens * H * hd * rw                # attn out pre-proj
        if kind != "decode":
            ctx = seq if ld.window is None else min(seq, ld.window)
            nb = max(1, seq // _Q_BLOCK)
            b += nb * tokens / max(seq, 1) * ctx * 2 * K * hd * _ACT_B  # kv re-reads
        b += _ffn_act_bytes(cfg, ld, tokens)
    elif ld.kind == "mla":
        H, m = cfg.num_heads, cfg.mla
        b += tokens * D * rw * 2                 # block in/out residual
        b += tokens * (H * m.qk_head_dim + m.latent_dim) * rw   # q, latent
        b += tokens * H * m.v_head_dim * rw      # attn out pre-proj
        if kind != "decode":                     # per-head k, v expanded
            b += tokens * H * (m.qk_head_dim + m.v_head_dim) * rw
        b += _ffn_act_bytes(cfg, ld, tokens)
    elif ld.kind == "mamba2":
        from repro.models import ssm as ssm_mod

        inner, H, conv_dim = ssm_mod.dims(cfg)
        s = cfg.ssm
        b += tokens * D * rw * 2
        b += tokens * (2 * inner + conv_dim) * rw
        if kind != "decode":
            nc = max(1, seq // s.chunk_size)
            b += (tokens / max(seq, 1)) * nc * H * s.state_dim * s.head_dim * _F32_B * 2
    elif ld.kind == "mlstm":
        from repro.models import xlstm as xl

        inner, H, hd = xl.mlstm_dims(cfg)
        b += tokens * D * rw * 2
        b += tokens * inner * rw * 5              # x_m, z, q, k, v
    elif ld.kind == "slstm":
        H, hd = 0, 0
        ff = int(cfg.xlstm.slstm_proj_factor * D)
        b += tokens * D * rw * 2
        b += tokens * D * 4 * rw                  # gate pre-activations
        b += tokens * ff * rw * 2
    return b


def _ffn_act_bytes(cfg: ModelConfig, ld: LayerDef, tokens: int) -> float:
    rw = 2 * _ACT_B
    if cfg.is_moe_layer(ld):
        m = cfg.moe
        cap = tokens * m.top_k * 1.25
        b = cap * cfg.d_model * rw * 2           # grouped in/out buffers
        b += cap * m.expert_ff * rw              # expert hidden
        if m.num_shared_experts:
            b += tokens * m.num_shared_experts * m.expert_ff * rw
        if m.dense_residual_ff:
            b += tokens * m.dense_residual_ff * rw
        return b
    if cfg.d_ff > 0:
        return tokens * cfg.d_ff * rw * (2 if cfg.mlp_gated else 1)
    return 0.0


def _cache_bytes(cfg: ModelConfig, seq: int, batch: int) -> float:
    """KV/state cache read+write traffic for one decode step."""
    total = 0.0
    for ld in cfg.layer_defs:
        if ld.kind == "attn":
            ctx = seq if ld.window is None else min(seq, ld.window)
            total += batch * ctx * 2 * cfg.num_kv_heads * cfg.head_dim * _ACT_B
            if cfg.cross_attention:
                total += batch * cfg.encoder_seq * 2 * cfg.num_kv_heads * cfg.head_dim * _ACT_B
        elif ld.kind == "mla":
            total += batch * seq * cfg.mla.latent_dim * _ACT_B
        elif ld.kind == "mamba2":
            from repro.models import ssm as ssm_mod

            inner, H, conv_dim = ssm_mod.dims(cfg)
            total += 2 * batch * H * cfg.ssm.state_dim * cfg.ssm.head_dim * _F32_B
            total += 2 * batch * (cfg.ssm.conv_width - 1) * conv_dim * _ACT_B
        elif ld.kind == "mlstm":
            from repro.models import xlstm as xl

            inner, H, hd = xl.mlstm_dims(cfg)
            total += 2 * batch * H * hd * hd * _F32_B
        elif ld.kind == "slstm":
            H, hd = cfg.xlstm.num_heads, cfg.d_model // cfg.xlstm.num_heads
            total += 8 * batch * H * hd * _F32_B
    return total


def step_hbm_bytes(cfg: ModelConfig, seq: int, batch: int, kind: str) -> float:
    """Whole-step analytic HBM bytes (global, all chips combined). In
    serving, an expert layer reads the held experts that the step's tokens
    touch, not one token's share."""
    p_act = model_active_param_count(cfg)
    tokens = batch * (1 if kind == "decode" else seq)
    wb = _ACT_B * p_act
    if cfg.moe is not None and kind != "train":
        n_moe = sum(cfg.is_moe_layer(ld) for ld in cfg.layer_defs)
        wb += _ACT_B * n_moe * expert_params(cfg) * (
            experts_touched(cfg, tokens) - routed_per_token(cfg))
    act = sum(_block_act_bytes(cfg, ld, tokens, seq, kind)
              for ld in cfg.layer_defs)
    if cfg.encoder_layers and kind != "decode":
        enc_tokens = batch * cfg.encoder_seq
        from repro.configs.base import LayerDef as LD

        act += cfg.encoder_layers * _block_act_bytes(
            cfg, LD("attn"), enc_tokens, cfg.encoder_seq, kind)
    # lm head / loss logits traffic (chunked: logits written+read once)
    logits = tokens * cfg.vocab_size * _F32_B if kind == "train" else \
        batch * cfg.vocab_size * _F32_B
    if kind == "train":
        p_tot = model_param_count(cfg)
        # fwd + remat + bwd weight reads, grad write/read, AdamW f32 state r/w
        # + f32 master-param r/w.
        weight_traffic = 3 * wb + 2 * wb + 4 * _F32_B * p_tot + 2 * _F32_B * p_tot
        return weight_traffic + 3 * act + 2 * logits
    if kind == "prefill":
        return wb + act + logits + _cache_bytes(cfg, seq, batch)  # cache write
    # decode
    return wb + act + logits + _cache_bytes(cfg, seq, batch)
