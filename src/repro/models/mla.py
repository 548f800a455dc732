"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1), with
no low-rank query projection:

    q            = x W_q                          H x (nope ‖ rope)
    c ‖ k_pe     = x W_kv_a                       rank ‖ rope
    c            = RMSNorm(c) g_kv
    k_nope ‖ v   = c W_kv_b                       H x (nope ‖ v)
    k            = k_nope ‖ rope(k_pe)            (k_pe shared by all heads)
    q            = q_nope ‖ rope(q_pe)
    y            = softmax(q k^T s) v W_o,        s = (nope + rope)^-0.5 mscale^2

The cache keeps one (rank + rope)-wide latent per position: c after its
norm, then the roped k_pe. Prefill and the full forward run expanded
attention (per-head k and v from the latent, ``flash_attention`` on the
chip). Decode absorbs W_uk (the k_nope columns of W_kv_b) into the query
and W_uv (the v columns) into the output, so it attends over the latent
cache directly (``mla_decode_attention``).

Departure from the published code: rope rotates halves of the 64 rope
dimensions, where DeepSeek's checkpoint first permutes them into
interleaved pairs; with random weights that only relabels columns of
W_q and W_kv_a.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, common
from repro.models.common import ParamSpec


def mla_spec(cfg: ModelConfig) -> Dict:
    D, H, m = cfg.d_model, cfg.num_heads, cfg.mla
    return {
        "wq": ParamSpec((D, H, m.qk_head_dim), ("embed", "heads", "head_dim")),
        "wkv_a": ParamSpec((D, m.latent_dim), ("embed", "kv_latent")),
        "kv_norm": ParamSpec((m.kv_lora_rank,), ("norm",), "zeros"),
        "wkv_b": ParamSpec((m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim),
                           ("kv_latent", "heads", "head_dim")),
        "wo": ParamSpec((H, m.v_head_dim, D), ("heads", "head_dim", "embed")),
    }


def softmax_scale(cfg: ModelConfig) -> float:
    s = cfg.mla.qk_head_dim ** -0.5
    if cfg.rope_scaling is not None:
        ms = common.yarn_mscale(cfg.rope_scaling.factor,
                                cfg.rope_scaling.mscale_all_dim)
        s *= ms * ms
    return s


def _project(cfg: ModelConfig, p, x, positions):
    """x: (B,S,D) -> q_nope (B,S,H,nope), roped q_pe (B,S,H,rope) and the
    latent to cache (B,S,rank+rope)."""
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope = q[..., :m.qk_nope_head_dim]
    q_pe = common.apply_rope(q[..., m.qk_nope_head_dim:], positions,
                             cfg.rope_theta, cfg.rope_scaling)
    kv = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"])
    c = common.rms_norm(kv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_pe = common.apply_rope(kv[..., None, m.kv_lora_rank:], positions,
                             cfg.rope_theta, cfg.rope_scaling)[:, :, 0]
    return q_nope, q_pe, jnp.concatenate([c, k_pe], axis=-1)


def _expanded(cfg: ModelConfig, p, x, positions):
    """Full-sequence causal attention with per-head k and v expanded from
    the latent. Returns (y (B,S,D), latent (B,S,rank+rope))."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_pe, latent = _project(cfg, p, x, positions)
    kvb = jnp.einsum("bsr,rhk->bshk", latent[..., :m.kv_lora_rank], p["wkv_b"])
    k_pe = jnp.broadcast_to(latent[:, :, None, m.kv_lora_rank:],
                            (B, S, H, m.qk_rope_head_dim))
    k = jnp.concatenate([kvb[..., :m.qk_nope_head_dim], k_pe], axis=-1)
    v = kvb[..., m.qk_nope_head_dim:]
    # the attention paths scale scores by qk_head_dim^-0.5; YaRN's mscale^2
    # goes into the query
    gain = softmax_scale(cfg) * m.qk_head_dim ** 0.5
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    q = (q.astype(jnp.float32) * gain).astype(x.dtype)
    out = attention.chunked_attention(cfg, q, k, v, causal=True, window=None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), latent


def mla_forward(cfg: ModelConfig, p, x, positions=None):
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    return _expanded(cfg, p, x, positions)[0]


def mla_prefill(cfg: ModelConfig, p, x, cache_len: int, positions=None):
    """Returns (y, latent cache (B, cache_len, rank+rope))."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    y, latent = _expanded(cfg, p, x, positions)
    cache = jnp.zeros((B, cache_len, latent.shape[-1]), latent.dtype)
    return y, cache.at[:, :S].set(latent)


def mla_decode(cfg: ModelConfig, p, x, cache, pos, layer=None):
    """x: (B,1,D); cache: (B,Smax,rank+rope), or with ``layer`` (a scalar
    int32) the (L,B,Smax,rank+rope) stack over layers, read and written at
    that layer in place; pos: scalar int32. Absorbed attention over the
    latent cache; returns (y, new cache)."""
    from repro.kernels import ops
    from repro.kernels.decode_attention import BLOCK_K
    from repro.sharding.constraints import _current_mesh

    m = cfg.mla
    B, Smax, _ = cache.shape[-3:]
    nope, r = m.qk_nope_head_dim, m.kv_lora_rank
    q_nope, q_pe, latent = _project(cfg, p, x, jnp.full((B, 1), pos))
    if layer is None:
        cache = jax.lax.dynamic_update_slice_in_dim(
            cache, latent.astype(cache.dtype), pos, axis=1)
    else:
        cache = jax.lax.dynamic_update_slice(
            cache, latent[None].astype(cache.dtype), (layer, 0, pos, 0))
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], p["wkv_b"][..., :nope],
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_lat, q_pe[:, 0].astype(jnp.float32)], axis=-1)
    scale = softmax_scale(cfg)
    if ops.use_pallas() and _current_mesh() is None and Smax % BLOCK_K == 0:
        o_lat = ops.mla_decode_attention(q, cache, pos, layer, scale=scale,
                                         rank=r)
    else:
        lat = cache if layer is None else \
            jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
        o_lat = ops.mla_decode_attention_ref(q, lat, pos, scale=scale, rank=r)
    o = jnp.einsum("bhr,rhk->bhk", o_lat, p["wkv_b"][..., nope:])
    return jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None], cache
