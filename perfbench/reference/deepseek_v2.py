"""Plain reference forward pass of a DeepSeek-V2 decoder (arXiv:2405.04434
§2.1-2.2 and its YaRN appendix; the published ``config.json``) for one
chip's share of an expert-parallel deployment, in jax.numpy and float32
at ``Precision.HIGHEST``, with no kernels, cache, absorption or batching
tricks. It imports nothing of the code under test.

    h = E[tokens]
    per layer:  x = RMSNorm(h) g1
                q = x Wq                       per head: q_nope ‖ q_pe
                c ‖ k_pe = x Wkv_a;  c = RMSNorm(c) gkv
                k_nope ‖ v = c Wkv_b           per head
                q_pe, k_pe = RoPE_yarn(q_pe), RoPE_yarn(k_pe)  (k_pe: one
                                               for all heads)
                h += causal softmax([q_nope q_pe][k_nope k_pe]^T s) v Wo
                x = RMSNorm(h) g2
                h += FFN(x)
    logits = (RMSNorm(h) g_f) W_head             (untied)

with s = (nope + rope)^-0.5 mscale(factor, mscale_all_dim)^2. FFN is a
SwiGLU of width ``intermediate_size`` in the first ``first_k_dense_replace``
layers; after them it is DeepSeekMoE: p = softmax(x W_router) over all
routed experts, the top ``num_experts_per_tok`` kept as they are
(``norm_topk_prob`` false) and scaled by ``routed_scaling_factor``, and

    FFN(x) = sum over held experts e in the top k of p_e SwiGLU_e(x)
             + SwiGLU_shared(x)

where the held experts are this chip's ``experts_held`` from
``expert_offset`` (the experts the other chips hold are theirs to add;
this chip's partial result is what goes on to its next layer).

Rope rotates halves of the rope dimensions (DeepSeek's checkpoint first
permutes them into interleaved pairs; with random weights that relabels
columns of Wq and Wkv_a).

The weights are read from the nested dict the benchmark made them in:
``embedding.table``, ``lm_head``, ``final_norm.scale`` and, stacked over
layers, ``prefix`` (the dense layers) and ``pattern.0`` (the expert
layers), each with ``ln1.scale``, ``ln2.scale``,
``attn.{wq,wkv_a,kv_norm,wkv_b,wo}`` and ``mlp.{w_gate,w_up,w_down}`` or
``moe.{router,w_gate,w_up,w_down,shared.{w_gate,w_up,w_down}}``. Each
RMSNorm gain is stored as its offset from 1. Weights stay in their stored
precision and are upcast one layer at a time, so the reference fits
beside them.

``fp8=True`` is the control: every matrix product takes its inputs
rounded to float8 e4m3 (activations scaled per row, weights per tensor),
the step below bfloat16 that a faster serving path could take.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, x, w, fp8: bool):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x = _fp8(x, -1)
        w = _fp8(w, None)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + scale.astype(jnp.float32))


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(d: int, theta: float, rs: Dict) -> np.ndarray:
    """YaRN's inverse frequencies for ``d`` rope dimensions."""
    base = 1.0 / theta ** (np.arange(0, d, 2) / d)
    L = rs["original_max_position_embeddings"]

    def dim_of(rot):
        return d * math.log(L / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(dim_of(rs["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    if lo == hi:
        hi += 0.001
    extrapolate = 1.0 - np.clip((np.arange(d // 2) - lo) / (hi - lo), 0, 1)
    return base / rs["factor"] * (1 - extrapolate) + base * extrapolate


def _rope(x, inv, cos_scale):
    """x: (B, S, H, d), positions 0..S-1; rotate halves."""
    S, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos = jnp.cos(ang)[None, :, None, :] * cos_scale
    sin = jnp.sin(ang)[None, :, None, :] * cos_scale
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale, q_block):
    """Causal attention, one block of queries at a time.
    q, k: (B, S, H, dq); v: (B, S, H, dv)."""
    B, S, H, _ = q.shape
    kpos = jnp.arange(S)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * q_block, q_block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HI) * scale
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)

    out = lax.map(block, jnp.arange(S // q_block))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, v.shape[-1])


def _swiglu(x, f, fp8):
    g = jax.nn.silu(_mm("...d,df->...f", x, f["w_gate"], fp8))
    u = _mm("...d,df->...f", x, f["w_up"], fp8)
    return _mm("...f,fd->...d", g * u, f["w_down"], fp8)


def _moe(x, p, top_k, scaling, norm_topk, offset, fp8):
    """The held experts' weighted outputs plus the shared experts."""
    probs = jax.nn.softmax(_mm("bsd,de->bse", x, p["router"], fp8), axis=-1)
    kth = lax.top_k(probs, top_k)[0][..., -1:]
    w = jnp.where(probs >= kth, probs, 0.0)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * scaling
    held = p["w_gate"].shape[0]

    def expert(y, e):
        f = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        return y + w[..., offset + e, None] * _swiglu(x, f, fp8), None

    y, _ = lax.scan(expert, jnp.zeros(x.shape, jnp.float32), jnp.arange(held))
    return y + _swiglu(x, p["shared"], fp8)


@partial(jax.jit, static_argnames=("cfg", "fp8", "q_block"))
def hidden(params, tokens, *, cfg, fp8=False, q_block=512):
    """Final normed hidden states (B, S, D) of ``tokens`` (B, S). ``cfg``:
    a hashable tuple of the settings (see ``settings``)."""
    c = dict(cfg)
    eps = c["eps"]
    nope, rope_d, r = c["nope"], c["rope"], c["rank"]
    inv = yarn_inv_freq(rope_d, c["theta"], dict(c["yarn"]))
    cos_scale = c["cos_scale"]
    h = params["embedding"]["table"][tokens].astype(jnp.float32)

    def layer(h, p, moe: bool):
        a = p["attn"]
        x = _rms(h, p["ln1"]["scale"], eps)
        q = _mm("bsd,dhk->bshk", x, a["wq"], fp8)
        kv = _mm("bsd,dk->bsk", x, a["wkv_a"], fp8)
        ckv = _rms(kv[..., :r], a["kv_norm"], eps)
        k_pe = _rope(kv[..., None, r:], inv, cos_scale)
        kvb = _mm("bsr,rhk->bshk", ckv, a["wkv_b"], fp8)
        H = q.shape[2]
        qf = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, cos_scale)], -1)
        kf = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
            k_pe, k_pe.shape[:2] + (H, rope_d))], -1)
        o = _attention(qf, kf, kvb[..., nope:], c["scale"], q_block)
        h = h + _mm("bshk,hkd->bsd", o, a["wo"], fp8)
        x = _rms(h, p["ln2"]["scale"], eps)
        if moe:
            return h + _moe(x, p["moe"], c["top_k"], c["scaling"],
                            c["norm_topk"], c["offset"], fp8), None
        return h + _swiglu(x, p["mlp"], fp8), None

    h, _ = lax.scan(partial(layer, moe=False), h, params["prefix"])
    h, _ = lax.scan(partial(layer, moe=True), h, params["pattern"]["0"])
    return _rms(h, params["final_norm"]["scale"], eps)


def settings(m: Dict, offset: int) -> tuple:
    """The reference's settings from the published config.json keys and
    the first expert held here."""
    rs = m["rope_scaling"]
    dq = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    ms = _mscale(rs["factor"], rs["mscale_all_dim"])
    yarn = tuple(sorted((k, rs[k]) for k in (
        "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow")))
    return tuple(sorted({
        "eps": float(m["rms_norm_eps"]), "theta": float(m["rope_theta"]),
        "nope": m["qk_nope_head_dim"], "rope": m["qk_rope_head_dim"],
        "rank": m["kv_lora_rank"], "yarn": yarn,
        "cos_scale": _mscale(rs["factor"], rs["mscale"]) / ms,
        "scale": dq ** -0.5 * ms * ms, "top_k": m["num_experts_per_tok"],
        "scaling": float(m["routed_scaling_factor"]),
        "norm_topk": bool(m["norm_topk_prob"]), "offset": offset,
    }.items()))


@partial(jax.jit, static_argnames=("fp8",))
def logit_stats(params, h, positions, lookup, *, fp8=False):
    """At ``positions`` (B, P) of hidden states ``h``: the best logit, the
    logit of token ``lookup`` (B, P), and the argmax token."""
    hp = jnp.take_along_axis(h, positions[..., None], axis=1)
    logits = _mm("bpd,dv->bpv", hp, params["lm_head"], fp8)
    at = jnp.take_along_axis(logits, lookup[..., None], axis=-1)[..., 0]
    return logits.max(-1), at, jnp.argmax(logits, -1).astype(jnp.int32)


def token_gaps(params, m: Dict, offset: int, tokens, positions, served,
               block: int = 128, control: bool = False):
    """For each position (B, P): how far the token served there lies below
    the reference's best logit. With ``control``, the served token is
    replaced by the one the fp8 path would put first."""
    cfg = settings(m, offset)
    q_block = min(512, tokens.shape[1])
    h = hidden(params, tokens, cfg=cfg, q_block=q_block)
    lookup = served
    if control:
        hq = hidden(params, tokens, cfg=cfg, fp8=True, q_block=q_block)
        lookup = np.concatenate([
            np.asarray(logit_stats(params, hq, positions[:, i:i + block],
                                   served[:, i:i + block], fp8=True)[2])
            for i in range(0, positions.shape[1], block)], axis=1)
        del hq
    gaps = []
    for i in range(0, positions.shape[1], block):
        best, at, _ = logit_stats(params, h, positions[:, i:i + block],
                                  lookup[:, i:i + block])
        gaps.append(np.asarray(best) - np.asarray(at))
    return np.concatenate(gaps, axis=1), lookup
