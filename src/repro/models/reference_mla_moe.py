"""Plain float32 reference of a DeepSeek-V2 decoder (latent attention,
YaRN rope, leading dense layers, DeepSeekMoE with an optional held share),
for the CPU tests: token-by-token formulas in jax.numpy at
``Precision.HIGHEST``, no kernels, cache, absorption, sorting or scans.

    per layer:  x = RMSNorm(h)
                q_h = x Wq_h = q_nope_h ‖ q_pe_h
                c ‖ k_pe = x Wkv_a;  c = RMSNorm(c)
                k_nope_h ‖ v_h = c Wkv_b_h
                score_h(s, t) = (q_nope_h(s) . k_nope_h(t)
                                 + rope(q_pe_h)(s) . rope(k_pe)(t)) * scale
                h += sum_h softmax_t<=s(score_h) v_h Wo_h
                x = RMSNorm(h)
                h += dense SwiGLU(x)                       (leading layers)
                  or sum_{e held, e in topk(p)} w_e SwiGLU_e(x)
                     + shared SwiGLU(x) [* sigmoid gate]   (expert layers)

with p = softmax(x W_router) over every routed expert and w_e = p_e,
renormalised over the top k only when ``norm_topk_prob``, times
``routed_scaling_factor``. It reads the program's parameter tree and its
``ModelConfig``; the rope frequencies follow YaRN's published formulas,
written out here again.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _dot(spec, *xs):
    return jnp.einsum(spec, *[x.astype(jnp.float32) for x in xs], precision=HI)


def _rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + gain)


def _yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _inv_freq(d, theta, ys):
    inv = np.array([theta ** (-2.0 * i / d) for i in range(d // 2)])
    if ys is None:
        return inv, 1.0

    def corr(rot):
        return d * math.log(ys.original_max_position / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(corr(ys.beta_fast)), 0)
    hi = min(math.ceil(corr(ys.beta_slow)), d - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = np.array([min(max((i - lo) / (hi - lo), 0.0), 1.0)
                     for i in range(d // 2)])
    inv = inv * (1 - ramp) + inv / ys.factor * ramp
    cos_scale = (_yarn_mscale(ys.factor, ys.mscale)
                 / _yarn_mscale(ys.factor, ys.mscale_all_dim))
    return inv, cos_scale


def _rope(x, pos, inv, cos_scale):
    """x: (S, [H,] d) rotated by halves at positions ``pos`` (S,)."""
    ang = jnp.asarray(pos, jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang) * cos_scale, jnp.sin(ang) * cos_scale
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(x, p):
    return _dot("sf,fd->sd", jax.nn.silu(_dot("sd,df->sf", x, p["w_gate"]))
                * _dot("sd,df->sf", x, p["w_up"]), p["w_down"])


def attention(cfg, p, x, pos):
    """Expanded latent attention of one sequence x (S, D) at positions
    ``pos`` (S,); causal over that sequence."""
    m = cfg.mla
    r, nope = m.kv_lora_rank, m.qk_nope_head_dim
    inv, cs = _inv_freq(m.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling)
    q = _dot("sd,dhk->shk", x, p["wq"])
    kv = _dot("sd,dk->sk", x, p["wkv_a"])
    c = _rms(kv[:, :r], p["kv_norm"].astype(jnp.float32), cfg.norm_eps)
    k_pe = _rope(kv[:, r:], pos, inv, cs)                    # (S, rope)
    q_pe = _rope(q[..., nope:], pos, inv, cs)                # (S, H, rope)
    kvb = _dot("sr,rhk->shk", c, p["wkv_b"])
    scale = m.qk_head_dim ** -0.5
    if cfg.rope_scaling is not None:
        scale *= _yarn_mscale(cfg.rope_scaling.factor,
                              cfg.rope_scaling.mscale_all_dim) ** 2
    s = (_dot("shk,thk->hst", q[..., :nope], kvb[..., :nope])
         + _dot("shk,tk->hst", q_pe, k_pe)) * scale
    S = x.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = _dot("hst,thk->shk", jax.nn.softmax(s, -1), kvb[..., nope:])
    return _dot("shk,hkd->sd", o, p["wo"])


def moe_layer(cfg, p, x):
    """The held experts' weighted outputs plus the shared experts, for
    tokens x (S, D)."""
    mc = cfg.moe
    probs = jax.nn.softmax(_dot("sd,de->se", x, p["router"])[:, :mc.num_experts], -1)
    top = jnp.argsort(-probs, axis=-1)[:, :mc.top_k]
    chosen = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], top].set(1.0)
    w = probs * chosen
    if mc.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * mc.routed_scaling_factor
    y = jnp.zeros(x.shape, jnp.float32)
    for j in range(p["w_gate"].shape[0]):
        e = mc.expert_offset + j
        if e >= mc.num_experts:
            break
        ex = {k: p[k][j] for k in ("w_gate", "w_up", "w_down")}
        y = y + w[:, e:e + 1] * _swiglu(x, ex)
    if mc.num_shared_experts:
        sh = _swiglu(x, p["shared"])
        if mc.shared_gate:
            sh = sh * jax.nn.sigmoid(_dot("sd,dz->sz", x, p["shared_gate"]))
        y = y + sh
    return y


def _layers(cfg, params):
    """Each layer's params, in order."""
    out = [jax.tree.map(lambda a: a[n], params["prefix"])
           for n in range(len(cfg.prefix))]
    for rep in range(cfg.repeats):
        out += [jax.tree.map(lambda a: a[rep], params["pattern"][str(i)])
                for i in range(len(cfg.pattern))]
    out += [jax.tree.map(lambda a: a[n], params["suffix"])
            for n in range(len(cfg.suffix))]
    return out


def logits(cfg, params, tokens):
    """(B, S) tokens -> (B, S, V) float32 logits, one sequence at a time."""
    eps = cfg.norm_eps
    out = []
    for row in np.asarray(tokens):
        h = params["embedding"]["table"][row].astype(jnp.float32)
        pos = np.arange(len(row))
        for p in _layers(cfg, params):
            h = h + attention(cfg, p["attn"], _rms(h, p["ln1"]["scale"], eps), pos)
            x = _rms(h, p["ln2"]["scale"], eps)
            h = h + (moe_layer(cfg, p["moe"], x) if "moe" in p
                     else _swiglu(x, p["mlp"]))
        h = _rms(h, params["final_norm"]["scale"], eps)
        head = (params["embedding"]["table"].T if cfg.tie_embeddings
                else params["lm_head"])
        out.append(_dot("sd,dv->sv", h, head))
    return jnp.stack(out)
