"""Plain reference forward pass of a Qwen3 decoder (Qwen3 technical report,
arXiv:2505.09388; the published ``config.json``), in jax.numpy and
float32 at ``Precision.HIGHEST``, with no kernels, cache or batching
tricks. It imports nothing of the code under test.

    h = E[tokens]
    per layer:  x = RMSNorm(h) g1
                q, k, v = x Wq, x Wk, x Wv   (per head)
                q, k = RMSNorm_head(q) gq, RMSNorm_head(k) gk, then RoPE
                h += causal softmax(q k^T / sqrt(d)) v Wo   (GQA: query head
                                         i reads key/value head i // (H/K))
                x = RMSNorm(h) g2
                h += (silu(x Wgate) * (x Wup)) Wdown
    logits = (RMSNorm(h) g_f) E^T            (tied embeddings)

The weights are read from the nested dict the benchmark made them in:
``embedding.table``, ``final_norm.scale`` and, stacked over layers,
``pattern.0.{ln1,ln2}.scale``, ``attn.{wq,wk,wv,wo,q_norm,k_norm}``,
``mlp.{w_gate,w_up,w_down}``. Each RMSNorm gain is stored as its offset
from 1 (g = 1 + scale).

``precision="fp8"`` is the control: every matrix product takes its
inputs rounded to float8 e4m3 (activations scaled per row, weights per
tensor, into e4m3's range), the step below bfloat16 that a faster serving
path could take.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 after scaling its largest magnitude
    along ``axis`` (None: the whole tensor) to e4m3's largest value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, x, w, fp8: bool):
    """einsum ``spec`` of activations ``x`` (features last) and weight
    ``w``, in float32."""
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


def _rope(x, theta):
    """x: (B, S, H, d), positions 0..S-1; rotate halves."""
    S, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, q_block):
    """Causal GQA attention, one block of queries at a time.
    q: (B, S, H, d); k, v: (B, S, K, d)."""
    B, S, H, d = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    kpos = jnp.arange(S)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * q_block, q_block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HI) * d ** -0.5
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)

    out = lax.map(block, jnp.arange(S // q_block))   # (nb, B, qb, H, d)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, d)


@partial(jax.jit, static_argnames=("eps", "theta", "fp8", "q_block"))
def hidden(params, tokens, *, eps, theta, fp8=False, q_block=512):
    """Final normed hidden states (B, S, D) of ``tokens`` (B, S)."""
    h = params["embedding"]["table"][tokens].astype(jnp.float32)

    def layer(h, p):
        a = p["attn"]
        x = _rms(h, p["ln1"]["scale"], eps)
        q = _mm("bsd,dhk->bshk", x, a["wq"], fp8)
        k = _mm("bsd,dhk->bshk", x, a["wk"], fp8)
        v = _mm("bsd,dhk->bshk", x, a["wv"], fp8)
        q = _rope(_rms(q, a["q_norm"], eps), theta)
        k = _rope(_rms(k, a["k_norm"], eps), theta)
        o = _attention(q, k, v, q_block)
        h = h + _mm("bshk,hkd->bsd", o, a["wo"], fp8)
        x = _rms(h, p["ln2"]["scale"], eps)
        f = p["mlp"]
        g = jax.nn.silu(_mm("bsd,df->bsf", x, f["w_gate"], fp8))
        u = _mm("bsd,df->bsf", x, f["w_up"], fp8)
        return h + _mm("bsf,fd->bsd", g * u, f["w_down"], fp8), None

    h, _ = lax.scan(layer, h, params["pattern"]["0"])
    return _rms(h, params["final_norm"]["scale"], eps)


@partial(jax.jit, static_argnames=("fp8",))
def logit_stats(params, h, positions, lookup, *, fp8=False):
    """At ``positions`` (B, P) of hidden states ``h``: the best logit, the
    logit of token ``lookup`` (B, P), and the argmax token."""
    hp = jnp.take_along_axis(h, positions[..., None], axis=1)   # (B, P, D)
    logits = _mm("bpd,vd->bpv", hp, params["embedding"]["table"], fp8)
    at = jnp.take_along_axis(logits, lookup[..., None], axis=-1)[..., 0]
    return logits.max(-1), at, jnp.argmax(logits, -1).astype(jnp.int32)


def token_gaps(params, m: Dict, tokens, positions, served, block: int = 128,
               control: bool = False):
    """For each position (B, P): how far the token served there lies below
    the reference's best logit. With ``control``, the served token is
    replaced by the one the fp8 path would put first. Positions are
    processed ``block`` at a time so the (B, block, vocab) logits fit."""
    import numpy as np

    kw = dict(eps=float(m["rms_norm_eps"]), theta=float(m["rope_theta"]),
              q_block=min(512, tokens.shape[1]))
    h = hidden(params, tokens, **kw)
    lookup = served
    if control:
        hq = hidden(params, tokens, fp8=True, **kw)
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
