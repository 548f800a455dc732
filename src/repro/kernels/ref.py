"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None, softcap: float = 0.0):
    """q: (B,H,Sq,hd); k/v: (B,K,Sk,hd). Plain softmax attention."""
    B, H, Sq, hd = q.shape
    K, Sk = k.shape[1], k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=1)
        v = jnp.repeat(v, H // K, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (hd ** -0.5)
    if softcap and softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    qi = jnp.arange(Sq)[:, None]
    ki = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(q, k, v, pos, *, window: Optional[int] = None,
                         softcap: float = 0.0):
    """q: (B,H,hd); k/v: (B,S,K,hd), the decode cache's layout; pos
    scalar."""
    B, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (hd ** -0.5)
    if softcap and softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    ki = jnp.arange(S)
    mask = ki <= pos
    if window is not None:
        mask &= ki > pos - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32)).astype(q.dtype)


def mla_decode_attention_ref(q, cache, pos, *, scale: float, rank: int):
    """q: (B,H,W) absorbed queries; cache: (B,S,W); pos scalar. Softmax
    over the positions up to ``pos`` of q . cache, weighting the first
    ``rank`` columns of the cache."""
    c = cache.astype(jnp.float32)
    s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32), c) * scale
    s = jnp.where((jnp.arange(cache.shape[1]) <= pos)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btr->bhr", p, c[..., :rank]).astype(cache.dtype)


def moe_gmm_ref(x, w_gate, w_up, w_down, sizes, act=jax.nn.silu):
    """Grouped gated MLP by ``ragged_dot``: rows [sum(sizes[:e]), +sizes[e])
    through expert e; rows past sum(sizes) give zeros."""
    g = jax.lax.ragged_dot(x, w_gate, sizes)
    u = jax.lax.ragged_dot(x, w_up, sizes)
    h = (act(g) * u).astype(x.dtype)
    return jax.lax.ragged_dot(h, w_down, sizes)


def mamba2_chunk_ref(xdt, Bh, Ch, cum, state):
    """Sequential within-chunk recurrence (the ground truth).

    xdt: (B,H,L,P); Bh/Ch: (B,H,L,N); cum: (B,H,L); state: (B,H,N,P).
    """
    B, H, L, P = xdt.shape
    dA = jnp.diff(jnp.concatenate(
        [jnp.zeros(cum.shape[:-1] + (1,), cum.dtype), cum], axis=-1), axis=-1)

    def step(s, t):
        a = jnp.exp(dA[:, :, t])[..., None, None]              # (B,H,1,1)
        upd = jnp.einsum("bhn,bhp->bhnp", Bh[:, :, t].astype(jnp.float32),
                         xdt[:, :, t].astype(jnp.float32))
        s = a * s + upd
        y = jnp.einsum("bhn,bhnp->bhp", Ch[:, :, t].astype(jnp.float32), s)
        return s, y

    s, ys = jax.lax.scan(step, state.astype(jnp.float32), jnp.arange(L))
    y = jnp.moveaxis(ys, 0, 2).astype(xdt.dtype)               # (B,H,L,P)
    return y, s


def node_scores_ref(features, weights):
    """features: (N, 8); weights: (8,) -> (N,). Mirrors core/scheduler."""
    f = features.astype(jnp.float32)
    s_r = 0.5 * jnp.minimum(f[:, 0], 1.0) + 0.5 * jnp.minimum(f[:, 1], 1.0)
    s_l = 1.0 - f[:, 2]
    s_p = 1.0 / (1.0 + f[:, 3])
    s_b = 1.0 / (1.0 + 2.0 * f[:, 4])
    s_c = 1.0 / (1.0 + f[:, 5])
    total = (weights[0] * s_r + weights[1] * s_l + weights[2] * s_p
             + weights[3] * s_b + weights[4] * s_c)
    return jnp.where(f[:, 6] > 0.5, total, NEG_INF)


def node_scores_batched_ref(features, weights):
    """features: (B, N, 8); weights: (8,) -> (B, N)."""
    return jax.vmap(node_scores_ref, in_axes=(0, None))(features, weights)
