"""Flash attention forward — Pallas TPU kernel.

Online-softmax blocked attention: grid (batch, q_heads, q_blocks,
kv_blocks) with VMEM scratch accumulators carried across the innermost
(arbitrary) kv dimension. GQA is handled in the KV index_map (kv head =
q_head // group) so KV is never materialised per-q-head. Causal and
sliding-window masks are applied from block offsets; Gemma-style logit
softcap supported.

Block shapes default to (128, 128) — MXU-aligned, and the working set
(q, k, v, scores, acc ≈ 6 * 128 * head_dim * 4B) stays well under VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: float, bq: int, bk: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)
    if softcap and softcap > 0:
        s = jnp.tanh(s / softcap) * softcap

    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                         # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        lsum = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / lsum).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, softcap: float = 0.0,
                    bq: int = 128, bk: int = 128, interpret: bool = False):
    """q/k: (B, H|K, S, hd); v: (B, K, Sk, hdv) with H % K == 0. The value
    head may be narrower than the query/key head (MLA: 192 and 128).

    Returns (B, H, Sq, hdv). Sq/Sk must be multiples of bq/bk.
    """
    B, H, Sq, hd = q.shape
    K, Sk = k.shape[1], k.shape[2]
    hdv = v.shape[3]
    g = H // K
    nq, nk = Sq // bq, Sk // bk
    scale = hd ** -0.5

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, hdv), lambda b, h, iq, ik: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hdv), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hdv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hdv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
