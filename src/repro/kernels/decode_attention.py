"""Single-token decode attention over a long KV cache — Pallas TPU kernel.

One query token per (batch, head); the KV cache is streamed through VMEM in
bk-sized blocks along the innermost (arbitrary) grid dimension with a
running log-sum-exp. Positions > `pos` (and, with a window, positions
<= pos - window) are masked, so the cache may be over-allocated
(decode_32k / long_500k shapes).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# KV positions streamed per grid step; a cache length must be a multiple.
BLOCK_K = 256


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, window: Optional[int], softcap: float,
            bk: int, nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)            # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (1, bk)
    if softcap and softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = cols <= pos
    if window is not None:
        mask &= cols > pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        lsum = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / lsum).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "bk", "interpret"))
def decode_attention(q, k, v, pos, *, window: Optional[int] = None,
                     softcap: float = 0.0, bk: int = BLOCK_K,
                     interpret: bool = False):
    """q: (B, H, hd); k/v: (B, K, S, hd); pos: scalar int32.

    Returns (B, H, hd). S must be a multiple of bk.
    """
    B, H, hd = q.shape
    K, S = k.shape[1], k.shape[2]
    g = H // K
    nk = S // bk
    scale = hd ** -0.5
    q4 = q[:, :, None, :]                          # (B,H,1,hd)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(_kernel, scale=scale, window=window,
                               softcap=softcap, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ik: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, q4, k, v)
    return out[:, :, 0, :]
