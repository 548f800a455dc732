"""Single-token GQA decode attention over a long KV cache — Pallas TPU
kernel.

The K and V caches are the decode step's own stacks over layers,
(L, B, S, K, hd) row-major, and the layer attended is a scalar-prefetched
index, so the kernel reads that layer's blocks where they lie and no copy
of a layer's K/V is made; a single layer's (B, S, K, hd) is the L = 1 case.
Each grid step takes ``rows`` batch rows and all H query heads against one
(rows, bk, K, hd) block of K and of V, read as its (rows, bk * K, hd) view
(the same bytes). The scores of every query head against the block's
bk * K rows come from one matmul, and the pairs whose KV head is not the
query head's (h // (H / K)) are masked with the positions past ``pos``
(and, with a window, those at or before pos - window), so each KV head is
read once for its H / K query heads. Softmax runs as a running
log-sum-exp over the blocks in float32. Blocks past ``pos`` (and before
the window) are neither fetched (the index map holds the nearest needed
block) nor computed, so the cache may be longer than the context.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# KV positions per block, and the unit a cache length is rounded up to.
BLOCK_K = 256

# The most bytes of K (and of V) one grid step reads: one batch row a step at
# Qwen3-1.7B's decode shapes, which timed 1-3% faster on a v5e than two or
# four rows.
BLOCK_BYTES = 1 << 19


def _first_block(pos, window: Optional[int], bk: int):
    """Index of the first block holding a position attended."""
    if window is None:
        return 0
    return jnp.maximum(pos - window + 1, 0) // bk


def _kernel(pos_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
            l_ref, *, scale: float, window: Optional[int], softcap: float,
            bk: int, nk: int, rows: int, kv_heads: int):
    ik = pl.program_id(1)
    pos = pos_ref[0]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((ik <= pos // bk) & (ik >= _first_block(pos, window, bk)))
    def _step():
        H = q_ref.shape[1]
        shape = (H, bk * kv_heads)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        head = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        t = ik * bk + col // kv_heads
        keep = (t <= pos) & (col % kv_heads == head // (H // kv_heads))
        if window is not None:
            keep &= t > pos - window
        for r in range(rows):
            q = q_ref[r]                                   # (H, hd)
            k = k_ref[0, r]                                # (bk * K, hd)
            dt = jnp.promote_types(q.dtype, k.dtype)
            s = jax.lax.dot_general(
                q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if softcap and softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[r]                              # (H, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[r] = l_ref[r] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[r] = acc_ref[r] * alpha + jax.lax.dot(
                p, v_ref[0, r].astype(jnp.float32))
            m_ref[r] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        lsum = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / lsum).astype(o_ref.dtype)


def _rows_per_step(B: int, row_bytes: int, cap: int = 8) -> int:
    """The most batch rows, a divisor of B, whose block stays within
    ``BLOCK_BYTES`` (at least one)."""
    fit = max(1, min(B, cap, BLOCK_BYTES // row_bytes))
    return max(r for r in range(1, fit + 1) if B % r == 0)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "bk", "interpret"))
def decode_attention(q, cache_k, cache_v, pos, layer=None, *,
                     window: Optional[int] = None, softcap: float = 0.0,
                     bk: int = BLOCK_K, interpret: bool = False):
    """q: (B, H, hd); cache_k/cache_v: (L, B, S, K, hd) stacked over layers
    with ``layer`` the scalar int32 layer attended, or one layer's
    (B, S, K, hd); pos: scalar int32, the last position attended.

    Returns (B, H, hd) in q's dtype. H must be a multiple of K and S of bk.
    """
    if cache_k.ndim == 4:
        cache_k, cache_v, layer = cache_k[None], cache_v[None], 0
    L, B, S, K, hd = cache_k.shape
    H = q.shape[1]
    nk = S // bk
    rows = _rows_per_step(B, bk * K * hd * cache_k.dtype.itemsize)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)

    def kv_block(i, ik, pos_ref, layer_ref):
        p = pos_ref[0]
        ik = jnp.clip(ik, _first_block(p, window, bk), p // bk)
        return (layer_ref[0], i, ik, 0)

    def q_block(i, ik, pos_ref, layer_ref):
        return (i, 0, 0)

    kernel = functools.partial(_kernel, scale=hd ** -0.5, window=window,
                               softcap=softcap, bk=bk, nk=nk, rows=rows,
                               kv_heads=K)
    kv_spec = pl.BlockSpec((1, rows, bk * K, hd), kv_block)
    return pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B // rows, nk),
            in_specs=[pl.BlockSpec((rows, H, hd), q_block), kv_spec, kv_spec],
            out_specs=pl.BlockSpec((rows, H, hd), q_block),
            scratch_shapes=[
                pltpu.VMEM((rows, H, hd), jnp.float32),
                pltpu.VMEM((rows, H, 1), jnp.float32),
                pltpu.VMEM((rows, H, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, layer_arr, q, cache_k.reshape(L, B, S * K, hd),
      cache_v.reshape(L, B, S * K, hd))
