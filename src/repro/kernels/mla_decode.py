"""Absorbed multi-head latent attention for one decode token — Pallas TPU
kernel.

With W_uk absorbed into the query and W_uv into the output (DeepSeek-V2,
arXiv:2405.04434 §2.1.3), every head attends over the same cached latent:
position t holds c_t (the normed ``rank``-wide latent) followed by the
roped shared key. Scores are q_h . cache_t over all of its width, values
are c_t, so the cache is read once for all heads. Each grid step takes
``rows`` batch rows and one ``bk`` block of positions; blocks past ``pos``
are neither fetched (the index map holds the last needed block) nor
computed, and positions past ``pos`` inside the last block are masked, so
the cache may be longer than the context.

The cache may be one layer's (B, S, W) or a stack over layers
(L, B, S, W) with the layer given as a second scalar prefetch: the kernel
then reads that layer's blocks where they lie in the stack, so a decode
step that carries the stacked cache makes no per-layer copy of it. The
kernel reads blocks of the cache's (W, S) view: the TPU holds a latent
cache whose width (576) is not a multiple of 128 position-minor, so that
view is the buffer as it lies and needs no relayout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import BLOCK_K

NEG_INF = -1e30


def _kernel(pos_ref, layer_ref, q_ref, c_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, rank: int, bk: int, nk: int, rows: int):
    ik = pl.program_id(1)
    pos = pos_ref[0]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ik * bk <= pos)
    def _step():
        H = q_ref.shape[1]
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (H, bk), 1)
        keep = cols <= pos
        for r in range(rows):
            q = q_ref[r]                                   # (H, W)
            c = c_ref[0, r]                                # (W, bk)
            s = jax.lax.dot(
                q, c, preferred_element_type=jnp.float32) * scale  # (H, bk)
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[r]                              # (H, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[r] = l_ref[r] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[r] = acc_ref[r] * alpha + jax.lax.dot_general(
                p.astype(c.dtype), c[:rank], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[r] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        lsum = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / lsum).astype(o_ref.dtype)


def _rows_per_step(B: int, cap: int = 8) -> int:
    return max(r for r in range(1, min(B, cap) + 1) if B % r == 0)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "bk",
                                             "interpret"))
def mla_decode_attention(q, cache, pos, layer=None, *, scale: float,
                         rank: int, bk: int = BLOCK_K,
                         interpret: bool = False):
    """q: (B, H, W) absorbed queries (latent part, then rope part);
    cache: (B, S, W) latent cache, or (L, B, S, W) stacked over layers with
    ``layer`` the scalar int32 layer attended; pos: scalar int32, the last
    position attended. Returns (B, H, rank) in ``cache.dtype``: each head's
    softmax-weighted sum of the latents. S must be a multiple of bk."""
    if cache.ndim == 3:
        cache, layer = cache[None], 0
    B, H, W = q.shape
    S = cache.shape[2]
    nk = S // bk
    rows = _rows_per_step(B)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)

    def cache_block(i, ik, pos_ref, layer_ref):
        return (layer_ref[0], i, 0, jnp.minimum(ik, pos_ref[0] // bk))

    kernel = functools.partial(_kernel, scale=scale, rank=rank, bk=bk,
                               nk=nk, rows=rows)
    return pl.pallas_call(
        kernel,
        name="mla_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B // rows, nk),
            in_specs=[
                pl.BlockSpec((rows, H, W), lambda i, ik, p, l: (i, 0, 0)),
                pl.BlockSpec((1, rows, W, bk), cache_block),
            ],
            out_specs=pl.BlockSpec((rows, H, rank),
                                   lambda i, ik, p, l: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, H, rank), jnp.float32),
                pltpu.VMEM((rows, H, 1), jnp.float32),
                pltpu.VMEM((rows, H, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), cache.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, layer_arr, q.astype(cache.dtype), jnp.swapaxes(cache, 2, 3))
