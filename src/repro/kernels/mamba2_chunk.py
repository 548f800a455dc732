"""Mamba2 (SSD) chunk step — Pallas TPU kernel.

One program per (batch, head): computes the intra-chunk quadratic term, the
inter-chunk contribution of the carried state, and the updated state for a
single chunk of length L. The chunk loop itself stays a lax.scan in JAX
(models/ssm.py), calling this kernel per step.

VMEM working set per program: x (L,P), B/C (L,N), scores (L,L), state
(N,P) — with L=256, N=64, P=64 that is ~0.6 MB, comfortably resident. The
(L,L) score matmul and the (L,N)x(L,P) state update run on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, b_ref, c_ref, cum_ref, state_ref, y_ref, newstate_ref):
    x = x_ref[0, 0].astype(jnp.float32)            # (L, P)
    Bm = b_ref[0, 0].astype(jnp.float32)           # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)           # (L, N)
    cum = cum_ref[0, 0].astype(jnp.float32)        # (L, 1) cumsum(dt*A)
    state = state_ref[0, 0].astype(jnp.float32)    # (N, P)

    L = x.shape[0]
    # Intra-chunk: scores[t, s] = (C_t . B_s) * exp(cum_t - cum_s), s <= t.
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (L, L)
    dec = cum - cum.T                                           # (L, L)
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    mask = cols <= rows
    scores = jnp.where(mask, cb * jnp.exp(dec), 0.0)
    y = jax.lax.dot(scores, x)                                  # (L, P)
    # Inter-chunk contribution: C_t exp(cum_t) . state.
    y = y + jax.lax.dot(Cm * jnp.exp(cum), state)
    # State update: exp(last - cum_s) B_s^T x_s + exp(last) * state.
    last = cum[L - 1, 0]
    w_in = jnp.exp(last - cum)                                  # (L, 1)
    s_local = jax.lax.dot_general(Bm * w_in, x, (((0,), (0,)), ((), ())))
    newstate_ref[0, 0] = jnp.exp(last) * state + s_local
    y_ref[0, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba2_chunk(xdt, Bh, Ch, cum, state, *, interpret: bool = False):
    """One SSD chunk for all (batch, head) pairs.

    xdt:   (B, H, L, P)  x premultiplied by dt
    Bh/Ch: (B, H, L, N)  input/output projections (head-expanded)
    cum:   (B, H, L)     within-chunk cumsum of dt*A
    state: (B, H, N, P)  carried state (f32)
    Returns (y (B,H,L,P), new_state (B,H,N,P)).
    """
    B, H, L, P = xdt.shape
    N = Bh.shape[-1]
    cum4 = cum[..., None]                          # (B,H,L,1)
    y, new_state = pl.pallas_call(
        _kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, P), xdt.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xdt, Bh, Ch, cum4, state)
    return y, new_state
