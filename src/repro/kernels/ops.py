"""Jit'd public wrappers for the Pallas kernels.

On a TPU the kernels compile to Mosaic; on any other backend they run in
interpret mode (the CPU tests). ``use_pallas()`` is true exactly on a TPU:
model code routes through the kernels there and through the pure-jnp
reference path elsewhere, where interpret mode is slow.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import mamba2_chunk as _mc
from repro.kernels import mla_decode as _mla
from repro.kernels import moe_gmm as _gmm
from repro.kernels import node_score as _ns
from repro.kernels import ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def use_pallas() -> bool:
    return not _interpret()


def flash_attention(q, k, v, *, causal=True, window: Optional[int] = None,
                    softcap: float = 0.0):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, interpret=_interpret())


def decode_attention(q, k, v, pos, *, window: Optional[int] = None,
                     softcap: float = 0.0):
    return _dec.decode_attention(q, k, v, pos, window=window,
                                 softcap=softcap, interpret=_interpret())


def mla_decode_attention(q, cache, pos, layer=None, *, scale: float,
                         rank: int):
    return _mla.mla_decode_attention(q, cache, pos, layer, scale=scale,
                                     rank=rank, interpret=_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def moe_gmm(x, w_gate, w_up, w_down, sizes, tile_expert, n_valid, layer, tm):
    """Grouped SwiGLU over held experts in tile layout (see
    ``moe_gmm.moe_gmm``; weights stacked over layers): (M + tm, D).
    Differentiable through the ``ragged_dot`` oracle, so a training step
    may run the kernel."""
    return _gmm.moe_gmm(x, w_gate, w_up, w_down, tile_expert, n_valid, layer,
                        tm=tm, interpret=_interpret())


def _moe_gmm_oracle(x, w_gate, w_up, w_down, sizes, layer, tm):
    ws = [w[layer[0]] for w in (w_gate, w_up, w_down)]
    y = ref.moe_gmm_ref(x, *ws, sizes)
    return jnp.concatenate([y, jnp.zeros((tm, y.shape[1]), y.dtype)])


def _moe_gmm_fwd(x, w_gate, w_up, w_down, sizes, tile_expert, n_valid, layer,
                 tm):
    out = moe_gmm(x, w_gate, w_up, w_down, sizes, tile_expert, n_valid, layer,
                  tm)
    return out, (x, w_gate, w_up, w_down, sizes, layer)


def _moe_gmm_bwd(tm, res, g):
    x, w_gate, w_up, w_down, sizes, layer = res
    _, vjp = jax.vjp(lambda *a: _moe_gmm_oracle(*a, sizes, layer, tm),
                     x, w_gate, w_up, w_down)
    return (*vjp(g), None, None, None, None)


moe_gmm.defvjp(_moe_gmm_fwd, _moe_gmm_bwd)


def mamba2_chunk(xdt, Bh, Ch, cum, state):
    return _mc.mamba2_chunk(xdt, Bh, Ch, cum, state, interpret=_interpret())


def node_scores_batched(features, weights):
    """(B, N, 8) x (8,) -> (B, N): the one-launch batched scorer of
    ``TemporalPolicy``'s slot grid."""
    return _ns.node_scores_batched(features, weights, interpret=_interpret())


def select_best_node_columns(nodes, node_keys, tasks, task_keys, weights):
    """Node columns (7, N) + keys (4, N), task profiles (U, 2) + keys
    (U, 4), weights (8,) -> ((U,) int32 best index, (U,) f32 best score):
    the engine's select, scored from columns on the chip with no (U, N, 8)
    tensor; see node_score.select_best_columns."""
    return _ns.select_best_columns(nodes, node_keys, tasks, task_keys,
                                   weights, interpret=_interpret())


def select_best_node_joint(features, weights):
    """(B, P, N, 8) x (8,) -> ((B,) int32 cut idx, (B,) int32 node idx,
    (B,) f32 best score): the fused joint partition+placement reduction —
    per-task (cut, node) winners folded on-chip with lowest-(p, n) tie
    semantics; see node_score.select_best_joint."""
    return _ns.select_best_joint(features, weights, interpret=_interpret())


# Re-export oracles for tests/benchmarks.
flash_attention_ref = ref.flash_attention_ref
decode_attention_ref = ref.decode_attention_ref
mla_decode_attention_ref = ref.mla_decode_attention_ref
moe_gmm_ref = ref.moe_gmm_ref
mamba2_chunk_ref = ref.mamba2_chunk_ref
node_scores_ref = ref.node_scores_ref
node_scores_batched_ref = ref.node_scores_batched_ref
