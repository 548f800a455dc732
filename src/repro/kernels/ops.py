"""Jit'd public wrappers for the Pallas kernels.

On a TPU the kernels compile to Mosaic; on any other backend they run in
interpret mode (the CPU tests). ``use_pallas()`` is true exactly on a TPU:
model code routes through the kernels there and through the pure-jnp
reference path elsewhere, where interpret mode is slow.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import mamba2_chunk as _mc
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


def mamba2_chunk(xdt, Bh, Ch, cum, state):
    return _mc.mamba2_chunk(xdt, Bh, Ch, cum, state, interpret=_interpret())


def node_scores(features, weights):
    return _ns.node_scores(features, weights, interpret=_interpret())


def select_best_node(features, weights):
    return _ns.select_best(features, weights, interpret=_interpret())


def node_scores_batched(features, weights):
    """(B, N, 8) x (8,) -> (B, N): the engine's one-launch batched scorer."""
    return _ns.node_scores_batched(features, weights, interpret=_interpret())


def select_best_node_batched(features, weights):
    return _ns.select_best_batched(features, weights, interpret=_interpret())


def select_best_node_fused(features, weights):
    """(B, N, 8) x (8,) -> ((B,) int32 best index, (B,) f32 best score):
    the fused score+argmax kernel — per-task winners reduced on-chip, no
    (B, N) score matrix shipped to host."""
    return _ns.select_best_fused(features, weights, interpret=_interpret())


def select_best_node_columns(nodes, node_keys, tasks, task_keys, weights):
    """Node columns (7, N) + keys (4, N), task profiles (U, 2) + keys
    (U, 4), weights (8,) -> ((U,) int32 best index, (U,) f32 best score):
    the fused select scored from columns on the chip, no (U, N, 8) tensor;
    see node_score.select_best_columns."""
    return _ns.select_best_columns(nodes, node_keys, tasks, task_keys,
                                   weights, interpret=_interpret())


def select_best_node_joint(features, weights):
    """(B, P, N, 8) x (8,) -> ((B,) int32 cut idx, (B,) int32 node idx,
    (B,) f32 best score): the fused joint partition+placement reduction —
    per-task (cut, node) winners folded on-chip with lowest-(p, n) tie
    semantics; see node_score.select_best_joint."""
    return _ns.select_best_joint(features, weights, interpret=_interpret())


def select_best_node_sharded(features, weights, mesh=None, axis="nodes"):
    """Fused select with the node axis sharded across devices via
    shard_map (cross-shard argmax combine); see node_score.select_best_sharded."""
    return _ns.select_best_sharded(features, weights, mesh, axis,
                                   interpret=_interpret())


# Re-export oracles for tests/benchmarks.
flash_attention_ref = ref.flash_attention_ref
decode_attention_ref = ref.decode_attention_ref
mamba2_chunk_ref = ref.mamba2_chunk_ref
node_scores_ref = ref.node_scores_ref
node_scores_batched_ref = ref.node_scores_batched_ref
