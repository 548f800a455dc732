"""Carbon-aware node scoring (paper Algorithm 1) — Pallas TPU kernel.

The paper's NSA inner loop at fleet scale: for N nodes, fuse the five score
components (Eq. 3) and the feasibility filter into one VMEM pass, emitting
per-node total scores (invalid nodes get -inf). The host (or a tiny jnp
argmax) picks the winner. At 10^5-10^6 nodes this is one HBM read of the
(N, 8) feature matrix — the op is memory-bound and the fusion is the win.

Feature layout (N, 8) float32:
  0 cpu_free_frac, 1 mem_free_frac, 2 load, 3 avg_time_s,
  4 running_tasks, 5 intensity_x_e_est (I * E_est, Eq. 4),
  6 valid (1/0 feasibility), 7 padding
Weights: (8,) = [w_R, w_L, w_P, w_B, w_C, 0, 0, 0].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _eq3_tile_scores(f, w):
    """(bn, 8) feature tile x (1, 8) weights -> (bn,) masked total scores.
    The single in-kernel statement of the Eq. 3/4 component math, shared by
    the score-emitting and the fused select kernels."""
    s_r = 0.5 * jnp.minimum(f[:, 0], 1.0) + 0.5 * jnp.minimum(f[:, 1], 1.0)
    s_l = 1.0 - f[:, 2]
    s_p = 1.0 / (1.0 + f[:, 3])
    s_b = 1.0 / (1.0 + 2.0 * f[:, 4])
    s_c = 1.0 / (1.0 + f[:, 5])
    total = (w[0, 0] * s_r + w[0, 1] * s_l + w[0, 2] * s_p
             + w[0, 3] * s_b + w[0, 4] * s_c)
    valid = f[:, 6] > 0.5
    return jnp.where(valid, total, NEG_INF)


def _kernel(f_ref, w_ref, s_ref):
    f = f_ref[...]                                 # (bn, 8)
    w = w_ref[...]                                 # (1, 8)
    s_ref[...] = _eq3_tile_scores(f, w)[:, None]


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def node_scores(features, weights, *, bn: int = 1024, interpret: bool = False):
    """features: (N, 8) f32; weights: (8,) f32 -> (N,) scores.

    N is padded up to a multiple of bn internally (padding rows invalid).
    """
    n0 = features.shape[0]
    pad = (-n0) % bn
    if pad:
        features = jnp.pad(features, ((0, pad), (0, 0)))
    N = features.shape[0]
    w2 = weights.reshape(1, 8)
    out = pl.pallas_call(
        _kernel,
        name="node_scores",
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, 8), lambda i: (i, 0)),
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(features, w2)
    return out[:n0, 0]


def select_best(features, weights, *, interpret: bool = False) -> jnp.ndarray:
    """Fused scoring + argmax; returns best node index (int32)."""
    s = node_scores(features, weights, interpret=interpret)
    return jnp.argmax(s).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Batched variant: B pending tasks x N nodes in ONE kernel launch
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def node_scores_batched(features, weights, *, bn: int = 1024,
                        interpret: bool = False):
    """features: (B, N, 8) f32; weights: (8,) f32 -> (B, N) scores.

    The CarbonEdgeEngine hot path: scoring is row-wise with shared weights,
    so B tasks x N nodes flattens to one (B*N, 8) pass through the single
    kernel above — still exactly one pallas_call (and one HBM read of the
    feature tensor) per batch, with no duplicated Eq. 3 math.
    """
    B, N, _ = features.shape
    flat = node_scores(features.reshape(B * N, 8), weights, bn=bn,
                       interpret=interpret)
    return flat.reshape(B, N)


def select_best_batched(features, weights, *, interpret: bool = False):
    """Fused batched scoring + per-task argmax -> (B,) int32 node indices."""
    idx, _ = select_best_fused(features, weights, interpret=interpret)
    return idx


# ---------------------------------------------------------------------------
# Fused score + argmax: reduce to (best_index, best_score) on-chip
# ---------------------------------------------------------------------------


def _fold_tile_best(s, base, first, idx_ref, val_ref):
    """Reduce one (1, bn) score tile to its (first) max and fold it into the
    running per-task best held in the resident (1, 1, 1) output blocks.

    Every value stays a (1, 1) vector: the TPU cannot store scalars to
    VMEM. Inside the tile the lowest index among equal maxima wins
    (np.argmax semantics, via a 2D iota — TPU requires >= 2D); across
    tiles the strict ``>`` keeps the earlier tile, so exact ties resolve to
    the lowest global index. ``base`` is the tile's first global index."""
    bn = s.shape[1]
    tile_max = jnp.max(s, axis=1, keepdims=True)               # (1, 1)
    ii = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    tile_arg = jnp.min(jnp.where(s == tile_max, ii, bn), axis=1,
                       keepdims=True)                          # (1, 1)
    gidx = (base + tile_arg).astype(jnp.int32)

    @pl.when(first)
    def _init():
        val_ref[0] = tile_max
        idx_ref[0] = gidx

    @pl.when(jnp.logical_not(first))
    def _fold():
        prev = val_ref[0]
        better = tile_max > prev
        val_ref[0] = jnp.where(better, tile_max, prev)
        idx_ref[0] = jnp.where(better, gidx, idx_ref[0])


def _select_kernel(f_ref, w_ref, idx_ref, val_ref):
    """One (1, bn, 8) node tile of one task row: score it, reduce to the
    tile's (first) max, and fold into the running per-task best across the
    sequential node-tile grid axis. Emits per-task winner index + score —
    the (B, N) score matrix never leaves the chip."""
    j = pl.program_id(1)
    s = _eq3_tile_scores(f_ref[0], w_ref[...])[None, :]      # (1, bn)
    _fold_tile_best(s, j * s.shape[1], j == 0, idx_ref, val_ref)


# Per-task outputs are (B, 1, 1): a (1, 1, 1) block's last two dims equal
# the array's, which the TPU tiling accepts (a (1, 1) block of a (B, 1)
# array is refused unless B == 1).
def _winner_specs(index_map, B):
    specs = [pl.BlockSpec((1, 1, 1), index_map)] * 2
    shapes = [jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
              jax.ShapeDtypeStruct((B, 1, 1), jnp.float32)]
    return specs, shapes


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def select_best_fused(features, weights, *, bn: int = 1024,
                      interpret: bool = False):
    """features: (B, N, 8) f32; weights: (8,) f32 ->
    ((B,) int32 best index, (B,) f32 best score).

    One pallas_call tiling the node axis: each tile reduces to its local
    (max, first-argmax) and folds into the per-task running best across
    the sequential tile axis, so only 2*B scalars ship to host instead of
    a (B, N) score matrix. N is padded to a multiple of bn (padding rows
    invalid -> NEG_INF, never selected while any real node is feasible).
    Callers that want a bounded jit cache should pad (B, N) to shape
    buckets first (VectorizedPolicy does).
    """
    B, n0, _ = features.shape
    pad = (-n0) % bn
    if pad:
        features = jnp.pad(features, ((0, 0), (0, pad), (0, 0)))
    N = features.shape[1]
    out_specs, out_shape = _winner_specs(lambda i, j: (i, 0, 0), B)
    idx, val = pl.pallas_call(
        _select_kernel,
        name="select_best_fused",
        grid=(B, N // bn),
        in_specs=[
            pl.BlockSpec((1, bn, 8), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 8), lambda i, j: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(features, weights.reshape(1, 8))
    return idx[:, 0, 0], val[:, 0, 0]


# ---------------------------------------------------------------------------
# Joint (cut, node) selection: fold the winner over a (B, P, N) grid
# ---------------------------------------------------------------------------


def _joint_select_kernel(n_pad, f_ref, w_ref, idx_ref, val_ref):
    """One (1, 1, bn, 8) node tile of one (task, cut) cell: score it with
    the shared Eq. 3 tile math and fold into the running per-task best
    across the sequential cut-major (p, then node-tile j) grid axes. The
    emitted index is flat over the padded (P, N_pad) plane — cut-major, so
    strict-> folding keeps the lowest (p, n) on exact ties, np.argmax-
    compatible with the numpy path's reshape over (P, N)."""
    p = pl.program_id(1)
    j = pl.program_id(2)
    s = _eq3_tile_scores(f_ref[0, 0], w_ref[...])[None, :]   # (1, bn)
    _fold_tile_best(s, p * n_pad + j * s.shape[1], (p == 0) & (j == 0),
                    idx_ref, val_ref)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def select_best_joint(features, weights, *, bn: int = 1024,
                      interpret: bool = False):
    """features: (B, P, N, 8) f32; weights: (8,) f32 ->
    ((B,) int32 cut index, (B,) int32 node index, (B,) f32 best score).

    The joint partition+placement reduction
    (:class:`repro.partition.policy.PartitionPolicy`): each task row scans
    its P candidate cuts x N nodes in one pallas_call and ships 3*B
    scalars to host — the (B, P, N) score tensor never leaves the chip.
    The fold order is cut-major (all node tiles of cut 0, then cut 1, ...)
    with a strict-> combine, so exact score ties resolve to the lowest
    (p, n) pair — the same winner ``np.argmax`` picks over the flattened
    (P, N) plane. N is padded to a multiple of ``bn`` (padding rows
    invalid -> NEG_INF); callers wanting a bounded jit cache pad (B, P, N)
    to shape buckets first (PartitionPolicy does).
    """
    B, P, n0, _ = features.shape
    pad = (-n0) % bn
    if pad:
        features = jnp.pad(features, ((0, 0), (0, 0), (0, pad), (0, 0)))
    N = features.shape[2]
    out_specs, out_shape = _winner_specs(lambda i, p, j: (i, 0, 0), B)
    idx, val = pl.pallas_call(
        functools.partial(_joint_select_kernel, N),
        name="select_best_joint",
        grid=(B, P, N // bn),
        in_specs=[
            pl.BlockSpec((1, 1, bn, 8), lambda i, p, j: (i, p, j, 0)),
            pl.BlockSpec((1, 8), lambda i, p, j: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(features, weights.reshape(1, 8))
    flat = idx[:, 0, 0]
    # Padding rows can only win when nothing real is feasible, in which
    # case the score is NEG_INF and callers discard the indices anyway.
    return ((flat // N).astype(jnp.int32), (flat % N).astype(jnp.int32),
            val[:, 0, 0])


# ---------------------------------------------------------------------------
# Sharded node axis: N >= 10^5 fleets across devices
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _sharded_select_fn(mesh, axis: str, bn: int, interpret: bool):
    """Build (and cache) the shard_map'd fused select for one mesh: each
    device scores its node shard with the fused kernel, then a cross-shard
    argmax combine picks the global winner (lowest global index on ties)."""

    def local_select(f_local, w):
        # f_local: (B, N/d, 8) on this device
        idx, val = select_best_fused(f_local, w, bn=bn, interpret=interpret)
        shard = jax.lax.axis_index(axis)
        gidx = idx + (shard * f_local.shape[1]).astype(jnp.int32)
        vals = jax.lax.all_gather(val, axis)                   # (d, B)
        gidxs = jax.lax.all_gather(gidx, axis)                 # (d, B)
        best_val = jnp.max(vals, axis=0)                       # (B,)
        # among shards attaining the max, take the lowest global index
        cand = jnp.where(vals == best_val[None, :], gidxs, jnp.iinfo(jnp.int32).max)
        return jnp.min(cand, axis=0).astype(jnp.int32), best_val

    from jax.sharding import PartitionSpec as P

    return jax.jit(jax.shard_map(
        local_select, mesh=mesh,
        in_specs=(P(None, axis, None), P(None)),
        out_specs=(P(None), P(None)),
        check_vma=False))


def select_best_sharded(features, weights, mesh=None, axis: str = "nodes",
                        *, bn: int = 1024, interpret: bool = False):
    """Fused select with the node axis sharded across devices.

    features: (B, N, 8) f32 with N divisible by the mesh's ``axis`` size
    (pad with invalid rows first); returns ((B,) int32, (B,) f32) exactly
    like :func:`select_best_fused`. With ``mesh=None`` builds a 1-D mesh
    over all local devices.
    """
    if mesh is None:
        from jax.sharding import Mesh

        devs = np.array(jax.devices())
        mesh = Mesh(devs, (axis,))
    return _sharded_select_fn(mesh, axis, bn, interpret)(features, weights)
