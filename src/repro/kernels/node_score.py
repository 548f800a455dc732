"""Carbon-aware node scoring (paper Algorithm 1) — Pallas TPU kernels.

The paper's NSA inner loop at fleet scale: the five score components
(Eq. 3) and the feasibility filter fused into one VMEM pass. Three kernels
share that math (:func:`_eq3_total`):

- :func:`select_best_columns` — the engine's select on the chip
  (``VectorizedPolicy``): every (task, node) cell scored from node columns
  and task profiles, reduced on the chip to each task's winner. Its trace
  name is ``select_best_fused``.
- :func:`select_best_joint` — the (task, cut, node) reduction of
  ``PartitionPolicy`` over a (B, P, N, 8) feature tensor.
- :func:`node_scores` / :func:`node_scores_batched` — per-cell totals of
  an (N, 8) or (B, N, 8) feature tensor, for ``TemporalPolicy``'s slot
  grid.

Feature layout (N, 8) float32:
  0 cpu_free_frac, 1 mem_free_frac, 2 load, 3 avg_time_s,
  4 running_tasks, 5 intensity_x_e_est (I * E_est, Eq. 4),
  6 valid (1/0 feasibility), 7 padding
Weights: (8,) = [w_R, w_L, w_P, w_B, w_C, 0, 0, 0].

The column kernel's operands, with no per-(task, node) tensor outside the
chip:

  node rows (7, N) float32: 0 free_cpu, 1 free_mem, 2 load, 3 avg_time_s,
    4 running_tasks, 5 intensity_x_e_est, 6 node_ok (1/0)
  node keys (4, N) int32: :func:`f64_keys` of free_cpu, then of free_mem
  task rows (U, 2) float32: cpu, mem_mb
  task keys (U, 4) int32: :func:`f64_keys` of cpu, then of mem_mb
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _eq3_total(cpu_frac, mem_frac, load, time_s, running, ixe, w):
    """Eq. 3/4 weighted total from its six inputs, which broadcast against
    each other; ``w`` is the (1, 8) weight tile. The single in-kernel
    statement of the component math: the tensor kernels pass the columns
    of a feature tile, the column kernel (rows, 1) x (1, bn) cell fractions
    and (1, bn) node rows, and each cell's arithmetic is the same."""
    s_r = 0.5 * jnp.minimum(cpu_frac, 1.0) + 0.5 * jnp.minimum(mem_frac, 1.0)
    s_l = 1.0 - load
    s_p = 1.0 / (1.0 + time_s)
    s_b = 1.0 / (1.0 + 2.0 * running)
    s_c = 1.0 / (1.0 + ixe)
    return (w[0, 0] * s_r + w[0, 1] * s_l + w[0, 2] * s_p
            + w[0, 3] * s_b + w[0, 4] * s_c)


def _eq3_tile_scores(f, w):
    """(bn, 8) feature tile x (1, 8) weights -> (bn,) masked total scores."""
    total = _eq3_total(f[:, 0], f[:, 1], f[:, 2], f[:, 3], f[:, 4], f[:, 5],
                       w)
    return jnp.where(f[:, 6] > 0.5, total, NEG_INF)


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


# ---------------------------------------------------------------------------
# Batched variant: B pending tasks x N nodes in ONE kernel launch
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def node_scores_batched(features, weights, *, bn: int = 1024,
                        interpret: bool = False):
    """features: (B, N, 8) f32; weights: (8,) f32 -> (B, N) scores.

    ``TemporalPolicy``'s slot grid on the pallas backend: scoring is
    row-wise with shared weights, so B rows x N nodes flattens to one
    (B*N, 8) pass through the single kernel above — one pallas_call (and
    one HBM read of the feature tensor) per grid, with no duplicated Eq. 3
    math.
    """
    B, N, _ = features.shape
    flat = node_scores(features.reshape(B * N, 8), weights, bn=bn,
                       interpret=interpret)
    return flat.reshape(B, N)


# ---------------------------------------------------------------------------
# Score + argmax: reduce to (best_index, best_score) on-chip
# ---------------------------------------------------------------------------


def _fold_tile_best(s, base, first, idx_ref, val_ref):
    """Reduce an (r, bn) score tile to each row's (first) max and fold it
    into the running per-row best held in the resident (r, 1) output refs.

    Every value stays a 2D vector: the TPU cannot store scalars to VMEM.
    Inside the tile the lowest index among equal maxima wins (np.argmax
    semantics, via a 2D iota — TPU requires >= 2D); across tiles the
    strict ``>`` keeps the earlier tile, so exact ties resolve to the
    lowest global index. ``base`` is the tile's first global index."""
    bn = s.shape[1]
    tile_max = jnp.max(s, axis=1, keepdims=True)               # (r, 1)
    ii = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    tile_arg = jnp.min(jnp.where(s == tile_max, ii, bn), axis=1,
                       keepdims=True)                          # (r, 1)
    gidx = (base + tile_arg).astype(jnp.int32)

    @pl.when(first)
    def _init():
        val_ref[...] = tile_max
        idx_ref[...] = gidx

    @pl.when(jnp.logical_not(first))
    def _fold():
        prev = val_ref[...]
        better = tile_max > prev
        val_ref[...] = jnp.where(better, tile_max, prev)
        idx_ref[...] = jnp.where(better, gidx, idx_ref[...])


# Per-task outputs are (B, 1, 1): a (1, 1, 1) block's last two dims equal
# the array's, which the TPU tiling accepts (a (1, 1) block of a (B, 1)
# array is refused unless B == 1).
def _winner_specs(index_map, B):
    specs = [pl.BlockSpec((1, 1, 1), index_map)] * 2
    shapes = [jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
              jax.ShapeDtypeStruct((B, 1, 1), jnp.float32)]
    return specs, shapes


# ---------------------------------------------------------------------------
# Column select: score (task, node) cells from node columns + task profiles
# ---------------------------------------------------------------------------


def f64_keys(x) -> np.ndarray:
    """(2, n) int32 order-preserving keys of float64 values: ``a >= b``
    exactly when :func:`_keys_ge` says so of their keys, for any finite
    values — the exact float64 answer, which a float32 compare does not
    give. Row 0 is the high word of the sign-adjusted bit pattern (negative
    values have their magnitude bits flipped, -0.0 is taken as 0.0); row 1
    the low word with its top bit flipped, so that a signed compare orders
    it as unsigned."""
    b = (np.asarray(x, np.float64) + 0.0).view(np.int64)
    k = np.where(b < 0, b ^ np.int64(0x7FFFFFFFFFFFFFFF), b)
    hi = (k >> 32).astype(np.int32)
    lo = ((k & 0xFFFFFFFF) ^ 0x80000000).astype(np.uint32).view(np.int32)
    return np.stack([hi, lo])


def _keys_ge(a_hi, a_lo, b_hi, b_lo):
    """Lexicographic ``a >= b`` on :func:`f64_keys` words."""
    return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo >= b_lo))


def _column_select_kernel(t_ref, tk_ref, n_ref, nk_ref, w_ref, idx_ref,
                          val_ref):
    """One (bu task rows x bn nodes) tile: decide each cell's feasibility
    exactly from the float64 keys, score it with the shared Eq. 3 math, and
    fold the per-row best across the sequential node-tile grid axis.

    S_R's fractions clamp to 1 wherever the exact compare says the node
    holds the need (or nothing is needed), so a feasible cell reads exactly
    1 as the host's float64 ``min(free / need, 1)`` does; the chip's f32
    division, which need not round correctly, only reaches cells that
    ``valid`` masks."""
    j = pl.program_id(1)
    t, tk = t_ref[...], tk_ref[...]            # (bu, 2) f32, (bu, 4) i32
    n, nk = n_ref[...], nk_ref[...]            # (7, bn) f32, (4, bn) i32
    need_cpu, need_mem = t[:, 0:1], t[:, 1:2]
    free_cpu, free_mem = n[0:1], n[1:2]
    fits_cpu = _keys_ge(nk[0:1], nk[1:2], tk[:, 0:1], tk[:, 1:2])
    fits_mem = _keys_ge(nk[2:3], nk[3:4], tk[:, 2:3], tk[:, 3:4])
    cpu_frac = jnp.where(fits_cpu | (need_cpu <= 0.0), 1.0,
                         free_cpu / need_cpu)
    mem_frac = jnp.where(fits_mem | (need_mem <= 0.0), 1.0,
                         free_mem / need_mem)
    total = _eq3_total(cpu_frac, mem_frac, n[2:3], n[3:4], n[4:5], n[5:6],
                       w_ref[...])
    valid = (n[6:7] > 0.5) & fits_cpu & fits_mem
    s = jnp.where(valid, total, NEG_INF)                      # (bu, bn)
    _fold_tile_best(s, j * s.shape[1], j == 0, idx_ref, val_ref)


@functools.partial(jax.jit, static_argnames=("bu", "bn", "interpret"))
def select_best_columns(nodes, node_keys, tasks, task_keys, weights, *,
                        bu: int = 128, bn: int = 1024,
                        interpret: bool = False):
    """nodes (7, N) f32, node_keys (4, N) i32, tasks (U, 2) f32, task_keys
    (U, 4) i32 (layouts in the module docstring); weights (8,) f32 ->
    ((U,) int32 best index, (U,) f32 best score).

    Each task's first-argmax over the float32 Eq. 3 totals of the cells
    ``featurize_cached`` would build from the same columns — lowest index
    on exact ties, ``NEG_INF`` when no node is feasible, feasibility
    decided exactly in float64 — from O(U + N) inputs: one pallas_call
    over (task-row tiles x node tiles), each tile computing every cell's
    Eq. 3 total in VMEM. Rows are
    padded to a multiple of ``bu`` (and 8) and nodes to a multiple of
    ``bn`` when N exceeds it (padding nodes are not ok -> NEG_INF). Its
    trace name is ``select_best_fused``: it is the engine's select kernel.
    """
    u0, n0 = tasks.shape[0], nodes.shape[1]
    bu = min(bu, -(-u0 // 8) * 8)
    bn = min(bn, n0)
    pu, pn = (-u0) % bu, (-n0) % bn
    if pu:
        tasks = jnp.pad(tasks, ((0, pu), (0, 0)))
        task_keys = jnp.pad(task_keys, ((0, pu), (0, 0)))
    if pn:
        nodes = jnp.pad(nodes, ((0, 0), (0, pn)))
        node_keys = jnp.pad(node_keys, ((0, 0), (0, pn)))
    U, N = tasks.shape[0], nodes.shape[1]
    idx, val = pl.pallas_call(
        _column_select_kernel,
        name="select_best_fused",
        grid=(U // bu, N // bn),
        in_specs=[
            pl.BlockSpec((bu, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((bu, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((7, bn), lambda i, j: (0, j)),
            pl.BlockSpec((4, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, 8), lambda i, j: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((bu, 1), lambda i, j: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((U, 1), jnp.int32),
                   jax.ShapeDtypeStruct((U, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tasks, task_keys, nodes, node_keys, weights.reshape(1, 8))
    return idx[:u0, 0], val[:u0, 0]


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
                    idx_ref.at[0], val_ref.at[0])


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
