"""Shared model machinery: param specs, init, norms, positions.

Parameters are plain nested-dict pytrees of jnp arrays. Every model first
builds a *spec tree* of :class:`ParamSpec` (shape + logical axes + init);
from the spec we derive, without duplication:

- ``init_from_spec``      real parameters (seeded, deterministic by path)
- ``abstract_from_spec``  ShapeDtypeStructs for the multi-pod dry-run
- ``axes_from_spec``      logical-axis tree consumed by sharding/rules.py
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | small_normal
    scale: float = 0.02

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def map_spec(fn, spec: PyTree) -> PyTree:
    return jax.tree.map(fn, spec, is_leaf=_is_spec)


def init_from_spec(spec: PyTree, key: jax.Array, dtype: jnp.dtype) -> PyTree:
    """Deterministic init: each leaf's key is fold_in(key, crc32(path)),
    the same in every process (``hash`` of a string is salted per
    process)."""
    leaves_with_path = jax.tree_util.tree_flatten_with_path(spec, is_leaf=_is_spec)
    flat, treedef = leaves_with_path

    def init_one(path, p: ParamSpec):
        pathstr = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, np.uint32(zlib.crc32(pathstr.encode())
                                              & 0x7FFFFFFF))
        if p.init == "zeros":
            return jnp.zeros(p.shape, dtype)
        if p.init == "ones":
            return jnp.ones(p.shape, dtype)
        scale = p.scale if p.init == "normal" else p.scale * 0.1
        return (jax.random.normal(k, p.shape, jnp.float32) * scale).astype(dtype)

    leaves = [init_one(path, p) for path, p in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def abstract_from_spec(spec: PyTree, dtype: jnp.dtype) -> PyTree:
    return map_spec(lambda p: jax.ShapeDtypeStruct(p.shape, dtype), spec)


def axes_from_spec(spec: PyTree) -> PyTree:
    return map_spec(lambda p: p.axes, spec)


def stack_spec(spec: PyTree, n: int, axis_name: str = "layers") -> PyTree:
    """Prepend a stacking dimension (for lax.scan over layers)."""
    return map_spec(
        lambda p: ParamSpec((n,) + p.shape, (axis_name,) + p.axes, p.init, p.scale),
        spec,
    )


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def layer_norm(x, weight, bias, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32)) + bias.astype(jnp.float32)).astype(dt)


def norm_spec(cfg, dim: int) -> Dict[str, ParamSpec]:
    if cfg.norm_type == "layernorm":
        return {
            "scale": ParamSpec((dim,), ("norm",), "zeros"),
            "bias": ParamSpec((dim,), ("norm",), "zeros"),
        }
    return {"scale": ParamSpec((dim,), ("norm",), "zeros")}


def apply_norm(cfg, p, x):
    if cfg.norm_type == "layernorm":
        return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rms_norm(x, p["scale"], cfg.norm_eps)


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# Positional embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, scaling=None) -> jnp.ndarray:
    """Inverse frequencies of rotate-half rope; with ``scaling`` (a
    ``YarnScaling``) YaRN's blend of the base frequencies and those
    divided by ``factor``, ramped between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    if scaling is None:
        return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    base = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))

    def dim_of(rotations):
        return (head_dim * math.log(scaling.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim_of(scaling.beta_fast)), 0)
    hi = min(math.ceil(dim_of(scaling.beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp                      # 1: base frequency, 0: scaled
    inv = base / scaling.factor * (1.0 - keep) + base * keep
    return jnp.asarray(inv, jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_cos_scale(scaling) -> float:
    """YaRN's multiplier on cos and sin (1 without scaling)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x, positions, theta: float, scaling=None):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)                  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                          # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        m = rope_cos_scale(scaling)
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions, theta: float, sections: Tuple[int, ...]):
    """M-RoPE (qwen2-vl): positions (B, 3, S); sections sum to D/2.

    Each frequency band uses the position stream of its section
    (temporal / height / width).
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                           # (D/2,)
    # section id per frequency index
    sec_id = np.concatenate([np.full(s, i) for i, s in enumerate(sections)])
    sec_id = jnp.asarray(sec_id)                           # (D/2,)
    # pos_per_freq: (B, S, D/2)
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32).transpose(0, 2, 1),  # (B, S, 3)
        jnp.broadcast_to(sec_id[None, None, :],
                         positions.shape[0:1] + (positions.shape[2], d // 2)),
        axis=-1,
    )
    angles = (pos * freqs)[..., None, :]                   # (B, S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_emb(positions, dim: int):
    """Whisper-style sinusoidal embeddings; positions (...,) -> (..., dim)."""
    half = dim // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / (half - 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
