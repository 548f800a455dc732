"""Activation sharding constraints (MaxText-style ``with_sharding_constraint``).

Without explicit constraints GSPMD may re-shard activations badly — e.g.
replicating the batch dimension inside attention (observed: per-device
attention dots at full global batch, a 16x FLOP overcount). Model code
calls ``constrain(x, "batch", "seq", "heads", ...)`` with *logical* axis
names; mapping respects the active mesh, divisibility, and axis reuse.

No-op outside a ``jax.set_mesh`` context (CPU unit tests).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

_MODEL_AXES = {"heads", "kv_heads", "ff", "expert", "inner", "vocab",
               "head_dim", "ssm_heads", "kv_seq"}


def mesh_axis_size(name: str) -> int:
    """Size of a mesh axis in the active mesh (0 if absent / no mesh)."""
    m = _current_mesh()
    if m is None or name not in m.axis_names:
        return 0
    return int(m.shape[name])


def _current_mesh():
    """The mesh set by ``jax.set_mesh`` (None without one, or on a single
    device). Callers enter meshes with ``jax.set_mesh``; the legacy
    ``with mesh:`` context is not visible here."""
    m = jax.sharding.get_abstract_mesh()
    if m.empty or m.size <= 1:
        return None
    return m


def constrain(x, *axes: Optional[str]):
    """Constrain array ``x``'s dims to logical axes (None = replicated)."""
    m = _current_mesh()
    if m is None:
        return x
    names = m.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp_size = math.prod(m.shape[a] for a in dp) if dp else 1
    used = set()
    spec = []
    for dim, ax in zip(x.shape, axes):
        target = None
        if ax == "batch" and dp and "data" not in used and dim % dp_size == 0:
            target = dp if len(dp) > 1 else dp[0]
            used.update(dp)
        elif ax == "seq" and dp and "data" not in used and dim % dp_size == 0:
            # context parallelism (long-context decode)
            target = dp if len(dp) > 1 else dp[0]
            used.update(dp)
        elif ax in _MODEL_AXES and "model" in names and "model" not in used \
                and dim % m.shape["model"] == 0:
            target = "model"
            used.add("model")
        spec.append(target)
    return jax.lax.with_sharding_constraint(x, P(*spec))
