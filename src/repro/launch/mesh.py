"""Production mesh construction.

A function (not module-level constant) so importing never touches jax
device state. Single pod: (16, 16) = 256 chips ("data", "model").
Multi-pod: (2, 16, 16) = 512 chips ("pod", "data", "model").

Axes are ``Auto``: the model code places activations with
``with_sharding_constraint`` (``repro.sharding.constraints``), which only
accepts Auto axes; ``jax.make_mesh`` defaults to Explicit ones.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(*, model: int = 2, data: int = 2, pod: int = 0):
    """Small mesh for CPU tests (requires host-device-count env set)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
