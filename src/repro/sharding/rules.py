"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Params carry logical axis names (models/common.ParamSpec.axes); these rules
map them to mesh axes per mode. Training uses FSDP (embed axis sharded over
``data``) so 480B-scale AdamW state is distributed; serving shards params
over ``model`` only and batch/sequence over (pod, data).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# axisname -> mesh axis (None = replicated). Resolution is left-to-right,
# skipping a mapping when the dimension is not divisible by the mesh-axis
# size or the mesh axis is already used — `head_dim -> model` then acts as
# the fallback for narrow KV-head counts (kv=8 on a 16-way model axis).
_BASE_RULES: Dict[str, Optional[str]] = {
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "expert": "model",
    "inner": "model",
    "head_dim": "model",
    "heads_inner": None,
    "xlstm_heads": None,
    "ssm_heads": "model",
    "state": None,
    "conv": None,
    "norm": None,
    "layers": None,
    None: None,
}


def rules_for(mode: str) -> Dict[str, Optional[str]]:
    r = dict(_BASE_RULES)
    r["embed"] = "data" if mode == "train" else None
    return r


def spec_from_axes(axes: Tuple[Optional[str], ...],
                   shape: Tuple[int, ...], rules, mesh: Mesh) -> P:
    used = set()
    out = []
    for a, dim in zip(axes, shape):
        m = rules.get(a)
        if m is None or m in used or m not in mesh.axis_names or dim % mesh.shape[m]:
            out.append(None)
        else:
            out.append(m)
            used.add(m)
    return P(*out)


def param_pspecs(cfg, mode: str, mesh: Mesh):
    from repro.models import transformer
    from repro.models.common import ParamSpec

    rules = rules_for(mode)
    spec_tree = transformer.model_spec(cfg)
    return jax.tree.map(
        lambda ps: spec_from_axes(ps.axes, ps.shape, rules, mesh),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def opt_pspecs(cfg, mesh: Mesh):
    """AdamW state: mu/nu shard like params, step replicated."""
    from repro.optim.adamw import AdamWState

    p = param_pspecs(cfg, "train", mesh)
    return AdamWState(step=P(), mu=p, nu=p)


def named(tree, mesh: Mesh):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        tree, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Activations / batch / cache
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Data-parallel mesh axes (pod included when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _dp_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in batch_axes(mesh)]))


def batch_pspecs(cfg, shape_kind: str, global_batch: int, mesh: Mesh):
    """PartitionSpecs for the input batch dict."""
    dp = batch_axes(mesh)
    b = dp if global_batch % _dp_size(mesh) == 0 else (
        dp[:-1] if len(dp) > 1 and global_batch % mesh.shape[dp[0]] == 0 else ())
    bspec = b if b else None
    specs = {"tokens": P(bspec, None)}
    if shape_kind == "train":
        specs["labels"] = P(bspec, None)
    if cfg.encoder_layers:
        specs["encoder_embeds"] = P(bspec, None, None)
    if cfg.vision_tokens:
        specs["vision_embeds"] = P(bspec, None, None)
    if cfg.mrope_sections:
        specs["mrope_positions"] = P(bspec, None, None)
    return specs


def kv_layout() -> str:
    """Decode KV-cache layout policy: "heads" (baseline: KV heads/head_dim
    on `model`) or "seq" (optimized: KV sequence on `model`, flash-decode
    style distributed softmax — §Perf iteration)."""
    import os

    return os.environ.get("REPRO_DECODE_KV_LAYOUT", "seq")


def decode_kv_plan(batch: int, kv_heads: int, mesh: Mesh, q_heads: int = 0) -> str:
    """Per-case layout under the "seq" policy (§Perf iterations 2-3):

    - batch fills the dp axes  -> shard KV seq over `model` ("seq"):
      measured 1.5-32x on decode_32k, no regressions.
    - batch=1 (long_500k) with kv_heads divisible -> seq is already
      dp-sharded; keep heads on `model` ("heads") — adding model to seq
      regressed gemma3 long_500k 180x.
    - batch=1, kv_heads NOT divisible -> seq over dp+model ("seq"):
      20-39x measured on qwen1.5 / qwen2-moe / whisper long_500k.
    """
    if kv_layout() != "seq" or "model" not in mesh.axis_names:
        return "heads"
    batch_shardable = batch % _dp_size(mesh) == 0
    if batch_shardable:
        return "seq"
    # batch=1: seq is already dp-sharded; if the *query* heads divide the
    # model axis, expanded-heads attention is fully local ("heads"); else
    # add model to the seq sharding ("seq").
    heads = q_heads or kv_heads
    if heads % mesh.shape["model"] == 0:
        return "heads"
    return "seq"


def cache_pspecs(cfg, batch: int, mesh: Mesh):
    """Cache sharding by leaf path: KV seq-sharded when batch can't fill the
    data axes (long_500k batch=1) — context parallelism for decode."""
    from repro.models import transformer

    dp = batch_axes(mesh)
    batch_shardable = batch % _dp_size(mesh) == 0
    bspec: Any = dp if batch_shardable else None
    seq_spec: Any = None if batch_shardable else dp
    if decode_kv_plan(batch, cfg.num_kv_heads, mesh, cfg.num_heads) == "seq":
        seq_spec = ("model",) if seq_spec is None else tuple(seq_spec) + ("model",)

    abstract = transformer.abstract_cache(cfg, batch, 16 * _dp_size(mesh))

    msize = mesh.shape["model"]

    def _div(dim: int) -> Optional[str]:
        return "model" if dim % msize == 0 else None

    def leaf_spec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = len(leaf.shape)
        if name in ("k", "v", "xk", "xv"):
            # (layers, B, S, K, hd): heads on model, falling back to head_dim
            # — unless the seq layout owns the model axis.
            s_ax = seq_spec if name in ("k", "v") else None
            seq_has_model = s_ax is not None and "model" in (
                s_ax if isinstance(s_ax, tuple) else (s_ax,))
            k_ax = None if seq_has_model else _div(leaf.shape[3])
            hd_ax = None if seq_has_model or k_ax is not None else _div(leaf.shape[4])
            return P(None, bspec, s_ax, k_ax, hd_ax)
        if name == "conv":
            # (layers, B, width-1, conv_dim)
            return P(None, bspec, None, _div(leaf.shape[3]))
        if name == "ssm":
            # (layers, B, H, N, P)
            return P(None, bspec, _div(leaf.shape[2]), None, None)
        if name == "C":
            return P(None, bspec, None, None, None)
        if name == "moe_rows":
            # (expert layers, n_held) counts, no batch axis
            return P()
        # n/m/c/h and other small states
        return P(*([None, bspec] + [None] * (nd - 2)))

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    leaves = [leaf_spec(path, leaf) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)
