"""Serving cells of a latent-attention, shared-expert MoE model
(DeepSeek-V2): ``paths/serving.py``'s cell, with the model, its weights
and its reference swapped.

The configuration file holds the published ``config.json`` keys at its
top level, with ``n_routed_experts`` cut to the experts this chip holds,
beside the harness's own keys (``HARNESS_KEYS``); ``deployment`` gives
the published count and the first expert held. The
program routes over all of them and adds the held experts' part (plus the
shared experts), as one chip of an expert-parallel deployment does; the
reference (``reference/deepseek_v2.py``) is given the same share.

The engine runs with an observability registry, so after each batch it
reads the cache's count of rows routed to held experts into
``serve.moe_rows_held``; each batch's record keeps its rows
(``moe_rows_held``), which the expert-layer metrics read.
"""
from __future__ import annotations

import sys
from typing import Dict

import numpy as np

from perfbench import traffic
from perfbench.paths import serving
from perfbench.reference import deepseek_v2

# The axes each matrix contracts with its input (the fan-in).
FAN_IN_AXES = {"wq": ("embed",), "wkv_a": ("embed",),
               "wkv_b": ("kv_latent",), "wo": ("heads", "head_dim"),
               "router": ("embed",), "w_up": ("embed",),
               "w_gate": ("embed",), "w_down": ("ff",)}


# The configuration file's keys that are the harness's; the others are
# the published ``config.json`` keys.
HARNESS_KEYS = frozenset({"name", "path", "source", "deployment", "serving",
                          "precision", "limits", "reduced", "assumed",
                          "guarantees"})


def published(config: Dict) -> Dict:
    """The published ``config.json`` keys of the configuration file."""
    return {k: v for k, v in config.items() if k not in HARNESS_KEYS}


def model_config(config: Dict):
    """The program's ``ModelConfig`` for the DeepSeek-V2 keys in the
    configuration file."""
    from repro.configs.base import (LayerDef, MLAConfig, ModelConfig,
                                    MoEConfig, YarnScaling)

    m, dep = published(config), config["deployment"]
    if m["model_type"] != "deepseek_v2":
        raise ValueError(f"no mapping for model_type {m['model_type']!r}")
    unsupported = {"q_lora_rank": None, "scoring_func": "softmax",
                   "topk_method": "greedy", "moe_layer_freq": 1,
                   "attention_bias": False}
    for k, want in unsupported.items():
        if m[k] != want:
            raise ValueError(f"{k}={m[k]!r}: the program has {want!r} only")
    rs = m["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r}")
    return ModelConfig(
        name=config["name"], arch_type="moe",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"],
        head_dim=m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
        prefix=(LayerDef("mla", dense=True),) * m["first_k_dense_replace"],
        pattern=(LayerDef("mla"),),
        mla=MLAConfig(kv_lora_rank=m["kv_lora_rank"],
                      qk_nope_head_dim=m["qk_nope_head_dim"],
                      qk_rope_head_dim=m["qk_rope_head_dim"],
                      v_head_dim=m["v_head_dim"]),
        rope_theta=float(m["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        max_position=m["max_position_embeddings"],
        moe=MoEConfig(
            num_experts=dep["n_routed_experts"], top_k=m["num_experts_per_tok"],
            expert_ff=m["moe_intermediate_size"],
            num_shared_experts=m["n_shared_experts"],
            norm_topk_prob=m["norm_topk_prob"],
            routed_scaling_factor=float(m["routed_scaling_factor"]),
            shared_gate=False, experts_held=m["n_routed_experts"],
            expert_offset=dep["expert_offset"]),
        tie_embeddings=m["tie_word_embeddings"], norm_eps=m["rms_norm_eps"],
        act=m["hidden_act"], dtype=m["torch_dtype"],
        param_dtype=m["torch_dtype"])


def make_weights(cfg, seed: int, norm_scale: float):
    """Random weights in the served dtype, made on the device in one jitted
    call from the seed, as ``paths/serving.make_weights`` makes them, with
    fan-in scales for the latent-attention and expert matrices (the router
    too, so its logits have unit scale)."""
    import jax
    import jax.numpy as jnp

    from repro.models import common, transformer

    spec = transformer.model_spec(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        spec, is_leaf=lambda x: isinstance(x, common.ParamSpec))
    shapes = []
    for path, p in flat:
        leaf = getattr(path[-1], "key", None)
        if leaf in FAN_IN_AXES:
            fan_in = int(np.prod([d for d, ax in zip(p.shape, p.axes)
                                  if ax in FAN_IN_AXES[leaf]]))
            scale = fan_in ** -0.5
        else:
            scale = p.scale if p.init == "normal" else norm_scale
        shapes.append((p.shape, scale))
    shapes = tuple(shapes)
    dtype = jnp.dtype(cfg.param_dtype)

    @jax.jit
    def make(words):
        key = jax.random.PRNGKey(0)
        for i in range(words.shape[0]):
            key = jax.random.fold_in(key, words[i])
        return [(jax.random.normal(jax.random.fold_in(key, i), shape,
                                   jnp.float32) * scale).astype(dtype)
                for i, (shape, scale) in enumerate(shapes)]

    words = np.array([seed >> s & 0xFFFFFFFF for s in (0, 32, 64)], np.uint32)
    return jax.tree_util.tree_unflatten(treedef, make(words))


class Cell(serving.Cell):
    def __init__(self, config: Dict, mix: Dict, seed: int, trace: bool,
                 seconds: float):
        from repro.core import costmodel, energy
        from repro.obs import Observability
        from repro.runtime.serving import ServingEngine

        # ``paths/serving.py`` and the readers find the model under "model"
        self.config = dict(config, model=published(config))
        self.mix, self.seed, self.trace = mix, seed, trace
        self.seconds = seconds
        sv = config["serving"]
        self.cfg = model_config(config)
        self.params = make_weights(self.cfg, seed, sv["norm_scale"])
        self.router = serving.timed_router(sv["pods"], sv["mode"])
        B = mix["batch"]
        terms = energy.roofline(
            2.0 * self.cfg.active_param_count() * B,
            costmodel.step_hbm_bytes(self.cfg,
                                     min(mix["prompt_len"]["values"]), B,
                                     "decode"),
            0.0, chips=sv["pods"][0]["chips"])
        self.router.seed_profile({p["name"]: terms for p in sv["pods"]})
        self.obs = Observability(metrics=True)
        self.eng = ServingEngine(self.cfg, self.params, self.router,
                                 max_len=traffic.max_context(mix) + 8,
                                 batch_size=B, obs=self.obs)
        self._stamps = {"prefill": None, "decode": []}
        self._wrap()
        self._uid = 0
        rng = traffic.rng_for(seed, "warm")
        for L in sorted(set(mix["prompt_len"]["values"])):
            prompts = rng.integers(0, self.cfg.vocab_size, (B, L),
                                   dtype=np.int32)
            self._batch(L, prompts, [2] * B)
        self.router.records.clear()

    def _held_rows(self) -> float:
        fam = self.obs.metrics.get("serve.moe_rows_held")
        return 0.0 if fam is None else fam.get()

    def _batch(self, L, prompts, outs) -> Dict:
        before = self._held_rows()
        b = super()._batch(L, prompts, outs)
        b["moe_rows_held"] = self._held_rows() - before
        return b

    def window(self, trace_dir=None) -> Dict:
        rec = super().window(trace_dir)
        rec["deployment"] = self.config["deployment"]
        rec["moe_rows_max"] = self.obs.metrics.get("serve.moe_rows_max").get()
        return rec

    def _token_gap(self, rec: Dict, control: bool) -> float:
        import jax.numpy as jnp

        tokens, positions, served = self._ref_inputs(self._picked(rec))
        gaps, _ = deepseek_v2.token_gaps(
            self.params, self.config["model"],
            self.config["deployment"]["expert_offset"], jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(np.maximum(served, 0)),
            control=control)
        valid = served >= 0
        print(f"reference: {len(tokens)} requests, {int(valid.sum())} served "
              f"tokens compared", file=sys.stderr)
        return float(np.max(np.where(valid, gaps, -np.inf)))
