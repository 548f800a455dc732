"""Jit-able train / prefill / decode step functions.

These are the functions the launcher jits with in/out shardings and the
dry-run lowers against ShapeDtypeStructs.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer
from repro.optim import adamw
from repro.runtime import loss as loss_mod


def loss_fn(cfg: ModelConfig, params, batch) -> Tuple[jnp.ndarray, Dict]:
    hidden, aux = transformer.forward(cfg, params, batch)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if cfg.vision_tokens:
        # Prepended stub vision positions are excluded from the LM loss.
        B = labels.shape[0]
        pad = jnp.zeros((B, cfg.vision_tokens), labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
        m = jnp.concatenate([jnp.zeros((B, cfg.vision_tokens), jnp.float32),
                             jnp.ones(batch["labels"].shape, jnp.float32)], axis=1)
        mask = m if mask is None else mask * m
    ce = loss_mod.chunked_ce(cfg, params, hidden, labels, mask)
    total = ce + aux
    return total, {"ce": ce, "aux": aux}


def train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig):
    def step(params, opt_state, batch):
        (total, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
        params, opt_state, opt_metrics = adamw.apply(opt_cfg, grads, opt_state, params)
        return params, opt_state, {**metrics, **opt_metrics, "loss": total}

    return step


# The inner functions' names name the jitted modules (``jit_prefill``,
# ``jit_decode``), which is how a profiler trace tells the two apart.
def prefill_step(cfg: ModelConfig, max_len: int):
    def prefill(params, batch):
        cache, last_h = transformer.prefill(cfg, params, batch, max_len)
        logits = transformer.unembed(cfg, params, last_h)
        return cache, logits

    return prefill


def decode_fn(cfg: ModelConfig):
    def decode(params, cache, token, pos):
        return transformer.decode_step(cfg, params, cache, token, pos)

    return decode


def greedy_sample(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
