"""Per-architecture smoke tests: reduced variant (2 layers, d_model<=512,
<=4 experts), one forward + one train step on CPU; output shapes + no NaNs.
Decode shapes are exercised in test_decode_consistency.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_config, reduced_config
from repro.models import transformer
from repro.optim import adamw
from repro.runtime import steps

B, S = 2, 32


def make_batch(cfg, key, with_labels=True):
    st = S - cfg.vision_tokens
    tokens = jax.random.randint(key, (B, st), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if with_labels:
        batch["labels"] = jnp.roll(tokens, -1, axis=1)
    if cfg.encoder_layers:
        batch["encoder_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 1), (B, cfg.encoder_seq, cfg.d_model)) * 0.02
    if cfg.vision_tokens:
        batch["vision_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 2), (B, cfg.vision_tokens, cfg.d_model)) * 0.02
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nan(arch):
    cfg = reduced_config(arch)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1), with_labels=False)
    hidden, aux = transformer.forward(cfg, params, batch)
    assert hidden.shape == (B, S, cfg.d_model)
    assert not bool(jnp.any(jnp.isnan(hidden)))
    logits = transformer.unembed(cfg, params, hidden[:, -1])
    assert logits.shape == (B, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step(arch):
    cfg = reduced_config(arch)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1))
    step = steps.train_step(cfg, adamw.AdamWConfig(total_steps=4))
    opt = adamw.init(params)
    p2, o2, metrics = step(params, opt, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss)
    # sane loss near ln(vocab) at init
    assert 0.5 * np.log(cfg.vocab_size) < loss < 2.5 * np.log(cfg.vocab_size)
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.sum(jnp.abs(a - b))), params, p2))
    assert delta > 0
    # loss decreases over a few steps on repeated batch
    for _ in range(3):
        p2, o2, metrics = step(p2, o2, batch)
    assert float(metrics["loss"]) < loss


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The full configs carry the exact assigned hyperparameters."""
    spec = {
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "command-r-35b": (40, 8192, 64, 8, 22528, 256000),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936),
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
    }[arch]
    cfg = get_config(arch)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.d_ff, cfg.vocab_size) == spec
    assert len(cfg.layer_defs) == cfg.num_layers


def test_moe_configs():
    a = get_config("arctic-480b")
    assert a.moe.num_experts == 128 and a.moe.top_k == 2
    assert a.moe.dense_residual_ff == 4864
    q = get_config("qwen2-moe-a2.7b")
    assert q.moe.num_experts == 60 and q.moe.top_k == 4
    assert q.moe.num_shared_experts == 4


def test_param_counts_plausible():
    # arctic ~480B total; zamba2 ~2.7B; qwen3 ~1.7B-2B
    assert 4.0e11 < get_config("arctic-480b").param_count() < 5.5e11
    assert 2.0e9 < get_config("zamba2-2.7b").param_count() < 3.5e9
    assert 1.3e9 < get_config("qwen3-1.7b").param_count() < 2.3e9
    assert 3.0e8 < get_config("xlstm-350m").param_count() < 5.0e8
    # arctic active (top-2 of 128 + dense) is a small fraction of total
    a = get_config("arctic-480b")
    assert a.active_param_count() < 0.1 * a.param_count()
