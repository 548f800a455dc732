"""The decode step's in-place cache path against the per-layer scan path.

On one device ``transformer.decode_step`` carries the K/V and latent caches
through each layer scan and writes one position a layer; under a mesh it
scans each layer's cache in and out whole. Both compute the same values,
so on the CPU the carried path must return the same logits and caches bit
for bit.
"""
import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType

from repro.configs.registry import reduced_config
from repro.core.router import GreenRouter, PodSpec
from repro.kernels import mla_decode
from repro.models import transformer
from repro.obs import Observability
from repro.runtime import serving
from tests.test_archs_smoke import make_batch

SMAX, N_DEC = 48, 4
MESH = AbstractMesh((2, 2), ("data", "model"),
                    axis_types=(AxisType.Auto, AxisType.Auto))


def _decode(cfg, params, cache, tokens, start, in_place):
    """``N_DEC`` decode steps from ``cache`` with the path forced."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(transformer, "cache_in_place", lambda: in_place)
        step = jax.jit(functools.partial(transformer.decode_step, cfg))
        logits = []
        for t in range(N_DEC):
            lg, cache = step(params, cache, tokens[:, t:t + 1],
                             jnp.int32(start + t))
            logits.append(lg)
    return logits, cache


# a dense model, latent attention with a prefix stack and experts, a mamba2
# hybrid, xLSTM (nothing carried), sliding windows, cross-attention
@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v2-lite",
                                  "zamba2-2.7b", "xlstm-350m", "gemma3-27b",
                                  "whisper-base"])
def test_in_place_decode_equals_per_layer_scan(arch):
    cfg = reduced_config(arch)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1), with_labels=False)
    tokens = batch["tokens"]
    S = tokens.shape[1] - N_DEC
    cache, _ = transformer.prefill(cfg, params, dict(batch, tokens=tokens[:, :S]),
                                   SMAX)
    start = S + cfg.vision_tokens
    want_logits, want = _decode(cfg, params, cache, tokens[:, S:], start, False)
    got_logits, got = _decode(cfg, params, cache, tokens[:, S:], start, True)
    for a, b in zip(got_logits, want_logits):
        np.testing.assert_array_equal(a, b)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


def _scan_carries(cfg, mesh=None):
    """(carry shapes, ys shapes) of each layer scan of one decode step."""
    params = transformer.abstract_params(cfg)
    cache = transformer.abstract_cache(cfg, 4, 64)
    tok = jax.ShapeDtypeStruct((4, 1), jnp.int32)
    fn = lambda p, c, t: transformer.decode_step(cfg, p, c, t, jnp.int32(5))  # noqa: E731
    if mesh is None:
        jaxpr = jax.make_jaxpr(fn)(params, cache, tok)
    else:
        with jax.sharding.use_abstract_mesh(mesh):
            assert not transformer.cache_in_place()
            jaxpr = jax.make_jaxpr(fn)(params, cache, tok)
    out = []
    for e in jaxpr.jaxpr.eqns:
        if e.primitive.name == "scan":
            k, n = e.params["num_consts"], e.params["num_carry"]
            out.append(([v.aval.shape for v in e.invars[k:k + n]],
                        [v.aval.shape for v in e.outvars[n:]]))
    return out


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v2-lite"])
def test_mesh_decode_scans_each_layers_cache_whole(arch):
    """Under a mesh the layer scans carry the hidden state alone and the
    caches go through as xs/ys; on one device they ride in the carry."""
    cfg = reduced_config(arch)
    h = (4, 1, cfg.d_model)
    layer_cache = transformer.abstract_cache(cfg, 4, 64)["pattern"]["0"]
    stacked = {x.shape for x in jax.tree.leaves(layer_cache)}
    for carry, ys in _scan_carries(cfg, MESH):
        assert carry == [h]
        assert stacked <= set(ys)
    for carry, ys in _scan_carries(cfg):
        assert carry[0] == h and len(carry) > 1
        assert not stacked & set(ys)


@pytest.mark.parametrize("layer", [0, 2])
def test_mla_decode_kernel_reads_the_stack_at_its_layer(layer):
    key = jax.random.PRNGKey(layer)
    q = jax.random.normal(key, (4, 4, 80))
    stack = jax.random.normal(jax.random.fold_in(key, 1), (3, 4, 512, 80))
    kw = dict(scale=0.1, rank=64, interpret=True)
    got = mla_decode.mla_decode_attention(q, stack, jnp.int32(300),
                                          jnp.int32(layer), **kw)
    want = mla_decode.mla_decode_attention(q, stack[layer], jnp.int32(300),
                                           **kw)
    np.testing.assert_array_equal(got, want)


def _engine(cfg, obs=None):
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    router = GreenRouter([PodSpec("pod", chips=1, region="r",
                                  carbon_intensity=100.0)])
    return serving.ServingEngine(cfg, params, router, max_len=32,
                                 batch_size=2, obs=obs), params


def test_engine_donates_the_cache_and_reports_the_path():
    cfg = reduced_config("qwen3-1.7b")
    obs = Observability(metrics=True)
    eng, params = _engine(cfg, obs)
    assert obs.metrics.get("serve.decode_cache_in_place").get() == 1.0
    toks = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    cache, logits = eng._prefill(params, {"tokens": toks})
    given = jax.tree.leaves(cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    _, cache = eng._decode(params, cache, tok, jnp.int32(8))
    assert all(x.is_deleted() for x in given)
    assert not any(x.is_deleted() for x in jax.tree.leaves(cache))

    obs = Observability(metrics=True)
    with jax.sharding.use_abstract_mesh(MESH):
        _engine(cfg, obs)
    assert obs.metrics.get("serve.decode_cache_in_place").get() == 0.0


@pytest.mark.parametrize("arch,kernel,mesh,want", [
    ("qwen3-1.7b", True, False, 1.0),
    ("qwen3-1.7b", False, False, 0.0),
    ("qwen3-1.7b", True, True, 0.0),
    ("deepseek-v2-lite", True, False, 0.0),
])
def test_engine_reports_whether_decode_attention_reads_the_stack(
        monkeypatch, arch, kernel, mesh, want):
    """``serve.decode_attention_stacked`` follows the helper that steers
    ``attn_decode`` (forced here: the CPU takes the jnp path), reads 0
    under a mesh, and 0 for a model whose attention is all latent (MLA)."""
    from repro.kernels import ops
    from repro.models import attention

    monkeypatch.setattr(ops, "use_pallas", lambda: kernel)
    cfg = reduced_config(arch)
    obs = Observability(metrics=True)
    with (jax.sharding.use_abstract_mesh(MESH) if mesh
          else contextlib.nullcontext()):
        eng = serving.ServingEngine(cfg, None, None, max_len=32,
                                    batch_size=2, obs=obs)
        assert attention.decode_kernel_reads_stack(cfg, eng.max_len) is (
            kernel and not mesh)
    assert obs.metrics.get("serve.decode_attention_stacked").get() == want


# a dense GQA model, sliding windows with a softcap, a mamba2 hybrid's
# attention layers (H = K), cross-attention
@pytest.mark.parametrize("arch", ["qwen3-1.7b", "gemma3-27b", "zamba2-2.7b",
                                  "whisper-base"])
def test_decode_kernel_over_the_stack_equals_the_jnp_path(monkeypatch, arch):
    """With the Pallas path forced (the kernel in interpret mode on the
    CPU), each attention layer of the in-place decode step reads the
    stacked K/V cache by layer index in ``decode_attention``; its logits
    and caches match the jnp path's. Decode runs past gemma3's window of
    64, so the window masks part of the cache; each pattern position stacks
    two layers, so the layer index selects."""
    from repro.kernels import ops

    cfg = reduced_config(arch)
    cfg = dataclasses.replace(
        cfg, repeats=2 * cfg.repeats, num_layers=cfg.num_layers
        + len(cfg.pattern) * cfg.repeats)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    batch = make_batch(cfg, key, with_labels=False)
    tokens = jax.random.randint(key, (2, 100), 0, cfg.vocab_size)
    S = tokens.shape[1] - N_DEC
    cache, _ = transformer.prefill(cfg, params, dict(batch, tokens=tokens[:, :S]),
                                   256)
    runs = []
    for kernel in (False, True):
        monkeypatch.setattr(ops, "use_pallas", lambda: kernel)
        runs.append(_decode(cfg, params, cache, tokens[:, S:],
                            S + cfg.vision_tokens, True))
    (want_logits, want), (got_logits, got) = runs
    for a, b in zip(got_logits, want_logits):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
