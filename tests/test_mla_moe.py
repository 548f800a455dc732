"""DeepSeek-V2-Lite's latent attention and held-expert layer against the
plain reference (``models/reference_mla_moe.py``), on the reduced config
with seeded random weights in float32, on the CPU.

Tolerances: the program and the reference compute the same float32
arithmetic in another order (scans, fused projections, sorted rows, an
online softmax in the kernels), so logits of order 1 agree to about 1e-6;
``ATOL`` 2e-4 leaves room for that and stays far under the 1e-2 by which
leaving out one expert's share moves a layer's output (checked in the
share test).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.configs.registry import get_config, reduced_config
from repro.core import costmodel
from repro.kernels import mla_decode, moe_gmm, ops, ref
from repro.models import mla, moe, reference_mla_moe as reference, transformer

ATOL = 2e-4


@pytest.fixture(scope="module")
def model():
    cfg = reduced_config("deepseek-v2-lite")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab_size)
    return cfg, params, tokens, reference.logits(cfg, params, tokens)


def test_reduced_config_keeps_the_mechanisms(model):
    cfg = model[0]
    assert [ld.dense for ld in cfg.layer_defs] == [True, False]
    assert cfg.mla is not None and cfg.rope_scaling is not None
    assert not cfg.moe.norm_topk_prob and not cfg.moe.shared_gate


def test_forward_matches_reference(model):
    cfg, params, tokens, want = model
    h, _ = transformer.forward(cfg, params, {"tokens": tokens})
    got = transformer.unembed(cfg, params, h)
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_prefill_then_decode_matches_reference(model):
    """Prefill 16 tokens, then 8 decode steps through the latent cache:
    logits at every position against the reference's full forward."""
    cfg, params, tokens, want = model
    cache, last = transformer.prefill(cfg, params, {"tokens": tokens[:, :16]}, 48)
    np.testing.assert_allclose(transformer.unembed(cfg, params, last),
                               want[:, 15], atol=ATOL)
    for t in range(16, 24):
        lg, cache = transformer.decode_step(cfg, params, cache,
                                            tokens[:, t:t + 1], jnp.int32(t))
        np.testing.assert_allclose(lg, want[:, t], atol=ATOL)


def test_prefill_in_row_chunks_matches_whole(model, monkeypatch):
    cfg, params, tokens, _ = model
    whole = transformer.prefill(cfg, params, {"tokens": tokens}, 32)
    monkeypatch.setattr(transformer, "PREFILL_ROW_TOKENS", 24)
    assert transformer._prefill_rows(cfg, {"tokens": tokens}) == 1
    chunked = transformer.prefill(cfg, params, {"tokens": tokens}, 32)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(chunked)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_absorbed_decode_matches_expanded(model):
    """One attention layer: decoding position S through the latent cache
    (W_uk in the query, W_uv on the output) equals expanded attention's
    output at S."""
    cfg, params, _, _ = model
    p = jax.tree.map(lambda a: a[0], params["pattern"]["0"])["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, cfg.d_model))
    full = mla.mla_forward(cfg, p, x)
    _, cache = mla.mla_prefill(cfg, p, x[:, :19], 32)
    y, cache = mla.mla_decode(cfg, p, x[:, 19:], cache, jnp.int32(19))
    np.testing.assert_allclose(y[:, 0], full[:, 19], atol=1e-5)
    assert float(jnp.abs(cache[:, 20:]).max()) == 0.0


@pytest.mark.parametrize("pos", [0, 255, 256, 300, 511])
def test_mla_decode_kernel_matches_jnp(pos):
    key = jax.random.PRNGKey(pos)
    q = jax.random.normal(key, (4, 4, 80))
    cache = jax.random.normal(jax.random.fold_in(key, 1), (4, 512, 80))
    got = mla_decode.mla_decode_attention(q, cache, jnp.int32(pos), scale=0.1,
                                          rank=64, interpret=True)
    want = ref.mla_decode_attention_ref(q, cache, pos, scale=0.1, rank=64)
    np.testing.assert_allclose(got, want, atol=1e-5)


def _layout(num_experts=8, held=4, offset=2, T=40, k=2, tm=16):
    m = MoEConfig(num_experts=num_experts, top_k=k, expert_ff=32,
                  experts_held=held, expert_offset=offset)
    idx = jnp.stack([jax.random.permutation(jax.random.PRNGKey(t), num_experts)[:k]
                     for t in range(T)])
    return m, idx, moe.held_layout(m, idx, tm)


def test_held_layout_places_every_held_row_once():
    m, idx, lay = _layout()
    local = np.asarray(idx).reshape(-1) - m.expert_offset
    held = (local >= 0) & (local < m.experts_held)
    dest = np.asarray(lay["dest"])
    assert (dest[~held] == lay["slots"]).all()
    assert len(set(dest[held])) == held.sum()
    np.testing.assert_array_equal(lay["rows"], np.bincount(local[held], minlength=4))
    tm = 16
    te = np.asarray(lay["tile_expert"])
    assert (te[dest[held] // tm] == local[held]).all()


def test_moe_gmm_kernel_matches_ragged_dot():
    """The kernel reads layer 1's experts from weights stacked over 3
    layers; its gradient is the oracle's."""
    m, idx, lay = _layout()
    key = jax.random.PRNGKey(9)
    D, F = 128, 128
    x = jax.random.normal(key, (lay["slots"], D))
    ws = [jax.random.normal(jax.random.fold_in(key, i), s) * 0.1
          for i, s in enumerate([(3, 4, D, F), (3, 4, D, F), (3, 4, F, D)])]
    layer = jnp.array([1], jnp.int32)
    got = moe_gmm.moe_gmm(x, *ws, lay["tile_expert"], lay["n_valid"], layer,
                          tm=16, interpret=True)
    want = ref.moe_gmm_ref(x, *[w[1] for w in ws], lay["sizes"])
    n = int(lay["n_valid"][0]) * 16
    np.testing.assert_allclose(got[:n], want[:n], atol=1e-4)
    args = (lay["sizes"], lay["tile_expert"], lay["n_valid"], layer, 16)
    g = jax.grad(lambda x, w: ops.moe_gmm(x, w, *ws[1:], *args)[:n].sum(),
                 argnums=(0, 1))(x, ws[0])
    g_ref = jax.grad(lambda x, w: ref.moe_gmm_ref(
        x, w[1], ws[1][1], ws[2][1], lay["sizes"])[:n].sum(), argnums=(0, 1))(x, ws[0])
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-4)


def _moe_layer(cfg, seed=0):
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed))
    return jax.tree.map(lambda a: a[0], params["pattern"]["0"])["moe"]


def test_adversarial_routing_drops_no_token(model):
    """Every token's top expert is expert 0: its rows far exceed what a
    capacity bucket (1.25x the even share) holds, and the layer still gives
    the reference's answer."""
    cfg = model[0]
    p = _moe_layer(cfg)
    p["router"] = p["router"].at[:, 0].add(3.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.d_model)))
    y, _, rows = moe.moe_apply(cfg, p, x)
    assert int(rows[0]) == 64 > moe._capacity(64, cfg.moe.top_k, cfg.moe.num_experts)
    want = reference.moe_layer(cfg, p, x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(y.reshape(-1, cfg.d_model), want, atol=1e-5)


def test_held_shares_add_up_to_the_whole_layer(model):
    """Four chips each hold 2 of 8 experts: their layer outputs, with the
    shared experts (which every chip computes alike) counted once, add up
    to the uncut reference layer."""
    cfg = model[0]
    whole = cfg.with_overrides(moe=dataclasses.replace(cfg.moe, num_experts=8))
    p = _moe_layer(whole, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, cfg.d_model))
    want = reference.moe_layer(whole, p, x.reshape(-1, cfg.d_model))
    shared = reference._swiglu(x.reshape(-1, cfg.d_model), p["shared"])
    total = -3 * shared
    for chip in range(4):
        share = whole.with_overrides(moe=dataclasses.replace(
            whole.moe, experts_held=2, expert_offset=2 * chip))
        ps = dict(p, **{k: p[k][2 * chip:2 * chip + 2]
                        for k in ("w_gate", "w_up", "w_down")})
        y, _, rows = moe.moe_apply(share, ps, x)
        assert rows.shape == (2,)
        total = total + y.reshape(-1, cfg.d_model)
    np.testing.assert_allclose(total, want, atol=1e-5)
    # one share alone is not the whole layer
    assert float(jnp.abs(y.reshape(-1, cfg.d_model) - want).max()) > 1e-2


def test_cache_counts_rows_routed_to_held_experts(model):
    cfg, params, tokens, _ = model
    cache, _ = transformer.prefill(cfg, params, {"tokens": tokens[:, :16]}, 48)
    assert cache["moe_rows"].shape == (1, cfg.moe.n_held)
    assert int(cache["moe_rows"].sum()) == 2 * 16 * cfg.moe.top_k
    _, cache = transformer.decode_step(cfg, params, cache, tokens[:, 16:17],
                                       jnp.int32(16))
    assert int(cache["moe_rows"].sum()) == 2 * 17 * cfg.moe.top_k


def test_serving_engine_reads_the_counter_once_per_batch():
    from repro.core.router import GreenRouter, PodSpec
    from repro.obs import Observability
    from repro.runtime.serving import Request, ServingEngine

    cfg = reduced_config("deepseek-v2-lite")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    obs = Observability(metrics=True, profile=True)
    router = GreenRouter([PodSpec("pod", chips=1, region="r",
                                  carbon_intensity=100.0)])
    eng = ServingEngine(cfg, params, router, max_len=32, batch_size=2, obs=obs)
    for uid in range(2):
        eng.submit(Request(uid, np.arange(8, dtype=np.int32) + uid,
                           max_new_tokens=4))
    eng.run_batch()
    held = obs.metrics.get("serve.moe_rows_held").get()
    assert held == 2 * (8 + 3) * cfg.moe.top_k
    assert 0 < obs.metrics.get("serve.moe_rows_max").get() <= held
    assert obs.profiler.count("serve.moe_counts") == 1


def test_qwen3_is_untouched_by_the_new_options():
    """Qwen3-1.7B carries no latent cache and no counter, and bills as
    before (the parent's numbers)."""
    cfg = get_config("qwen3-1.7b")
    cache = transformer.abstract_cache(cfg, 8, 1280)
    assert set(cache) == {"pattern"}
    assert {k: v.shape for k, v in cache["pattern"]["0"].items()} == {
        "k": (28, 8, 1280, 8, 128), "v": (28, 8, 1280, 8, 128)}
    assert cfg.param_count() == cfg.active_param_count() == 1720572928
    assert costmodel.step_hbm_bytes(cfg, 1024, 8, "decode") == 4405716992.0
    assert costmodel.step_hbm_bytes(cfg, 1024, 8, "prefill") == 25994586112.0


def test_deepseek_bills_the_latent_cache_and_held_experts():
    from repro.configs import deepseek_v2_lite

    full = get_config("deepseek-v2-lite")
    share = deepseek_v2_lite.make_config(experts_held=16)
    D, F, E = 2048, 1408, 64
    expert = 3 * D * F
    assert full.param_count() - share.param_count() == 26 * 48 * expert
    # a token meets 6 of 64 experts, 1.5 of them held here
    assert full.active_param_count() - share.active_param_count() == \
        26 * (6 - 1.5) * expert
    # the decode step reads the latent cache (576 values a position)...
    ctx, B = 1152, 64
    cache = costmodel._cache_bytes(share, ctx, B)
    assert cache == 27 * B * ctx * 576 * 2
    # ...and the held experts its 64 rows touch, not one token's share:
    # a batch of one reads 1.5 experts' weights a layer in expectation
    touched = 16 * (1 - (1 - 6 / E) ** B)
    assert 15.9 < touched < 16
    one = costmodel.step_hbm_bytes(share, ctx, 1, "decode")
    hbm = costmodel.step_hbm_bytes(share, ctx, B, "decode")
    weights = 2 * (share.active_param_count() + 26 * expert * (touched - 1.5))
    assert weights < hbm - cache < weights + 0.5e9   # + activations, logits
    assert 2 * share.active_param_count() < one < 2 * share.active_param_count() + 0.1e9
