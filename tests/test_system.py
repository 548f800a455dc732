"""End-to-end behaviour tests: train loop learns; serving engine routes,
generates and accounts carbon; the full CarbonEdge story in one pass."""
import jax
import jax.numpy as jnp
import numpy as np
from repro.configs.registry import reduced_config
from repro.core import costmodel, energy
from repro.core.router import GreenRouter, PodSpec
from repro.data.pipeline import DataConfig, synthetic_batches
from repro.models import transformer
from repro.optim import adamw
from repro.runtime import steps
from repro.runtime.serving import Request, ServingEngine


def test_training_learns():
    """~60 steps on structured synthetic data: loss must drop >= 1 nat."""
    cfg = reduced_config("qwen3-1.7b")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    opt_cfg = adamw.AdamWConfig(lr=2e-3, total_steps=100, warmup_steps=5)
    opt = adamw.init(params)
    step = jax.jit(steps.train_step(cfg, opt_cfg))
    batches = synthetic_batches(cfg, DataConfig(seq_len=64, global_batch=8))
    losses = []
    for i in range(100):
        batch = {k: jnp.asarray(v) for k, v in next(batches).items()}
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert losses[0] > losses[-1] + 0.8, (losses[0], losses[-1])
    assert np.isfinite(losses).all()


PODS = [
    PodSpec("pod-high", 256, "coal-heavy", 620.0),
    PodSpec("pod-medium", 256, "cn-average", 530.0),
    PodSpec("pod-green", 256, "hydro-rich", 380.0),
]


def _engine(mode, obs=None):
    cfg = reduced_config("qwen3-1.7b")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    router = GreenRouter(PODS, mode=mode)
    flops = 2.0 * cfg.active_param_count() * 2
    hbm = costmodel.step_hbm_bytes(cfg, 16, 2, "decode")
    terms = energy.roofline(flops, hbm, 0.0, 256)
    router.seed_profile({p.name: terms for p in PODS})
    eng = ServingEngine(cfg, params, router, max_len=32, batch_size=2,
                        obs=obs)
    return cfg, eng


def test_serving_green_routing_and_accounting():
    cfg, eng = _engine("green")
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.submit(Request(uid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=12).astype(np.int32), max_new_tokens=3))
    comps = eng.run_all()
    assert len(comps) == 4
    assert all(c.pod == "pod-green" for c in comps)
    assert all(len(c.tokens) == 3 for c in comps)
    assert all(0 <= t < cfg.vocab_size for c in comps for t in c.tokens)
    rep = eng.report()
    assert rep["completed"] == 4
    assert rep["carbon_g_total"] > 0
    assert rep["per_region"]["pod-green"]["tasks"] > 0
    assert rep["per_region"]["pod-high"]["tasks"] == 0


def test_serving_cache_rounds_to_decode_block_without_changing_tokens():
    """The KV cache rounds up to the decode kernel's block (so decode can
    take the Pallas kernel on a TPU); slots past the decode position are
    masked, so the tokens equal those of a cache of the requested length."""
    from repro.kernels.decode_attention import BLOCK_K

    cfg, eng = _engine("green")                    # requests max_len=32
    assert eng.max_len == BLOCK_K
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, size=(2, 12)).astype(np.int32)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    got = [c.tokens for c in sorted(eng.run_all(), key=lambda c: c.uid)]

    cache, logits = jax.jit(steps.prefill_step(cfg, 32))(
        eng.params, {"tokens": jnp.asarray(prompts)})
    decode = jax.jit(steps.decode_fn(cfg))
    tok = steps.greedy_sample(logits)[:, None]
    want = [tok[:, 0]]
    for t in range(3):
        logits, cache = decode(eng.params, cache, tok, jnp.int32(12 + t))
        tok = steps.greedy_sample(logits)[:, None]
        want.append(tok[:, 0])
    assert got == np.stack(want, axis=1).tolist()


def test_serving_phases_per_batch_and_token():
    """An attached profiler gets one route and prefill per batch and, per
    token, the host's wait; per decode the dispatch, sampling and one
    billing per model step."""
    from repro.obs import Observability, StepProfiler

    prof = StepProfiler()
    cfg, eng = _engine("green", obs=Observability(profile=prof))
    rng = np.random.default_rng(2)
    for i in range(2):
        eng.submit(Request(uid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=12).astype(np.int32), max_new_tokens=4))
    assert len(eng.run_batch()) == 2
    counts = {ph: prof.count(ph) for ph in prof.phases()
              if ph.startswith("serve.")}
    assert counts == {"serve.route": 1, "serve.prefill": 1,
                      "serve.sync": 4, "serve.decode": 3,
                      "serve.sample": 4, "serve.bill": 4}


def test_prefill_and_decode_modules_are_named():
    """The two jitted steps lower as ``jit_prefill`` and ``jit_decode``, so
    a profiler trace tells them apart by name."""
    cfg, eng = _engine("green")
    toks = jnp.zeros((2, 12), jnp.int32)
    pre = jax.jit(steps.prefill_step(cfg, eng.max_len)).lower(
        eng.params, {"tokens": toks})
    cache, _ = jax.eval_shape(steps.prefill_step(cfg, eng.max_len),
                              eng.params, {"tokens": toks})
    dec = jax.jit(steps.decode_fn(cfg)).lower(
        eng.params, cache, jnp.zeros((2, 1), jnp.int32), jnp.int32(12))
    assert "module @jit_prefill" in pre.as_text()
    assert "module @jit_decode" in dec.as_text()


def test_green_pod_availability_changes_carbon():
    """Same workload with the green pod saturated (load filter, Algorithm 1
    line 3) must emit more carbon — and the ratio must follow the grid
    intensities exactly (identical work, different region)."""
    totals = {}
    pods_used = {}
    for scenario in ("green-free", "green-busy"):
        cfg, eng = _engine("green")
        if scenario == "green-busy":
            eng.router.cluster.nodes["pod-green"].load = 0.9
            eng.router.cluster.nodes["pod-medium"].load = 0.9
        rng = np.random.default_rng(0)
        for i in range(2):
            eng.submit(Request(uid=i, prompt=rng.integers(
                0, cfg.vocab_size, size=12).astype(np.int32), max_new_tokens=2))
        eng.run_all()
        totals[scenario] = eng.report()["carbon_g_total"]
        pods_used[scenario] = {r for c in eng.completions for r in [c.pod]}
    assert pods_used["green-free"] == {"pod-green"}
    assert pods_used["green-busy"] == {"pod-high"}
    np.testing.assert_allclose(totals["green-free"] / totals["green-busy"],
                               380.0 / 620.0, rtol=0.05)


def test_greedy_decode_deterministic():
    cfg, eng = _engine("green")
    prompt = np.arange(12, dtype=np.int32) % cfg.vocab_size
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
    a = eng.run_all()[0].tokens
    cfg2, eng2 = _engine("green")
    eng2.submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
    b = eng2.run_all()[0].tokens
    assert a == b


def test_compile_cache_placement(monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing overrides it; otherwise
    the cache goes to one fixed path inside the checkout."""
    from pathlib import Path

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
