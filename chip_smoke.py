"""Smoke run of CarbonEdge's main paths on one TPU chip.

    python chip_smoke.py

Everything runs in this one process (a chip serves one process at a
time), in order:

(a) device check: fails unless JAX's default backend is a TPU. There is no
    CPU fallback.
(b) scheduler: ``CarbonEdgeEngine`` with its default ``VectorizedPolicy``
    over a seeded 10^4-node heterogeneous fleet takes a few steps of 1024
    tasks with distinct (cpu, mem_mb) profiles, so the selection memo
    misses and the fused select kernel runs. Placements are checked
    against the float64 numpy backend on the same cluster state. Then a
    ``PartitionPolicy`` engine at P=32 cuts runs the joint kernel under
    the same check.
(c) serving: ``repro.launch.serve.main`` serves full-width Qwen3-1.7B
    (random weights from a seed), prompt length 128, routed by
    ``GreenRouter``. Checks that prefill lowers to the ``flash_attention``
    kernel and decode to ``decode_attention``, that every request gets its
    tokens, and that both kernels match their jnp references at the served
    shapes.

Timings printed are smoke timings (a first call that includes compilation,
then warm calls), not benchmark numbers. The last line is the JSON result;
any failure exits non-zero before printing it.
"""
from __future__ import annotations

import json
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.fleet_scale import make_fleet  # noqa: E402
from benchmarks.partition_scale import make_profile  # noqa: E402
from repro.core.api import CarbonEdgeEngine  # noqa: E402
from repro.core.policy import VectorizedPolicy, get_cache  # noqa: E402
from repro.core.scheduler import Task, node_feasible, scores  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.partition import PartitionPolicy  # noqa: E402
from repro.partition.policy import joint_time_energy  # noqa: E402
from repro.runtime import steps  # noqa: E402

SEED = 0
# The kernel scores in float32, the reference in float64: a placement may
# differ only where the two cells' float64 scores are within this.
TIE_EPS = 1e-5
# Attention kernels vs their jnp references: bf16 inputs and outputs, f32
# accumulation. Each element must satisfy |kernel - reference| <=
# ATTN_ATOL + ATTN_RTOL * |reference|; the relative part covers one bf16
# rounding step (2^-8 relative) of either side with room to spare.
ATTN_ATOL = ATTN_RTOL = 1e-2


def distinct_tasks(n: int, rng) -> list:
    cpu = rng.uniform(0.01, 0.5, n)
    mem = rng.uniform(8.0, 128.0, n)
    tasks = [Task(cpu=float(c), mem_mb=float(m), base_latency_ms=250.0)
             for c, m in zip(cpu, mem)]
    assert len({(t.cpu, t.mem_mb) for t in tasks}) == n
    return tasks


def cell_score64(cluster, task, node, weights, provider, cut=None) -> float:
    """Float64 Eq. 3 score of one (node[, (remote_frac, comm_s)]) cell, in
    the scalar oracles' arithmetic."""
    st = cluster.nodes[node]
    intensity = provider.intensity(node, 0.0)
    comp = scores(st, task, cluster.host_power_w, intensity=intensity)
    if cut is not None:
        t, e = joint_time_energy(st.avg_time_ms / 1000.0,
                                 st.power_w(cluster.host_power_w), *cut)
        comp[2] = 1.0 / (1.0 + t)
        comp[4] = 1.0 / (1.0 + intensity * e)
    return float(weights.as_array() @ comp)


def check_placements(label, got, ref, score64) -> None:
    """``got``/``ref``: per-task placement keys; ``score64(i, key)`` is the
    float64 score of task i's cell ``key``. Differences must be ties."""
    diff = [i for i, (g, r) in enumerate(zip(got, ref)) if g != r]
    gaps = []
    for i in diff:
        if got[i] is None or ref[i] is None:
            raise AssertionError(f"{label}: task {i} placed on {got[i]}, "
                                 f"reference {ref[i]}")
        gap = abs(score64(i, ref[i]) - score64(i, got[i]))
        if gap > TIE_EPS:
            raise AssertionError(f"{label}: task {i} placed on {got[i]}, "
                                 f"reference {ref[i]}, score gap {gap!r}")
        gaps.append(gap)
    print(f"{label}: {len(got)} placements, {len(got) - len(diff)} equal to "
          f"the float64 numpy backend, {len(diff)} ties within "
          f"{TIE_EPS} (largest gap {max(gaps, default=0.0)!r})")


def scheduler_phase(n_nodes=10_000, batch=1024, n_steps=3) -> None:
    rng = np.random.default_rng(SEED)
    eng = CarbonEdgeEngine(make_fleet(n_nodes, seed=SEED), batch_size=batch)
    backend = eng.policy._resolved_backend()
    print(f"scheduler: {type(eng.policy).__name__} resolved to {backend!r}")
    assert backend == "pallas", backend
    ref_pol = VectorizedPolicy(backend="numpy")
    times = []
    for k in range(n_steps):
        tasks = distinct_tasks(batch, rng)
        ref = ref_pol.select_batch(eng.cluster, tasks, eng.weights,
                                   provider=eng.provider)
        rev = get_cache(eng.cluster).data_rev
        t0 = time.perf_counter()
        got = [r.node for r in eng.submit_many(tasks).step()]
        times.append(time.perf_counter() - t0)
        # executing bills the nodes but moves no scored column, so the
        # reference saw the state the kernel scored
        assert get_cache(eng.cluster).data_rev == rev
        for t, node in zip(tasks, got):
            assert node_feasible(eng.cluster.nodes[node], t), node
        check_placements(
            f"engine step {k} (N={n_nodes}, B={batch})", got, ref,
            lambda i, node: cell_score64(eng.cluster, tasks[i], node,
                                         eng.weights, eng.provider))
    print(f"smoke timing, engine.step N={n_nodes} B={batch}: first call "
          f"{times[0]!r} s (includes compilation), warm "
          f"{[t * 1e3 for t in times[1:]]!r} ms")


def partition_phase(n_nodes=10_000, batch=16, cuts=32, n_steps=2) -> None:
    rng = np.random.default_rng(SEED + 1)
    prof = make_profile(cuts, seed=SEED)
    eng = CarbonEdgeEngine(make_fleet(n_nodes, seed=SEED),
                           policy=PartitionPolicy(prof))
    backend = eng.policy._resolved_backend()
    print(f"partition: PartitionPolicy resolved to {backend!r}")
    assert backend == "pallas", backend
    ref_pol = PartitionPolicy(prof, backend="numpy")
    rf, cs = prof.remote_frac(), prof.comm_seconds(eng.policy.link_mbps)
    times = []
    for k in range(n_steps):
        tasks = distinct_tasks(batch, rng)
        ref = ref_pol.decide_batch(eng.cluster, tasks, eng.weights,
                                   provider=eng.provider)
        rev = get_cache(eng.cluster).data_rev
        t0 = time.perf_counter()
        eng.submit_many(tasks).step()
        times.append(time.perf_counter() - t0)
        assert get_cache(eng.cluster).data_rev == rev
        key = [None if d is None else (d.node, d.cut_index)
               for d in eng.policy.last_decisions]
        check_placements(
            f"partition step {k} (N={n_nodes}, B={batch}, P={cuts})", key,
            [None if d is None else (d.node, d.cut_index) for d in ref],
            lambda i, c: cell_score64(eng.cluster, tasks[i], c[0],
                                      eng.weights, eng.provider,
                                      cut=(rf[c[1]], cs[c[1]])))
    print(f"smoke timing, partition step N={n_nodes} B={batch} P={cuts}: "
          f"first call {times[0]!r} s (includes compilation), warm "
          f"{[t * 1e3 for t in times[1:]]!r} ms")


def kernels_in(lowered_text: str) -> set:
    """Names of the Mosaic kernels a lowered program calls."""
    return set(re.findall(r'kernel_name = "(\w+)"', lowered_text))


def _timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def attention_vs_reference(cfg, batch, prompt_len, cache_len, pos) -> None:
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cap = cfg.attn_logit_softcap
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (batch, H, prompt_len, hd), bf)
    k = jax.random.normal(ks[1], (batch, K, prompt_len, hd), bf)
    v = jax.random.normal(ks[2], (batch, K, prompt_len, hd), bf)
    qd = jax.random.normal(ks[3], (batch, H, hd), bf)
    kd = jax.random.normal(ks[4], (batch, K, cache_len, hd), bf)
    vd = jax.random.normal(ks[5], (batch, K, cache_len, hd), bf)
    cases = {
        f"flash_attention q={q.shape} kv={k.shape}": (
            lambda: ops.flash_attention(q, k, v, causal=True, softcap=cap),
            lambda: ops.flash_attention_ref(q, k, v, causal=True,
                                            softcap=cap)),
        f"decode_attention q={qd.shape} kv={kd.shape} pos={pos}": (
            lambda: ops.decode_attention(qd, kd, vd, pos, softcap=cap),
            lambda: ops.decode_attention_ref(qd, kd, vd, pos, softcap=cap)),
    }
    for label, (kern, ref) in cases.items():
        out, first = _timed(kern)
        _, warm = _timed(kern)
        out = out.astype(jnp.float32)
        want = ref().astype(jnp.float32)
        err = jnp.abs(out - want)
        worst = float(jnp.max(err / (ATTN_ATOL + ATTN_RTOL * jnp.abs(want))))
        print(f"{label}: max |kernel - reference| {float(jnp.max(err))!r}, "
              f"{worst!r} of the bound {ATTN_ATOL} + {ATTN_RTOL}*|reference|")
        print(f"smoke timing, {label}: first call {first!r} s (includes "
              f"compilation), warm {warm * 1e3!r} ms")
        assert np.isfinite(worst) and worst <= 1.0, (label, worst)


def serving_phase(full_config=True, prompt_len=128, requests=8, max_new=16,
                  batch=4) -> None:
    argv = ["--arch", "qwen3-1.7b", "--prompt-len", str(prompt_len),
            "--requests", str(requests), "--max-new", str(max_new),
            "--batch-size", str(batch)]
    t0 = time.perf_counter()
    engine = serve.main(argv + (["--full-config"] if full_config else []))
    total = time.perf_counter() - t0
    cfg, cache_len = engine.cfg, engine.max_len
    print(f"serving: {cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"head_dim={cfg.head_dim} vocab={cfg.vocab_size}, "
          f"KV cache length {cache_len}")
    router_backend = engine.router.policy._resolved_backend()
    print(f"serving: GreenRouter policy {engine.router.policy.name!r} "
          f"resolved to {router_backend!r}")
    assert router_backend == "pallas", router_backend
    comps = sorted(engine.completions, key=lambda c: c.uid)
    counts = [len(c.tokens) for c in comps]
    print(f"serving: {len(comps)} requests completed, tokens per request "
          f"{counts}")
    assert len(comps) == requests and all(n == max_new for n in counts)
    assert all(0 <= t < cfg.vocab_size for c in comps for t in c.tokens)
    batches = [comps[i:i + batch] for i in range(0, requests, batch)]
    service = [max(c.service_s for c in b) for b in batches]
    print(f"smoke timing, serving {requests} requests x {max_new} tokens: "
          f"serve.main {total!r} s; per-batch service {service!r} s (the "
          f"first batch includes prefill and decode compilation)")

    params = transformer.abstract_params(cfg)
    toks = {"tokens": jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)}
    prefill = steps.prefill_step(cfg, cache_len)
    cache, _ = jax.eval_shape(prefill, params, toks)
    paths = {
        "prefill": kernels_in(jax.jit(prefill).lower(params, toks).as_text()),
        "decode": kernels_in(jax.jit(steps.decode_fn(cfg)).lower(
            params, cache, jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)).as_text()),
    }
    for phase, names in paths.items():
        print(f"serving: {phase} attention path: {sorted(names) or 'jnp'}")
    assert "flash_attention" in paths["prefill"], paths
    assert "decode_attention" in paths["decode"], paths
    attention_vs_reference(cfg, batch, prompt_len, cache_len,
                           pos=prompt_len + max_new - 2)


def main() -> int:
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}")
    if jax.default_backend() != "tpu":
        print("chip_smoke: JAX found no TPU; there is no CPU fallback",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    scheduler_phase()
    partition_phase()
    serving_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
