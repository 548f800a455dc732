"""Serving launcher: carbon-aware multi-pod inference (paper's deployment).

Simulates pods in three grid regions (the paper's node scenarios scaled to
pod granularity), routes batched requests via the NSA scheduler, and
reports per-region carbon. ``--mode`` picks the Table I weight profile.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.registry import get_config, list_archs, reduced_config
from repro.core import costmodel, energy
from repro.core.router import GreenRouter, PodSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer
from repro.obs import console_logger
from repro.runtime.serving import Request, ServingEngine

# Module-level logger (DESIGN.md §9): bare-message stream handler keeps the
# console output identical to the raw print() it replaces, while letting
# embedders re-route or silence the launcher through standard logging.
log = console_logger(__name__)

DEFAULT_PODS = [
    PodSpec("pod-high", chips=256, region="coal-heavy", carbon_intensity=620.0),
    PodSpec("pod-medium", chips=256, region="cn-average", carbon_intensity=530.0),
    PodSpec("pod-green", chips=256, region="hydro-rich", carbon_intensity=380.0),
]


def main(argv=None):
    """Serve the synthetic request mix; returns the :class:`ServingEngine`
    (its ``completions`` and ``report()``) for in-process callers."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="qwen3-1.7b")
    ap.add_argument("--mode", choices=["performance", "balanced", "green"],
                    default="green")
    ap.add_argument("--policy", choices=["vectorized", "scalar"],
                    default="vectorized",
                    help="scheduling policy: the batched vectorized/Pallas "
                         "path (default) or the scalar Algorithm-1 oracle")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--full-config", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full_config else reduced_config(args.arch)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    from repro.core.policy import VectorizedPolicy, WeightedScoringPolicy
    policy = (WeightedScoringPolicy() if args.policy == "scalar"
              else VectorizedPolicy())
    router = GreenRouter(DEFAULT_PODS, mode=args.mode, policy=policy)

    # Seed each pod's history with its compiled-step roofline time (identical
    # model on each pod here; heterogeneous pods would differ).
    flops = 2.0 * cfg.active_param_count() * args.batch_size
    hbm = costmodel.step_hbm_bytes(cfg, args.prompt_len, args.batch_size, "decode")
    terms = energy.roofline(flops, hbm, 0.0, chips=256)
    router.seed_profile({p.name: terms for p in DEFAULT_PODS})

    # ServingEngine rounds the cache up to the decode kernel's block
    engine = ServingEngine(cfg, params, router,
                           max_len=args.prompt_len + args.max_new + 8,
                           batch_size=args.batch_size)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=args.prompt_len).astype(np.int32)
        engine.submit(Request(uid=i, prompt=prompt, max_new_tokens=args.max_new))
    comps = engine.run_all()
    for c in comps[:4]:
        log.info("req %d: pod=%s latency=%.1fms carbon=%.3fugCO2 tokens=%s...",
                 c.uid, c.pod, c.latency_s * 1e3, c.carbon_g * 1e6,
                 c.tokens[:6])
    rep = engine.report()
    log.info("\ncompleted=%d total carbon %.4f mgCO2",
             rep["completed"], rep["carbon_g_total"] * 1e3)
    for region, acc in rep["per_region"].items():
        log.info("  %-12s tasks=%4d carbon=%.4f mgCO2 I=%.0f",
                 region, acc["tasks"], acc["carbon_g"] * 1e3,
                 acc["intensity"])
    return engine


if __name__ == "__main__":
    main()
