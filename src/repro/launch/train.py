"""Training launcher: --arch <id> [--steps N] with reduced-config CPU mode.

On the production mesh this is the function the dry-run lowers; here it
actually runs (reduced or full config, per flags) with the data pipeline,
AdamW, checkpointing and carbon accounting.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import store
from repro.configs.registry import get_config, list_archs, reduced_config
from repro.core.carbon import CarbonMonitor
from repro.data.pipeline import DataConfig, make_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer
from repro.obs import console_logger
from repro.optim import adamw
from repro.runtime import steps

# Module-level logger (DESIGN.md §9): bare-message stream handler keeps the
# console output identical to the raw print() it replaces.
log = console_logger(__name__)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full architecture (needs accelerators)")
    ap.add_argument("--corpus", default=None)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--carbon-intensity", type=float, default=380.0)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full_config else reduced_config(args.arch)
    log.info("arch=%s layers=%d d_model=%d params~%.1fM",
             cfg.name, cfg.num_layers, cfg.d_model, cfg.param_count() / 1e6)

    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                                warmup_steps=max(1, args.steps // 10))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw.init(params)
    step_fn = jax.jit(steps.train_step(cfg, opt_cfg))

    monitor = CarbonMonitor()
    monitor.register_region("train", args.carbon_intensity)
    dcfg = DataConfig(seq_len=args.seq, global_batch=args.batch,
                      corpus=args.corpus)
    batches = make_batches(cfg, dcfg)

    t_start = time.perf_counter()
    for step in range(1, args.steps + 1):
        batch = {k: jnp.asarray(v) for k, v in next(batches).items()}
        t0 = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        # Bill the step: wall-clock x a CPU power estimate on this host.
        monitor.record_power_sample("train", dt, p_cpu_w=65.0, ram_gb=4.0)
        if step % args.log_every == 0 or step == 1:
            log.info("step %4d  loss %.4f  %7.1f ms  lr %.2e  gnorm %.3f",
                     step, loss, dt * 1e3, float(metrics["lr"]),
                     float(metrics["grad_norm"]))
    total = time.perf_counter() - t_start
    log.info("done %d steps in %.1fs; carbon %.4f gCO2 (%.3f Wh) at "
             "%.0f gCO2/kWh",
             args.steps, total, monitor.total_carbon_g(),
             monitor.total_energy_kwh() * 1e3, args.carbon_intensity)
    if args.checkpoint:
        store.save(args.checkpoint, params,
                   {"arch": cfg.name, "steps": args.steps})
        log.info("checkpoint -> %s", args.checkpoint)
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
