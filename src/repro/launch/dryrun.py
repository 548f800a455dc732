"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

MUST set the host-device-count flag before any other import (jax locks the
device count on first init).

Two passes per combination:

1. **full**   — the production step function (scans intact) is jit-lowered
   with in_shardings on the 16x16 (and 2x16x16) mesh and compiled. Success
   proves the distribution config is coherent; memory_analysis() proves the
   footprint; collective op *counts* summarise the schedule.

2. **account** — roofline accounting. HloCostAnalysis counts while-loop
   bodies once, so the step is re-lowered with structural scans unrolled at
   repeats r=1 and r=2 and extrapolated: cost(R) = c1 + (R-1)*(c2-c1).
   sLSTM's time recurrence (never unrolled) gets an analytic per-step
   correction. Collective bytes come from the partitioned HLO text
   (launch/hlo.py). This pass runs on the single-pod mesh (§Roofline).

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape decode_32k [--multipod]
  python -m repro.launch.dryrun --all [--multipod] [--skip-account]
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import numpy as np       # noqa: E402

from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig  # noqa: E402
from repro.configs.registry import get_config, list_archs  # noqa: E402
from repro.core import costmodel, energy  # noqa: E402
from repro.launch import hlo as hlo_mod  # noqa: E402
from repro.launch import specs as specs_mod  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import modes, transformer  # noqa: E402
from repro.obs import console_logger  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.runtime import steps  # noqa: E402
from repro.sharding import rules  # noqa: E402

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# Module-level logger (DESIGN.md §9): bare-message stream handler keeps the
# console output identical to the raw print() it replaces (StreamHandler
# flushes per record, preserving the old flush=True behaviour).
log = console_logger(__name__)


# ---------------------------------------------------------------------------
# Step construction
# ---------------------------------------------------------------------------


def build(cfg: ModelConfig, shape: InputShape, mesh):
    """Returns (fn, args, in_shardings)."""
    mode = "train" if shape.kind == "train" else "serve"
    pspec = rules.param_pspecs(cfg, mode, mesh)
    p_ns = rules.named(pspec, mesh)
    if shape.kind == "train":
        fn = steps.train_step(cfg, adamw.AdamWConfig())
        params, opt = specs_mod.abstract_train_state(cfg)
        batch = specs_mod.train_inputs(cfg, shape)
        b_ns = rules.named(rules.batch_pspecs(cfg, "train", shape.global_batch, mesh), mesh)
        o_ns = rules.named(rules.opt_pspecs(cfg, mesh), mesh)
        return fn, (params, opt, batch), (p_ns, o_ns, b_ns)
    if shape.kind == "prefill":
        fn = steps.prefill_step(cfg, shape.seq_len)
        params = transformer.abstract_params(cfg)
        batch = specs_mod.prefill_inputs(cfg, shape)
        b_ns = rules.named(rules.batch_pspecs(cfg, "prefill", shape.global_batch, mesh), mesh)
        return fn, (params, batch), (p_ns, b_ns)
    # decode
    fn = steps.decode_fn(cfg)
    params = transformer.abstract_params(cfg)
    cache, token, pos = specs_mod.decode_inputs(cfg, shape)
    c_ns = rules.named(rules.cache_pspecs(cfg, shape.global_batch, mesh), mesh)
    tok_spec = rules.batch_pspecs(cfg, "decode", shape.global_batch, mesh)["tokens"]
    t_ns = rules.named(tok_spec, mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P

    pos_ns = NamedSharding(mesh, P())
    return fn, (params, cache, token, pos), (p_ns, c_ns, t_ns, pos_ns)


def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch


def _slstm_correction(cfg: ModelConfig, shape: InputShape):
    """Analytic (flops, bytes) for sLSTM time steps not visible to
    cost_analysis (scan body counted once)."""
    if cfg.xlstm is None or shape.kind == "decode":
        return 0.0, 0.0
    from repro.models import xlstm as xl

    H, hd = xl.slstm_dims(cfg)
    n_sl = sum(1 for ld in cfg.layer_defs if ld.kind == "slstm")
    if not n_sl:
        return 0.0, 0.0
    B, S = shape.global_batch, shape.seq_len
    steps_missing = S - 1
    cell_flops = B * (4 * 2 * H * hd * hd + 20 * H * hd)
    cell_bytes = B * (8 * H * hd) * 4
    mult = 3.0 if shape.kind == "train" else 1.0  # fwd+bwd+remat
    return (mult * n_sl * steps_missing * cell_flops,
            mult * n_sl * steps_missing * cell_bytes)


# ---------------------------------------------------------------------------
# Passes
# ---------------------------------------------------------------------------


def _cost_items(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    return flops, byts


def full_pass(cfg, shape, multi_pod: bool):
    mesh = make_production_mesh(multi_pod=multi_pod)
    fn, args, in_sh = build(cfg, shape, mesh)
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        compiled = lowered.compile()
    t1 = time.time()
    mem = compiled.memory_analysis()
    mem_d = {}
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            mem_d[k] = getattr(mem, k, None)
    text = compiled.as_text()
    counts = hlo_mod.collective_counts(text)
    cbytes = hlo_mod.collective_bytes(text)
    flops, byts = _cost_items(compiled)
    return {
        "compile_s": round(t1 - t0, 2),
        "memory_analysis": mem_d,
        "collective_counts_static": counts,
        "collective_bytes_static_per_device": cbytes["total"],
        "flops_once_per_device": flops,
        "bytes_once_per_device": byts,
        "hlo_size_chars": len(text),
    }


def _acct_cfg(cfg: ModelConfig, r: int) -> ModelConfig:
    return dataclasses.replace(
        cfg, repeats=r,
        num_layers=len(cfg.prefix) + len(cfg.pattern) * r + len(cfg.suffix))


def _acct_metrics(cfg, shape, mesh):
    """(flops, bytes, coll_bytes, counts) for one unrolled lowering."""
    fn, args, in_sh = build(cfg, shape, mesh)
    with modes.unroll_scans():
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
            compiled = lowered.compile()
    flops, byts = _cost_items(compiled)
    text = compiled.as_text()
    cb = hlo_mod.collective_bytes(text)
    return flops, byts, cb["total"], hlo_mod.collective_counts(text)


# Quadratic sequence-extrapolation for combos whose fully-unrolled inner
# scans are too large to compile (zamba2 prefill_32k: 128 SSD chunks x
# layers). Step costs are polynomials of degree <= 2 in S (attention S^2,
# everything else linear), so a Lagrange fit through S/16, S/8, S/4 is
# exact: y(16x) = 56*y(x) - 90*y(2x) + 35*y(4x).
_S_EXTRAP_COEFF = (56.0, -90.0, 35.0)


def _needs_s_extrapolation(cfg, shape) -> bool:
    if shape.kind not in ("prefill", "train"):
        return False
    n_mamba = sum(1 for ld in cfg.layer_defs if ld.kind == "mamba2")
    if not n_mamba or cfg.ssm is None:
        return False
    chunks = shape.seq_len // cfg.ssm.chunk_size
    return chunks * min(n_mamba, 2 * len([1 for ld in cfg.pattern
                                          if ld.kind == "mamba2"])) > 256


def account_pass(cfg, shape):
    """Roofline accounting on the single-pod mesh."""
    mesh = make_production_mesh(multi_pod=False)
    chips = int(np.prod(list(mesh.shape.values())))
    extrap = _needs_s_extrapolation(cfg, shape)
    res = {}
    for r in (1, 2):
        c = _acct_cfg(cfg, r)
        if not extrap:
            res[r] = _acct_metrics(c, shape, mesh)
            continue
        ys = []
        for div in (16, 8, 4):
            s_small = dataclasses.replace(shape, name=f"{shape.name}@{div}",
                                          seq_len=shape.seq_len // div)
            ys.append(_acct_metrics(c, s_small, mesh))
        f = sum(k * y[0] for k, y in zip(_S_EXTRAP_COEFF, ys))
        b = sum(k * y[1] for k, y in zip(_S_EXTRAP_COEFF, ys))
        coll = sum(k * y[2] for k, y in zip(_S_EXTRAP_COEFF, ys))
        res[r] = (f, b, coll, ys[-1][3])
    R = cfg.repeats
    f = res[1][0] + (R - 1) * (res[2][0] - res[1][0])
    b = res[1][1] + (R - 1) * (res[2][1] - res[1][1])
    coll = res[1][2] + (R - 1) * (res[2][2] - res[1][2])
    f_corr, b_corr = _slstm_correction(cfg, shape)
    f += f_corr / chips
    b += b_corr / chips
    # Memory term: analytic fused-TPU HBM model (the CPU-backend HLO byte
    # count is unfused and overstates traffic 10-30x; kept as upper bound).
    hbm = costmodel.step_hbm_bytes(cfg, shape.seq_len, shape.global_batch,
                                   shape.kind)
    terms = energy.roofline(f * chips, hbm, coll * chips, chips)
    terms_upper = energy.roofline(f * chips, b * chips, coll * chips, chips)
    mf = model_flops(cfg, shape)
    return {
        "chips": chips,
        "hlo_flops_total": f * chips,
        "hlo_bytes_total_unfused": b * chips,
        "hbm_bytes_model": hbm,
        "collective_bytes_total": coll * chips,
        "roofline": terms.as_dict(),
        "memory_s_unfused_upper": terms_upper.memory_s,
        "model_flops": mf,
        "model_to_hlo_flops_ratio": mf / (f * chips) if f else None,
        "acct_r1": {"flops": res[1][0], "bytes": res[1][1], "coll": res[1][2]},
        "acct_r2": {"flops": res[2][0], "bytes": res[2][1], "coll": res[2][2]},
        "collective_counts_r2": res[2][3],
    }


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_combo(arch: str, shape_name: str, multi_pod: bool, skip_account: bool,
              out_dir: Path = RESULTS_DIR, tag: str = "") -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg0 = get_config(arch)
    cfg, swa = specs_mod.config_for_shape(cfg0, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "swa_variant": swa, "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    out["full"] = full_pass(cfg, shape, multi_pod)
    if not skip_account and not multi_pod:
        out["account"] = account_pass(cfg, shape)
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{arch}__{shape_name}__{mesh_name}{tag}.json"
    (out_dir / name).write_text(json.dumps(out, indent=1))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-account", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args()
    out_dir = Path(args.out)

    combos = ([(a, s) for a in list_archs() for s in INPUT_SHAPES]
              if args.all else [(args.arch, args.shape)])
    ok = fail = 0
    for arch, shape in combos:
        mesh_name = "2x16x16" if args.multipod else "16x16"
        f = out_dir / f"{arch}__{shape}__{mesh_name}.json"
        if args.skip_existing and f.exists():
            log.info("[skip] %s %s %s", arch, shape, mesh_name)
            continue
        t0 = time.time()
        try:
            r = run_combo(arch, shape, args.multipod, args.skip_account, out_dir)
            dt = time.time() - t0
            rt = r.get("account", {}).get("roofline", {})
            log.info("[ok]   %-18s %-12s %s  %7.1fs compile=%ss bottleneck=%s",
                     arch, shape, mesh_name, dt, r["full"]["compile_s"],
                     rt.get("bottleneck", "-"))
            ok += 1
        except Exception as e:  # noqa: BLE001
            dt = time.time() - t0
            log.error("[FAIL] %s %s %s after %.1fs: %s",
                      arch, shape, mesh_name, dt, e)
            traceback.print_exc()
            fail += 1
    log.info("done: %d ok, %d failed", ok, fail)
    return 0 if fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
