"""Joint partition+placement scheduling at fleet scale (DESIGN.md §8).

Sweeps fleet size N x batch size B x candidate cuts P and times:

- **joint select** — ``PartitionPolicy.decide_batch`` over the (B, P, N)
  decision plane (numpy column path, selection memo off so the rows
  measure the scoring pass), with bit-exact parity against the cut-major
  scalar oracle asserted on a sampled sub-batch;
- **step** — the END-TO-END ``CarbonEdgeEngine.step`` with a
  ``PartitionPolicy`` (select + effective-latency execute + bill): the
  paper's 0.03 ms/task budget for the whole joint decision, measured at
  the production defaults (feature cache + selection memo + batched
  execute). The acceptance row is N=10^4, B=1024, P=32;
- **risk planning** — ``plan_wake_risk_batch`` (two interval grid reads)
  vs the point-forecast ``plan_wake_batch``, plus the never-defer
  invariant re-checked against raw provider reads;
- **conformal** — split-conformal intensity calibration on noisy
  synthetic traces: held-out coverage at the 90% target (gate asserts
  >= 0.87).

Writes ``BENCH_partition.json``. The CI smoke runs ``run(smoke=True)``;
gate assertions live in ``benchmarks/ci_gates.py``
(``python -m benchmarks.ci_gates partition``).
"""
from __future__ import annotations

import json
import time
from typing import Dict, List

import numpy as np

from benchmarks.fleet_scale import PAPER_PER_TASK_MS, _time, make_fleet, make_tasks
from repro.core.api import CarbonEdgeEngine
from repro.core.providers import (ForecastProvider, StaticProvider,
                                  TraceProvider, intensity_interval_batch)
from repro.core.scheduler import MODES
from repro.core.temporal import (DeferrableTask, plan_wake_batch,
                                 plan_wake_risk_batch, synthetic_trace)
from repro.partition import (ConformalProvider, PartitionPolicy, SplitConformal,
                             calibrate_intensity, profile_costs,
                             select_joint_scalar)

FULL_NS = (1_000, 10_000)
FULL_BS = (256, 1024)
FULL_PS = (8, 32)
SMOKE_NS = (512, 2_048)
SMOKE_BS = (64,)
SMOKE_PS = (8,)


def make_profile(p: int, seed: int = 0):
    """Synthetic per-layer costs/boundaries yielding exactly ``p`` cuts."""
    rng = np.random.default_rng(seed)
    costs = rng.uniform(1.0, 20.0, p)
    bb = np.append(rng.uniform(1e4, 1e6, p - 1), 0.0)
    return profile_costs(costs, boundary_bytes=bb, name=f"synth{p}")


def bench_joint_select(cluster, tasks, prof, *, reps: int) -> Dict:
    w = MODES["green"]
    provider = StaticProvider.from_cluster(cluster)
    pol = PartitionPolicy(prof, backend="numpy", use_select_memo=False)
    names = list(cluster.nodes)

    def step():
        # dirty a handful of nodes between steps, like a live engine would
        for nm in names[:8]:
            cluster.nodes[nm].running += 1
            cluster.nodes[nm].running -= 1
        return pol.decide_batch(cluster, tasks, w, provider)

    joint_s = _time(step, reps)
    # bit-exact parity with the cut-major scalar oracle on a sample
    sample = tasks[:: max(1, len(tasks) // 8)]
    got = pol.decide_batch(cluster, sample, w, provider)
    parity_ok = True
    for t, d in zip(sample, got):
        ref = select_joint_scalar(cluster, t, prof, w, provider=provider)
        ok = ((d is None and ref is None)
              or (d is not None and ref is not None
                  and (d.node, d.cut, d.score)
                  == (ref.node, ref.cut, ref.score)))
        parity_ok = parity_ok and ok
    b = len(tasks)
    return {
        "n_nodes": len(names), "batch": b, "cuts": prof.num_cuts,
        "joint_step_ms": joint_s * 1e3,
        "joint_per_task_ms": joint_s * 1e3 / b,
        "joint_tasks_per_sec": b / joint_s,
        "parity_ok": parity_ok,
        "paper_per_task_ms": PAPER_PER_TASK_MS,
    }


def bench_step(n: int, b: int, p: int, *, reps: int, seed: int = 0) -> Dict:
    """End-to-end ``engine.step`` with a PartitionPolicy at production
    defaults, plus bit-exact parity of the two execute paths under the
    effective-latency hook."""
    prof = make_profile(p, seed=seed)

    def run_path(batch_execute: bool, reps: int) -> float:
        eng = CarbonEdgeEngine(make_fleet(n, seed=seed),
                               policy=PartitionPolicy(prof, backend="numpy"),
                               batch_execute=batch_execute)
        tasks = make_tasks(b, seed=seed)
        eng.submit_many(tasks)
        eng.step()                         # warm (cache + memo fill)
        best = float("inf")
        for _ in range(reps):
            eng.submit_many(tasks)
            t0 = time.perf_counter()
            eng.step()
            best = min(best, time.perf_counter() - t0)
        return best

    batched_s = run_path(True, reps)
    ea = CarbonEdgeEngine(make_fleet(n, seed=seed),
                          policy=PartitionPolicy(prof, backend="numpy"),
                          batch_execute=False)
    eb = CarbonEdgeEngine(make_fleet(n, seed=seed),
                          policy=PartitionPolicy(prof, backend="numpy"),
                          batch_execute=True)
    tasks = make_tasks(b, seed=seed)
    ra = ea.submit_many(tasks).step()
    rb = eb.submit_many(tasks).step()
    exec_parity = (ra == rb and ea.cluster.log == eb.cluster.log
                   and ea.monitor.report() == eb.monitor.report())
    return {
        "n_nodes": n, "batch": b, "cuts": p,
        "step_ms": batched_s * 1e3,
        "per_task_ms": batched_s * 1e3 / b,
        "tasks_per_sec": b / batched_s,
        "exec_path_parity": exec_parity,
        "paper_per_task_ms": PAPER_PER_TASK_MS,
        "vs_paper_x": (batched_s * 1e3 / b) / PAPER_PER_TASK_MS,
    }


def bench_risk_planning(n: int, *, reps: int, seed: int = 0,
                        sigma: float = 0.5) -> Dict:
    """``sigma`` is the forecast residual spread the conformal band is
    calibrated from: tight bands (default) certify most of the point
    planner's deferrals, wide bands (see the ``sigma=20`` row) make the
    planner abstain — both must satisfy the never-defer invariant."""
    cluster = make_fleet(n, seed=seed)
    rng = np.random.default_rng(seed)
    traces = {nm: synthetic_trace(nm, st.spec.carbon_intensity, seed=i % 16)
              for i, (nm, st) in enumerate(cluster.nodes.items())}
    base = TraceProvider(traces)
    prov = ConformalProvider(base, SplitConformal(rng.normal(0, sigma, 200)))
    tasks = [DeferrableTask(cpu=0.05, mem_mb=16.0,
                            deadline_hours=float(rng.uniform(2.0, 12.0)),
                            duration_hours=0.5) for _ in range(64)]
    # morning submit: the midday solar dip is inside the longer deadlines,
    # so risk planning has genuine deferrals to certify
    now = 8.0
    point_s = _time(lambda: plan_wake_batch(prov, cluster, tasks, now), reps)
    risk_s = _time(lambda: plan_wake_risk_batch(prov, cluster, tasks, now),
                   reps)
    # never-defer invariant, re-derived from raw provider interval reads
    wakes = plan_wake_risk_batch(prov, cluster, tasks, now)
    names = list(cluster.nodes)
    invariant_ok = True
    for t, wk in zip(tasks, wakes):
        if wk == now:
            continue
        lo0, _ = intensity_interval_batch(prov, names, now)
        _, hi_w = intensity_interval_batch(prov, names, float(wk))
        invariant_ok = invariant_ok and \
            float(np.min(hi_w)) < float(np.min(lo0))
    return {
        "n_nodes": n, "tasks": len(tasks), "sigma": sigma,
        "point_ms": point_s * 1e3,
        "risk_ms": risk_s * 1e3,
        "risk_overhead_x": risk_s / point_s,
        "deferred": int(np.sum(wakes > now)),
        "invariant_ok": invariant_ok,
    }


def bench_conformal(seed: int = 0) -> Dict:
    """Held-out interval coverage of split-conformal intensity calibration
    on noisy duck-curve traces (nominal 90%)."""
    regions = [("coal-heavy", 620.0), ("cn-average", 530.0),
               ("hydro-rich", 380.0), ("solar-mix", 450.0)]
    actual = TraceProvider({r: synthetic_trace(r, b, noise=0.08,
                                               seed=seed + i)
                            for i, (r, b) in enumerate(regions)})
    forecast = ForecastProvider(
        TraceProvider({r: synthetic_trace(r, b) for r, b in regions}),
        smoothing_hours=2.0)
    names = [r for r, _ in regions]
    cal_hours = np.arange(0.0, 24.0, 0.25)
    sc = calibrate_intensity(forecast, actual, names, cal_hours)
    prov = ConformalProvider(forecast, sc)
    test_hours = np.arange(0.125, 24.0, 0.25)     # held-out offsets
    lo, hi = prov.intensity_interval_batch(names, test_hours, coverage=0.9)
    truth = actual.intensity_batch(names, test_hours)
    coverage = float(np.mean((truth >= lo) & (truth <= hi)))
    return {
        "nominal": 0.9,
        "heldout_coverage": coverage,
        "quantile_g_per_kwh": sc.quantile(0.9),
        "calibration_points": sc.n,
    }


def run(smoke: bool = False, out_path: str = "BENCH_partition.json") -> Dict:
    ns = SMOKE_NS if smoke else FULL_NS
    bs = SMOKE_BS if smoke else FULL_BS
    ps = SMOKE_PS if smoke else FULL_PS
    select_rows, step_rows, risk_rows = [], [], []
    for n in ns:
        cluster = make_fleet(n)
        for p in ps:
            prof = make_profile(p)
            for b in bs:
                reps = 20 if n * p <= 100_000 else 5
                row = bench_joint_select(cluster, make_tasks(b), prof,
                                         reps=reps)
                select_rows.append(row)
                print(f"joint  N={n:>6} B={b:>5} P={p:>3}: "
                      f"{row['joint_step_ms']:8.3f} ms "
                      f"({row['joint_per_task_ms']*1e3:8.2f} us/task, "
                      f"parity={'ok' if row['parity_ok'] else 'FAIL'})")
    for n in ns:
        for p in ps:
            b = max(bs)
            row = bench_step(n, b, p, reps=10 if n <= 10_000 else 3)
            step_rows.append(row)
            print(f"step   N={n:>6} B={b:>5} P={p:>3}: "
                  f"{row['step_ms']:8.3f} ms "
                  f"({row['per_task_ms']*1e3:8.2f} us/task, paper budget "
                  f"{PAPER_PER_TASK_MS*1e3:.0f} us, "
                  f"exec parity={'ok' if row['exec_path_parity'] else 'FAIL'})")
    for n in ns:
        for sigma in (0.5, 20.0):          # calibrated-tight vs sloppy band
            row = bench_risk_planning(n, reps=10 if n <= 10_000 else 3,
                                      sigma=sigma)
            risk_rows.append(row)
            print(f"risk   N={n:>6} s={sigma:>4}: point "
                  f"{row['point_ms']:8.3f} ms  risk {row['risk_ms']:8.3f} ms"
                  f" ({row['risk_overhead_x']:.2f}x, "
                  f"{row['deferred']}/{row['tasks']} deferred, "
                  f"invariant={'ok' if row['invariant_ok'] else 'FAIL'})")
    conf = bench_conformal()
    print(f"conformal: held-out coverage {conf['heldout_coverage']:.3f} "
          f"(nominal {conf['nominal']:.2f}, "
          f"q={conf['quantile_g_per_kwh']:.1f} g/kWh)")
    out = {"select": select_rows, "step": step_rows, "risk": risk_rows,
           "conformal": conf, "smoke": smoke,
           "paper_per_task_ms": PAPER_PER_TASK_MS}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")
    return out


def main(smoke: bool = False):
    return run(smoke=smoke)


if __name__ == "__main__":
    import sys

    main(smoke="--smoke" in sys.argv)
