"""Fleet-scale scheduling fast path: per-step latency at N up to 10^5+.

Sweeps fleet size N x batch size B and times one engine scheduling
decision (``VectorizedPolicy.select_batch``) through three paths:

- **legacy** — the rebuild-everything path: fresh ``featurize`` (O(N)
  Python per-node loop + N provider calls) of the distinct task profiles
  per step, scored by the float64 numpy scorer;
- **cached** — the incremental FeatureCache fast path (DESIGN.md §3):
  O(changed) sync, one batched provider read, task-profile dedup, chunked
  vectorized scoring (selection memo off, so the rows keep measuring the
  scoring pass itself);
- **plan_wake** — deferral planning over the (S, N) slot grid, scalar
  nodes x slots loop vs the batched grid read;
- **step** — the END-TO-END ``CarbonEdgeEngine.step`` (select + execute +
  bill, DESIGN.md §6): the production default (batched execution +
  selection memo) vs the per-task execute loop (``batch_execute=False``),
  so the paper's 0.03 ms/task budget is measured for the whole step, not
  just selection.

Reports per-step latency, scheduled tasks/sec, and per-task overhead vs
the paper's 0.03 ms claim, and writes ``BENCH_fleet_scale.json``. The CI
smoke runs a reduced sweep (`run(smoke=True)`); the gate assertions live
in ``benchmarks/ci_gates.py`` (runnable locally:
``python -m benchmarks.ci_gates fleet``).
"""
from __future__ import annotations

import json
import time
from typing import Dict, List

import numpy as np

from repro.core.cluster import EdgeCluster, NodeSpec
from repro.core.policy import VectorizedPolicy, featurize
from repro.core.providers import StaticProvider, TraceProvider
from repro.core.scheduler import MODES, Task
from repro.core.temporal import (DeferrableTask, plan_wake, plan_wake_scalar,
                                 synthetic_trace)

PAPER_PER_TASK_MS = 0.03

FULL_NS = (1_000, 10_000, 100_000)
FULL_BS = (64, 256, 1024)
SMOKE_NS = (512, 2_048)
SMOKE_BS = (64,)


def make_fleet(n: int, seed: int = 0) -> EdgeCluster:
    rng = np.random.default_rng(seed)
    nodes = [NodeSpec(f"n{i}", cpu=float(rng.uniform(0.1, 4.0)),
                      mem_mb=int(rng.integers(128, 4096)),
                      carbon_intensity=float(rng.uniform(10.0, 1200.0)))
             for i in range(n)]
    c = EdgeCluster(nodes=nodes, host_power_w=142.0)
    c.profile(250.0)
    loads = rng.uniform(0.0, 0.9, n)
    for st, ld in zip(c.nodes.values(), loads):
        st.load = float(ld)
    return c


def make_tasks(b: int, seed: int = 0) -> List[Task]:
    # a handful of distinct resource profiles, like a real request mix —
    # exercises (rather than trivially defeats) the dedup fast path
    rng = np.random.default_rng(seed)
    profiles = [(float(rng.uniform(0.01, 0.5)), float(rng.uniform(8.0, 128.0)))
                for _ in range(8)]
    return [Task(cpu=c, mem_mb=m, base_latency_ms=250.0)
            for c, m in (profiles[i % len(profiles)] for i in range(b))]


def _time(fn, reps: int) -> float:
    """Best-of-reps wall time: the min is robust to scheduler/GC noise
    (what we want for a per-step latency claim)."""
    fn()                                   # warm (jit, cache build)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_select(cluster: EdgeCluster, tasks: List[Task], *,
                 legacy_reps: int, cached_reps: int) -> Dict:
    w = MODES["green"]
    provider = StaticProvider.from_cluster(cluster)
    scorer = VectorizedPolicy(backend="numpy")
    # memo off: these rows measure the incremental-featurize scoring pass,
    # not the steady-state profile memo (bench_step measures that)
    cached = VectorizedPolicy(backend="numpy", use_select_memo=False)
    # dirty a handful of nodes between steps, like a live engine would
    names = list(cluster.nodes)

    def step_legacy():
        reps = {(t.cpu, t.mem_mb): t for t in tasks}
        F, node_names = featurize(cluster, list(reps.values()), provider)
        chosen = dict(zip(reps, scorer._select_from_features(F, node_names,
                                                             w)))
        return [chosen[(t.cpu, t.mem_mb)] for t in tasks]

    def step_cached():
        for nm in names[:8]:
            cluster.nodes[nm].running += 1
            cluster.nodes[nm].running -= 1
        return cached.select_batch(cluster, tasks, w, provider)
    legacy_s = _time(step_legacy, legacy_reps)
    cached_s = _time(step_cached, cached_reps)
    assert cached.select_batch(cluster, tasks, w, provider) == step_legacy(), \
        "cached fast path diverged from the fresh-featurize oracle"
    b = len(tasks)
    return {
        "n_nodes": len(names), "batch": b,
        "legacy_step_ms": legacy_s * 1e3,
        "cached_step_ms": cached_s * 1e3,
        "speedup_x": legacy_s / cached_s,
        "cached_per_task_ms": cached_s * 1e3 / b,
        "cached_tasks_per_sec": b / cached_s,
        "paper_per_task_ms": PAPER_PER_TASK_MS,
        "vs_paper_x": (cached_s * 1e3 / b) / PAPER_PER_TASK_MS,
    }


def bench_step(n: int, b: int, *, scalar_reps: int, batched_reps: int,
               seed: int = 0) -> Dict:
    """End-to-end ``engine.step`` (select + execute + bill) per-task time:
    the batched execution path (engine default) vs the per-task execute
    loop it replaced (``batch_execute=False``). Each path gets its own
    fresh engine so ledgers and caches are comparable; ledger parity
    between the two paths is asserted exactly."""
    from repro.core.api import CarbonEdgeEngine

    def run_path(batch_execute: bool, reps: int) -> float:
        eng = CarbonEdgeEngine(make_fleet(n, seed=seed),
                               batch_execute=batch_execute)
        tasks = make_tasks(b, seed=seed)
        eng.submit_many(tasks)
        eng.step()                         # warm (cache build, memo fill)
        best = float("inf")
        for _ in range(reps):
            eng.submit_many(tasks)
            t0 = time.perf_counter()
            eng.step()
            best = min(best, time.perf_counter() - t0)
        return best

    scalar_s = run_path(False, scalar_reps)
    batched_s = run_path(True, batched_reps)
    # bit-exact parity of the two execution paths on identical traffic
    ea = CarbonEdgeEngine(make_fleet(n, seed=seed), batch_execute=False)
    eb = CarbonEdgeEngine(make_fleet(n, seed=seed), batch_execute=True)
    tasks = make_tasks(b, seed=seed)
    ra = ea.submit_many(tasks).step()
    rb = eb.submit_many(tasks).step()
    assert ra == rb and ea.cluster.log == eb.cluster.log \
        and ea.monitor.report() == eb.monitor.report(), \
        "batched execution diverged from the per-task loop"
    return {
        "n_nodes": n, "batch": b,
        "scalar_step_ms": scalar_s * 1e3,
        "batched_step_ms": batched_s * 1e3,
        "speedup_x": scalar_s / batched_s,
        "scalar_per_task_ms": scalar_s * 1e3 / b,
        "batched_per_task_ms": batched_s * 1e3 / b,
        "batched_tasks_per_sec": b / batched_s,
        "paper_per_task_ms": PAPER_PER_TASK_MS,
        "vs_paper_x": (batched_s * 1e3 / b) / PAPER_PER_TASK_MS,
    }


def bench_plan_wake(cluster: EdgeCluster, *, reps: int) -> Dict:
    traces = {nm: synthetic_trace(nm, st.spec.carbon_intensity,
                                  seed=i % 16)
              for i, (nm, st) in enumerate(cluster.nodes.items())}
    provider = TraceProvider(traces)
    task = DeferrableTask(cpu=0.05, mem_mb=16.0, deadline_hours=12.0,
                          duration_hours=0.5)
    scalar_s = _time(lambda: plan_wake_scalar(provider, cluster, task, 17.0),
                     max(1, reps // 4))
    batched_s = _time(lambda: plan_wake(provider, cluster, task, 17.0), reps)
    assert plan_wake(provider, cluster, task, 17.0) == \
        plan_wake_scalar(provider, cluster, task, 17.0)
    return {
        "n_nodes": len(cluster.nodes),
        "scalar_ms": scalar_s * 1e3,
        "batched_ms": batched_s * 1e3,
        "speedup_x": scalar_s / batched_s,
    }


def run(smoke: bool = False, out_path: str = "BENCH_fleet_scale.json") -> Dict:
    ns = SMOKE_NS if smoke else FULL_NS
    bs = SMOKE_BS if smoke else FULL_BS
    select_rows, wake_rows = [], []
    for n in ns:
        cluster = make_fleet(n)
        # the fresh-featurize baseline is O(N) Python — keep its reps tiny
        # at fleet scale so the benchmark itself stays tractable
        legacy_reps = 5 if n <= 2_000 else (2 if n <= 10_000 else 1)
        cached_reps = 50 if n <= 2_000 else (20 if n <= 10_000 else 5)
        for b in bs:
            row = bench_select(cluster, make_tasks(b),
                               legacy_reps=legacy_reps,
                               cached_reps=cached_reps)
            select_rows.append(row)
            print(f"select N={n:>7} B={b:>5}: legacy {row['legacy_step_ms']:9.2f} ms"
                  f"  cached {row['cached_step_ms']:7.3f} ms"
                  f"  ({row['speedup_x']:7.1f}x, "
                  f"{row['cached_per_task_ms']*1e3:7.2f} us/task,"
                  f" paper budget {PAPER_PER_TASK_MS*1e3:.0f} us)")
        wake = bench_plan_wake(cluster, reps=20 if n <= 10_000 else 5)
        wake_rows.append(wake)
        print(f"plan_wake N={n:>7}: scalar {wake['scalar_ms']:9.2f} ms"
              f"  batched {wake['batched_ms']:7.3f} ms"
              f"  ({wake['speedup_x']:7.1f}x)")
    step_rows = []
    for n in ns:
        b = max(bs) if not smoke else 256
        row = bench_step(n, b,
                         scalar_reps=5 if n <= 10_000 else 2,
                         batched_reps=20 if n <= 10_000 else 5)
        step_rows.append(row)
        print(f"step e2e N={n:>7} B={b:>5}: scalar-exec "
              f"{row['scalar_step_ms']:9.2f} ms  batched "
              f"{row['batched_step_ms']:7.3f} ms  ({row['speedup_x']:5.1f}x,"
              f" {row['batched_per_task_ms']*1e3:7.2f} us/task,"
              f" paper budget {PAPER_PER_TASK_MS*1e3:.0f} us)")
    out = {"select": select_rows, "plan_wake": wake_rows, "step": step_rows,
           "smoke": smoke, "paper_per_task_ms": PAPER_PER_TASK_MS}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {out_path}")
    return out


def main(smoke: bool = False):
    return run(smoke=smoke)


if __name__ == "__main__":
    import sys

    main(smoke="--smoke" in sys.argv)
