"""Plain reference for carbon-aware placement and billing, written from the
paper (arXiv:2603.27420, Eq. 1-4, Table I, Algorithm 1) and independent of
the code under test: numpy only, in the precision asked for.

A node is feasible for a task when its load is at most the threshold, its
profiled time at most the latency limit, and its free cpu and memory
cover the task. Its score is Eq. 3,

    S = w_R S_R + w_L S_L + w_P S_P + w_B S_B + w_C S_C,

with S_R the mean of min(1, free/needed) over cpu and memory, S_L = 1 -
load, S_P = 1 / (1 + T_avg[s]), S_B = 1 / (1 + 2 running) and Eq. 4's
S_C = 1 / (1 + I E_est), E_est = P T_avg / 3.6e6 kWh. Algorithm 1 places a
task on the best feasible node whose score is above 0. A task executed for
T ms at host power P is billed E = P T / 3.6e6 kWh (Eq. 1) and
C = E I PUE grams (Eq. 2).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def scores(fleet: Dict[str, np.ndarray], task_cpu: np.ndarray,
           task_mem: np.ndarray, weights: np.ndarray, *,
           latency_threshold_ms: float, load_threshold: float,
           dtype=np.float64) -> np.ndarray:
    """(U, N) Eq. 3 scores, ``-inf`` where infeasible, every operation
    rounded to ``dtype``."""
    f = {k: np.asarray(v, dtype) for k, v in fleet.items()}
    tc = np.asarray(task_cpu, dtype)[:, None]
    tm = np.asarray(task_mem, dtype)[:, None]
    one = dtype(1.0)
    free_cpu = f["cpu"] * (one - f["load"])
    free_mem = f["mem_mb"] - f["mem_used_mb"]
    feasible = ((f["load"] <= dtype(load_threshold))
                & (f["avg_time_ms"] <= dtype(latency_threshold_ms)))
    feasible = feasible[None, :] & (free_cpu[None, :] >= tc) \
        & (free_mem[None, :] >= tm)
    with np.errstate(divide="ignore", invalid="ignore"):
        s_cpu = np.where(tc > 0, np.minimum(one, free_cpu[None, :] / tc), one)
        s_mem = np.where(tm > 0, np.minimum(one, free_mem[None, :] / tm), one)
    half = dtype(0.5)
    s_r = half * s_cpu + half * s_mem
    s_l = one - f["load"]
    s_p = one / (one + f["avg_time_ms"] / dtype(1000.0))
    s_b = one / (one + f["running"] * dtype(2.0))
    e_est = f["power_w"] * f["avg_time_ms"] / dtype(3.6e6)
    s_c = one / (one + f["intensity"] * e_est)
    w = np.asarray(weights, dtype)
    total = (w[0] * s_r + (w[1] * s_l + w[2] * s_p + w[3] * s_b
                           + w[4] * s_c)[None, :])
    return np.where(feasible, total, -np.inf).astype(dtype)


def place(fleet, task_cpu, task_mem, weights, *, block: int = 256,
          dtype=np.float64, **kw) -> Tuple[np.ndarray, np.ndarray]:
    """Algorithm 1 per task, in blocks of tasks: (best node index or -1,
    its score)."""
    n = len(task_cpu)
    best = np.full(n, -1, np.int64)
    val = np.full(n, -np.inf)
    for lo in range(0, n, block):
        s = scores(fleet, task_cpu[lo:lo + block], task_mem[lo:lo + block],
                   weights, dtype=dtype, **kw)
        b = np.argmax(s, axis=1)
        v = s[np.arange(len(b)), b].astype(np.float64)
        best[lo:lo + block] = np.where(v > 0, b, -1)
        val[lo:lo + block] = v
    return best, val


def score_of(fleet, task_cpu, task_mem, nodes, weights, **kw) -> np.ndarray:
    """Float64 score of each task's given node (``-inf`` where the node is
    infeasible or missing)."""
    out = np.full(len(nodes), -np.inf)
    ok = np.asarray(nodes) >= 0
    idx = np.flatnonzero(ok)
    for lo in range(0, len(idx), 256):
        sel = idx[lo:lo + 256]
        sub = {k: np.asarray(v)[np.asarray(nodes)[sel]]
               for k, v in fleet.items()}
        # one node per task: the diagonal of the (U, U) block
        s = scores(sub, task_cpu[sel], task_mem[sel], weights, **kw)
        out[sel] = np.diagonal(s)
    return out


def billing(nodes: np.ndarray, n_nodes: int, latency_ms: float,
            host_power_w: float, overhead: float, intensity: np.ndarray,
            pue: float, dtype=np.float64) -> Dict[str, np.ndarray]:
    """Per-node totals of what the executed tasks are billed: tasks, time
    (ms), energy (kWh) and carbon (g)."""
    lat = dtype(latency_ms) * (dtype(1.0) + dtype(overhead))
    e = dtype(host_power_w) * (lat / dtype(1000.0)) / dtype(3.6e6)
    count = np.bincount(nodes, minlength=n_nodes).astype(np.float64)
    inten = np.asarray(intensity, dtype)
    carbon_each = (e * inten * dtype(pue)).astype(np.float64)
    return {"tasks": count,
            "time_ms": count * float(lat),
            "energy_kwh": count * float(e),
            "carbon_g": count * carbon_each}
