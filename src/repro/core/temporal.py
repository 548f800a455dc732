"""Temporal carbon-aware scheduling — the paper's §V.A future work
("real-time carbon intensity integration ... deferring non-urgent tasks to
low-carbon time periods", §II.E).

Adds to the static-scenario core:

- :class:`IntensityTrace` — a diurnal grid-intensity signal per region
  (synthetic solar/wind-shaped traces, or user-supplied hourly series the
  way an Electricity Maps API feed would provide them);
- :class:`TemporalScheduler` — extends the NSA: for *deferrable* tasks it
  scans the (node x start-slot) grid within the task's deadline and picks
  the slot/node minimising expected carbon, subject to the same Eq. 3
  feasibility filters; urgent tasks fall through to the instantaneous NSA.

This keeps the paper's Eq. 4 scoring intact — S_C simply becomes
time-indexed — so the weight semantics of Table I are unchanged.

The slot-grid search itself lives in
:class:`repro.core.policy.TemporalPolicy` (the Eq. 3 math is *not*
duplicated here); intensity is read through a
:class:`repro.core.providers.TraceProvider`. This module keeps the trace types,
the deferrable-task model, and the thin scheduler wrapper.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.cluster import EdgeCluster
from repro.core.policy import Placement, TemporalPolicy
from repro.core.providers import (StaticProvider, TraceProvider,
                                  intensity_batch, intensity_interval_batch)
from repro.core.scheduler import Task, Weights, node_feasible


def interp_hourly(values: np.ndarray, hours: np.ndarray) -> np.ndarray:
    """Vectorized wrap-around linear interpolation over hourly tables:
    ``values`` is (24,) or (M, 24), ``hours`` (S,); returns (S,) resp.
    (M, S). THE single definition of :meth:`IntensityTrace.at`'s
    arithmetic — the batched provider API interpolates through this same
    function, keeping batch == scalar bit-identical (sim determinism
    depends on it)."""
    h = np.asarray(hours, dtype=float) % 24.0
    i = np.floor(h).astype(np.int64) % 24
    j = (i + 1) % 24
    frac = h - np.floor(h)
    v = np.asarray(values)
    return v[..., i] * (1 - frac) + v[..., j] * frac


@dataclass(frozen=True)
class IntensityTrace:
    """Hourly carbon intensity for one region. values[h] in gCO2/kWh."""

    region: str
    values: Tuple[float, ...]              # length 24 (wraps)

    def at(self, hour):
        """Linear interpolation at ``hour`` (wraps over 24 h). Accepts a
        scalar (returns float) or an array of hours (returns an array) —
        the array form backs the batched provider API and evaluates the
        exact scalar arithmetic elementwise (bit-identical)."""
        if np.ndim(hour) == 0:
            h = hour % 24.0
            i = int(h) % 24
            j = (i + 1) % 24
            frac = h - int(h)
            return self.values[i] * (1 - frac) + self.values[j] * frac
        return interp_hourly(self.values, hour)

    @property
    def mean(self) -> float:
        return float(np.mean(self.values))


def synthetic_trace(region: str, base: float, solar_dip: float = 0.35,
                    noise: float = 0.0, seed: int = 0) -> IntensityTrace:
    """Diurnal trace: intensity dips around midday (solar), peaks in the
    evening ramp — the canonical duck-curve shape."""
    rng = np.random.default_rng(seed)
    hours = np.arange(24)
    solar = np.exp(-0.5 * ((hours - 13.0) / 3.0) ** 2)       # midday dip
    evening = 0.15 * np.exp(-0.5 * ((hours - 19.0) / 2.0) ** 2)
    vals = base * (1.0 - solar_dip * solar + evening)
    if noise:
        vals = vals * (1.0 + noise * rng.standard_normal(24))
    return IntensityTrace(region, tuple(float(v) for v in vals))


@dataclass(frozen=True)
class SeriesTrace:
    """A measured intensity series on a uniform time grid — the shape an
    ElectricityMaps-style regional CSV export has (DESIGN.md §11): one
    value every ``step_hours`` from ``start_hour``, *not* wrapped over
    24 h (a day-long replay ends where the data ends; reads past either
    edge clamp to it).

    ``at`` is one array-aware code path: a scalar hour returns a float, an
    hour array returns an array of the exact same elementwise arithmetic —
    so :meth:`TraceProvider.intensity` and ``intensity_batch`` agree
    bit-for-bit, which the multi-region replay determinism test pins.
    """

    region: str
    values: Tuple[float, ...]
    start_hour: float = 0.0
    step_hours: float = 1.0

    def at(self, hour):
        v = np.asarray(self.values, dtype=float)
        if v.size == 1:
            out = np.full(np.shape(hour), v[0])
            return float(out) if np.ndim(hour) == 0 else out
        pos = (np.asarray(hour, dtype=float) - self.start_hour) \
            / self.step_hours
        pos = np.clip(pos, 0.0, float(v.size - 1))
        i = np.minimum(np.floor(pos).astype(np.int64), v.size - 2)
        frac = pos - i
        out = v[i] * (1 - frac) + v[i + 1] * frac
        return float(out) if np.ndim(hour) == 0 else out

    @property
    def mean(self) -> float:
        return float(np.mean(self.values))


@dataclass(frozen=True)
class DeferrableTask(Task):
    deadline_hours: float = 0.0            # 0 => not deferrable
    duration_hours: float = 0.1


def _wake_slots(task, slot_hours: float) -> int:
    """Number of start slots within ``deadline - duration`` (0 = no slack)."""
    deadline = getattr(task, "deadline_hours", 0.0)
    duration = getattr(task, "duration_hours", 0.0)
    horizon = max(deadline - duration, 0.0)
    if horizon <= 0.0:
        return 0
    return max(1, int(horizon / slot_hours) + 1)


def plan_wake_scalar(provider, cluster: EdgeCluster, task, now_hour: float,
                     slot_hours: float = 0.5) -> float:
    """Scalar nodes x slots Python scan — the parity oracle for
    :func:`plan_wake` (which vectorizes the same decision; the two are
    regression-tested equal, ties included)."""
    n_slots = _wake_slots(task, slot_hours)
    if n_slots == 0:
        return now_hour
    # half-slot pad so float fuzz in arange never drops/adds a slot
    end = now_hour + (n_slots - 0.5) * slot_hours
    best_slot, best_val = 0, np.inf
    for name, st in cluster.nodes.items():
        if not node_feasible(st, task):
            continue
        if hasattr(provider, "window"):
            series = np.asarray(provider.window(name, now_hour, end,
                                                slot_hours))[:n_slots]
        else:
            series = np.array([provider.intensity(name, now_hour + k * slot_hours)
                               for k in range(n_slots)])
        if series.size == 0:
            continue
        k = int(np.argmin(series))
        # strict < keeps the earliest slot (and first node) on exact ties
        if series[k] < best_val:
            best_val, best_slot = float(series[k]), k
    return now_hour + best_slot * slot_hours


def plan_wake(provider, cluster: EdgeCluster, task, now_hour: float,
              slot_hours: float = 0.5) -> float:
    """When should a deferrable task wake to minimise expected carbon?

    This is the *driver-routed* deferral path (DESIGN.md §2): instead of
    the eager slot scan executing a placement immediately
    (:meth:`TemporalPolicy.place`), the sim driver calls ``plan_wake`` to
    pick a wake hour, parks the task on a ``DEFER_WAKE`` event, and lets
    the engine's policy choose the node *at wake time* against the
    then-current cluster state — so capacity freed (or consumed) between
    submission and wake is seen, which the eager scan cannot do.

    The wake slot minimises the provider's intensity over the feasible
    nodes' forecast series within ``[now, now + deadline - duration]``.
    Ties keep the earliest slot, and across nodes the first (insertion-
    order) node's earliest minimum wins — identical to the scalar oracle
    :func:`plan_wake_scalar`. A task without deadline slack, or with no
    feasible node, wakes immediately.

    Fleet-scale fast path (DESIGN.md §3): feasibility comes from the
    cluster's incremental :class:`~repro.core.featcache.FeatureCache`
    columns (duck-typed cluster-likes without one fall back to the scalar
    feasibility filter) and the whole (S, N) slot grid is one batched
    :func:`~repro.core.providers.intensity_batch` read — no nodes x slots
    Python loop. Delegates to :func:`plan_wake_batch`.
    """
    return float(plan_wake_batch(provider, cluster, [task], now_hour,
                                 slot_hours)[0])


def plan_wake_batch(provider, cluster: EdgeCluster, tasks, now_hour: float,
                    slot_hours: float = 0.5) -> np.ndarray:
    """Vectorized :func:`plan_wake` for many tasks at once: one (S, N)
    intensity grid over the union of the tasks' feasible nodes, then a
    per-task argmin with the oracle's exact tie-breaks."""
    T = len(tasks)
    wakes = np.full(T, now_hour, dtype=float)
    n_slots = np.array([_wake_slots(t, slot_hours) for t in tasks])
    todo = np.nonzero(n_slots > 0)[0]
    if todo.size == 0:
        return wakes
    fc = getattr(cluster, "feature_cache", None)
    if callable(fc):
        cache = fc()
        all_names = cache.names
        task_cpu = np.array([tasks[i].cpu for i in todo], dtype=float)
        task_mem = np.array([tasks[i].mem_mb for i in todo], dtype=float)
        feas = cache.feasible(task_cpu, task_mem)        # (T', N)
    else:
        # duck-typed cluster-likes without the EdgeCluster cache plumbing:
        # scalar feasibility, still one batched grid read below
        all_names = list(cluster.nodes)
        feas = np.array([[node_feasible(cluster.nodes[n], tasks[i])
                          for n in all_names] for i in todo])
    need = feas.any(axis=0)
    if not need.any():
        return wakes
    cols = np.nonzero(need)[0]
    names = [all_names[j] for j in cols]
    S = int(n_slots[todo].max())
    hours = now_hour + np.arange(S) * slot_hours
    # One batched read for the whole grid. A provider exposing only the
    # legacy ``window`` protocol (and no intensity_batch) keeps its
    # per-node window path so series values stay bit-identical.
    if (not hasattr(provider, "intensity_batch")
            and hasattr(provider, "window")):
        end = now_hour + (S - 0.5) * slot_hours
        grid = np.full((S, len(names)), np.inf)
        for j, name in enumerate(names):
            series = np.asarray(provider.window(name, now_hour, end,
                                                slot_hours))[:S]
            grid[:series.size, j] = series
    else:
        grid = np.asarray(intensity_batch(provider, names, hours))
    grid = grid.reshape(S, len(names))
    # Per-node earliest argmin over its slots, then first node with the
    # strictly smallest value — the scalar oracle's exact tie-breaks.
    for row, ti in enumerate(todo):
        s = int(n_slots[ti])
        sub = grid[:s, :]
        m = np.where(feas[row, cols], sub.min(axis=0), np.inf)
        if not np.isfinite(m).any():
            continue
        j = int(np.argmin(m))
        k = int(np.argmin(sub[:, j]))
        wakes[ti] = now_hour + k * slot_hours
    return wakes


def plan_wake_risk(provider, cluster: EdgeCluster, task, now_hour: float,
                   slot_hours: float = 0.5, coverage: float = 0.9) -> float:
    """Risk-bounded :func:`plan_wake` (scalar front-end); see
    :func:`plan_wake_risk_batch`."""
    return float(plan_wake_risk_batch(provider, cluster, [task], now_hour,
                                      slot_hours, coverage)[0])


def plan_wake_risk_batch(provider, cluster: EdgeCluster, tasks,
                         now_hour: float, slot_hours: float = 0.5,
                         coverage: float = 0.9) -> np.ndarray:
    """Risk-bounded deferral planning over conformal intensity intervals
    (DESIGN.md §8).

    :func:`plan_wake_batch` trusts the provider's point forecast; with a
    noisy forecast that gambles real carbon on a predicted dip. Here the
    grid is read as ``coverage``-level intervals
    (:func:`repro.core.providers.intensity_interval_batch`) and a task defers
    only when the deferral wins even under the interval's pessimistic
    view: the candidate future slot is the feasible (slot >= 1, node)
    cell minimising the interval UPPER bound (earliest slot, first node
    on ties), and the task defers to it only if that upper bound strictly
    undercuts the best LOWER bound of executing now (slot 0 over the
    feasible nodes). Since lo <= hi everywhere, a deferral whose lower
    bound loses to executing now can never happen — the acceptance
    invariant regression-tested in tests/test_partition.py. Zero-width
    (point-interval) providers degrade to "defer only on strict
    improvement". Tasks without deadline slack, or with no feasible node,
    wake immediately.
    """
    T = len(tasks)
    wakes = np.full(T, now_hour, dtype=float)
    n_slots = np.array([_wake_slots(t, slot_hours) for t in tasks])
    todo = np.nonzero(n_slots > 1)[0]      # s == 1 has no future slot
    if todo.size == 0:
        return wakes
    fc = getattr(cluster, "feature_cache", None)
    if callable(fc):
        cache = fc()
        all_names = cache.names
        task_cpu = np.array([tasks[i].cpu for i in todo], dtype=float)
        task_mem = np.array([tasks[i].mem_mb for i in todo], dtype=float)
        feas = cache.feasible(task_cpu, task_mem)        # (T', N)
    else:
        all_names = list(cluster.nodes)
        feas = np.array([[node_feasible(cluster.nodes[n], tasks[i])
                          for n in all_names] for i in todo])
    need = feas.any(axis=0)
    if not need.any():
        return wakes
    cols = np.nonzero(need)[0]
    names = [all_names[j] for j in cols]
    S = int(n_slots[todo].max())
    hours = now_hour + np.arange(S) * slot_hours
    lo, hi = intensity_interval_batch(provider, names, hours,
                                      coverage=coverage)
    lo = np.asarray(lo, dtype=float).reshape(S, len(names))
    hi = np.asarray(hi, dtype=float).reshape(S, len(names))
    for row, ti in enumerate(todo):
        ok = feas[row, cols]
        if not ok.any():
            continue
        s = int(n_slots[ti])
        # optimistic cost of running now: best slot-0 lower bound
        now_opt = float(np.where(ok, lo[0, :], np.inf).min())
        # pessimistic cost of the best deferral candidate (slots 1..s-1)
        sub_hi = np.where(ok[None, :], hi[1:s, :], np.inf)
        if not np.isfinite(sub_hi).any():
            continue
        m = sub_hi.min(axis=0)
        j = int(np.argmin(m))              # first node on exact ties
        k = 1 + int(np.argmin(sub_hi[:, j]))   # earliest slot on ties
        if sub_hi[k - 1, j] < now_opt:
            wakes[ti] = now_hour + k * slot_hours
    return wakes


class TemporalScheduler:
    """Space-time extension of the NSA (Algorithm 1 over a slot grid).

    Thin wrapper: the grid search is
    :meth:`repro.core.policy.TemporalPolicy.place`; the intensity signal is
    a :class:`TraceProvider` over ``traces`` with the cluster's static
    regional values as fallback.
    """

    def __init__(self, cluster: EdgeCluster, traces: Dict[str, IntensityTrace],
                 weights: Weights, slot_hours: Optional[float] = None,
                 policy: Optional[TemporalPolicy] = None, provider=None):
        if (policy is not None and slot_hours is not None
                and slot_hours != policy.slot_hours):
            raise ValueError(
                f"conflicting slot_hours: {slot_hours} vs the supplied "
                f"policy's {policy.slot_hours}")
        self.cluster = cluster
        self.traces = traces
        self.weights = weights
        self.provider = provider or TraceProvider(
            traces, fallback=StaticProvider.from_cluster(cluster))
        self.policy = policy or TemporalPolicy(
            slot_hours=0.5 if slot_hours is None else slot_hours)
        # single source of truth: the policy's grid granularity
        self.slot_hours = self.policy.slot_hours

    def select(self, task: DeferrableTask, now_hour: float = 0.0) -> Optional[Placement]:
        return self.policy.place(self.cluster, task, self.weights,
                                 self.provider, now_hour)

    def run(self, tasks: Sequence[DeferrableTask], now_hour: float = 0.0
            ) -> Tuple[List[Placement], float]:
        placements = []
        total = 0.0
        for t in tasks:
            pl = self.select(t, now_hour)
            if pl is None:
                raise RuntimeError("no feasible placement")
            placements.append(pl)
            total += pl.expected_carbon_g
        return placements, total


def carbon_savings_from_deferral(cluster: EdgeCluster,
                                 traces: Dict[str, IntensityTrace],
                                 weights: Weights,
                                 tasks: Sequence[DeferrableTask],
                                 now_hour: float = 0.0) -> Dict[str, float]:
    """Compare run-now vs deadline-aware placement for the same workload."""
    sched = TemporalScheduler(cluster, traces, weights)
    urgent = [DeferrableTask(t.cpu, t.mem_mb, t.base_latency_ms, 0.0,
                             t.duration_hours) for t in tasks]
    _, now_carbon = sched.run(urgent, now_hour)
    _, deferred_carbon = sched.run(tasks, now_hour)
    return {
        "run_now_g": now_carbon,
        "deferred_g": deferred_carbon,
        "savings_pct": 100.0 * (1 - deferred_carbon / now_carbon)
        if now_carbon else 0.0,
    }
