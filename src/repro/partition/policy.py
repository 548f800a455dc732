"""Joint partition+placement policy: (B, N) scoring widened to (B, P, N).

:class:`PartitionPolicy` is a ``SchedulingPolicy``-compatible scorer that
decides a **(partition cut, node) pair** per task instead of a bare node
index. The Eq. 3/4 scoring rule is unchanged — only two feature columns
widen per (cut, node) cell:

- ``COL_TIME_S``: the *offloaded segment's* service time,
  ``avg_time_s[n] * remote_frac[p] + comm_s[p]`` (the boundary activation
  must cross the uplink before the node can start);
- ``COL_IXE``: Eq. 4's ``I * E_est`` with E_est derived from that widened
  time at the node's power draw.

S_R, S_L, S_B and feasibility stay per-node, so the Pallas kernel's tile
math (``kernels.node_score._eq3_tile_scores``) is reused verbatim by the
(B, P, N) on-chip reduction (``select_best_joint``); the numpy column path
broadcasts the cached (P, N) time/energy block
(``FeatureCache.partition_block``) and the scalar cut-major loop
:func:`select_joint_scalar` is the bit-exact parity oracle per house
style. Cut candidates come from a :class:`~repro.partition.profile.
CutProfile`; the scalar DP (``core.partitioner.partition_costs``) remains
the oracle for multi-segment splits of a *fixed* node list.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from repro.core.policy import (COL_CPU_FREE, COL_IXE, COL_LOAD, COL_MEM_FREE,
                               COL_RUNNING, COL_TIME_S, COL_VALID,
                               FEATURE_DIM, VectorizedPolicy, _SelectionMemo,
                               get_cache)
from repro.core.providers import CarbonIntensityProvider
from repro.core.scheduler import Task, Weights, node_feasible
from repro.obs.profiler import span
from repro.partition.profile import CutProfile

# Default uplink between the requesting device and the fleet: a 100 Mbps
# edge wireless link, slow enough that shipping a large early-layer
# activation genuinely competes with computing locally.
DEFAULT_LINK_MBPS = 100.0


def joint_time_energy(avg_time_s, power_w, remote_frac, comm_s):
    """Widened (cut, node) service time (s) and Eq. 4 energy (kWh).

    THE single statement of the joint columns' arithmetic: the scalar
    oracle evaluates it per cell, ``FeatureCache.partition_block``
    broadcasts the identical expressions over (P, N) — bit-exact parity by
    construction. Accepts scalars or broadcastable arrays.
    """
    t = avg_time_s * remote_frac + comm_s
    e = power_w * (t * 1000.0) / 3.6e6
    return t, e


@dataclass(frozen=True)
class JointDecision:
    """One task's joint decision: offload layers [cut, L) to ``node``."""

    node: str
    cut: int             # layer index (profile.cuts[cut_index])
    cut_index: int       # p — row into the profile's (P,) columns
    score: float
    remote_frac: float
    comm_s: float

    def effective_latency_ms(self, base_latency_ms: float) -> float:
        """Base latency of the offloaded segment (what the fleet executes
        and bills): the remote compute share plus the uplink transfer."""
        return base_latency_ms * self.remote_frac + self.comm_s * 1000.0


def select_joint_scalar(cluster, task: Task, profile: CutProfile,
                        weights: Weights,
                        provider: Optional[CarbonIntensityProvider] = None,
                        now_hour: float = 0.0,
                        latency_threshold_ms: float = 5000.0,
                        link_mbps: float = DEFAULT_LINK_MBPS
                        ) -> Optional[JointDecision]:
    """Cut-major Python loop over (p, n) — the joint parity oracle.

    Iterates cuts in the outer loop and nodes in insertion order inside,
    keeping the first strict maximum, so exact ties resolve to the lowest
    (p, n) — np.argmax semantics over the flattened (P, N) plane, which
    the numpy column path and the Pallas fold both reproduce. Component
    accumulation order matches the column path exactly (task-independent
    base first, then the S_R term), keeping parity bit-exact.
    """
    w = weights.as_array()
    rf = profile.remote_frac()
    cs = profile.comm_seconds(link_mbps)
    rows = []
    for name, st in cluster.nodes.items():
        if st.avg_time_ms > latency_threshold_ms:
            continue
        if not node_feasible(st, task):
            continue
        intensity = (provider.intensity(name, now_hour)
                     if provider is not None else st.spec.carbon_intensity)
        free_cpu = st.spec.cpu * (1.0 - st.load)
        free_mem = st.spec.mem_mb - st.mem_used_mb
        cpu_frac = free_cpu / task.cpu if task.cpu > 0 else 1.0
        mem_frac = free_mem / task.mem_mb if task.mem_mb > 0 else 1.0
        s_r = 0.5 * min(1.0, cpu_frac) + 0.5 * min(1.0, mem_frac)
        rows.append((name, s_r, 1.0 - st.load,
                     1.0 / (1.0 + st.running * 2.0),
                     st.avg_time_ms / 1000.0,
                     st.power_w(cluster.host_power_w), intensity))
    best_score, best = 0.0, None
    for p in range(profile.num_cuts):
        for name, s_r, s_l, s_b, avg_s, power, intensity in rows:
            t, e = joint_time_energy(avg_s, power, rf[p], cs[p])
            base = (w[1] * s_l + w[2] * (1.0 / (1.0 + t)) + w[3] * s_b
                    + w[4] * (1.0 / (1.0 + intensity * e)))
            s = w[0] * s_r + base
            if s > best_score:
                best_score = s
                best = JointDecision(name, profile.cuts[p], p, float(s),
                                     float(rf[p]), float(cs[p]))
    return best


class PartitionPolicy:
    """Batched joint (cut, node) selection over one :class:`CutProfile`.

    ``backend`` mirrors :class:`~repro.core.policy.VectorizedPolicy`:
    ``"numpy"`` broadcasts the cached (P, N) column block (bit-exact with
    the scalar oracle), ``"pallas"`` runs the fused (B, P, N) on-chip
    reduction (float32, interpret mode off TPU), ``"auto"`` picks by host.
    The fleet-scale machinery carries over: features come from the
    cluster's incremental FeatureCache (per-profile (P, N) block cached on
    ``data_rev``), duplicate (cpu, mem_mb) task profiles share one scored
    row, and steady-state selections memoize per profile epoch. Clusters
    without FeatureCache plumbing fall back to the scalar oracle per task.

    As an engine policy, ``select_batch`` returns node names and exposes
    the per-task joint decisions on ``last_decisions``;
    ``execution_latency_ms`` is the :class:`~repro.core.api.
    CarbonEdgeEngine` hook that makes the engine execute and bill only the
    offloaded segment (local-segment compute runs on the requesting
    device, outside the fleet's ledgers).
    """

    name = "partition"

    def __init__(self, profile: CutProfile, backend: str = "auto",
                 latency_threshold_ms: float = 5000.0,
                 link_mbps: float = DEFAULT_LINK_MBPS,
                 use_cache: bool = True, use_select_memo: bool = True):
        if backend not in ("auto", "numpy", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        self.profile = profile
        self.backend = backend
        self.latency_threshold_ms = latency_threshold_ms
        self.link_mbps = link_mbps
        self.use_cache = use_cache
        self.use_select_memo = use_select_memo
        self._rf = profile.remote_frac()             # (P,)
        self._cs = profile.comm_seconds(link_mbps)   # (P,)
        self._block_key = (profile, link_mbps)
        self.last_decisions: List[Optional[JointDecision]] = []
        self._last_eff: Optional[np.ndarray] = None
        # Observability hooks (DESIGN.md §9), mirroring VectorizedPolicy:
        # `capture_scores` publishes {"score", "runner_up", "cut"} per
        # task on `last_scores`; `profiler` gets featurize/score spans.
        self.profiler = None
        self.capture_scores = False
        self.last_scores = None

    def _resolved_backend(self) -> str:
        if self.backend != "auto":
            return self.backend
        import jax
        return "pallas" if jax.default_backend() == "tpu" else "numpy"

    # -- joint decisions ---------------------------------------------------
    def decide(self, cluster, task: Task, weights: Weights,
               provider: Optional[CarbonIntensityProvider] = None,
               now_hour: float = 0.0) -> Optional[JointDecision]:
        return self.decide_batch(cluster, [task], weights, provider,
                                 now_hour)[0]

    def decide_batch(self, cluster, tasks: Sequence[Task], weights: Weights,
                     provider: Optional[CarbonIntensityProvider] = None,
                     now_hour: float = 0.0) -> List[Optional[JointDecision]]:
        """Per-task joint decisions; rows depend only on (cpu, mem_mb), so
        duplicate resource profiles share one scored (P, N) pass."""
        if not tasks:
            return []
        keys = [(t.cpu, t.mem_mb) for t in tasks]
        uniq: dict = {}
        reps: List[Task] = []
        for t, key in zip(tasks, keys):
            if key not in uniq:
                uniq[key] = len(reps)
                reps.append(t)
        chosen = self._decide_unique(cluster, reps, weights, provider,
                                     now_hour)
        if not self.capture_scores:
            return [chosen[uniq[key]] for key in keys]
        # expand the rep-level runner-up capture with the same index map
        # (C-speed fromiter over map + one object-array gather)
        idx = np.fromiter(map(uniq.__getitem__, keys), np.intp,
                          count=len(keys))
        run = getattr(self, "_cap_run_reps", None)
        self._cap_run_tasks = (
            np.asarray(run)[idx]
            if run is not None and len(run) == len(reps)
            else np.full(len(keys), np.nan))
        return np.asarray(chosen, dtype=object)[idx].tolist()

    def _decide_unique(self, cluster, reps, weights, provider, now_hour):
        cap = self.capture_scores
        if cap:
            self._cap_run: List[np.ndarray] = []
        cache = get_cache(cluster) if self.use_cache else None
        if cache is None:
            # Cluster-likes without FeatureCache plumbing: the oracle IS
            # the decision procedure (P x N scalar scan per unique task).
            out = [select_joint_scalar(cluster, t, self.profile, weights,
                                       provider, now_hour,
                                       self.latency_threshold_ms,
                                       self.link_mbps) for t in reps]
            if cap:
                # oracle keeps only the winner; runner-up unavailable
                self._cap_run_reps = np.full(len(out), np.nan)
            return out
        if not self.use_select_memo:
            out = self._decide_cached(cache, reps, weights, provider,
                                      now_hour)
            if cap:
                self._cap_run_reps = (np.concatenate(self._cap_run)
                                      if self._cap_run else np.zeros(0))
            return out
        memo = getattr(cache, "_sel_memo", None)
        if memo is None:
            memo = cache._sel_memo = _SelectionMemo()
        memo.sync_epoch(cache, provider, now_hour)
        # `cap` keys the table: capture-on entries are (decision,
        # runner_up) pairs, plain entries bare decisions
        cfg = ("partition", self._block_key, self._resolved_backend(),
               self.latency_threshold_ms, weights.as_array().tobytes(), cap)
        table = memo.map.setdefault(cfg, {})
        keys = [(t.cpu, t.mem_mb) for t in reps]
        missing = [i for i, k in enumerate(keys) if k not in table]
        if missing:
            chosen = self._decide_cached(cache, [reps[i] for i in missing],
                                         weights, provider, now_hour)
            if (len(table) + len(missing)
                    > VectorizedPolicy.MEMO_MAX_PROFILES):
                table.clear()
            if cap:
                mr = (np.concatenate(self._cap_run) if self._cap_run
                      else np.zeros(0))
                for j, (i, ch) in enumerate(zip(missing, chosen)):
                    table[keys[i]] = (ch, float(mr[j]))
            else:
                for i, ch in zip(missing, chosen):
                    table[keys[i]] = ch
        if not cap:
            return [table[k] for k in keys]
        entries = [table[k] for k in keys]
        self._cap_run_reps = np.array([e[1] for e in entries])
        return [e[0] for e in entries]

    def _decide_cached(self, cache, reps, weights, provider, now_hour):
        prof = self.profiler
        with span(prof, "featurize"):
            t_pn, e_pn = cache.partition_block(self._block_key, self._rf,
                                               self._cs)           # (P, N)
            task_cpu = np.array([t.cpu for t in reps], dtype=float)
            task_mem = np.array([t.mem_mb for t in reps], dtype=float)
            feas = cache.feasible(task_cpu, task_mem,
                                  self.latency_threshold_ms)       # (U, N)
            ints = cache.intensities(provider, now_hour,
                                     need=feas.any(axis=0))        # (N,)
        with span(prof, "score"):
            if self._resolved_backend() == "pallas":
                out = self._decide_pallas(cache, task_cpu, task_mem, feas,
                                          ints, t_pn, e_pn, weights)
            else:
                out = self._decide_numpy(cache, task_cpu, task_mem, feas,
                                         ints, t_pn, e_pn, weights)
        return out

    @staticmethod
    def _resource_fracs(cache, task_cpu, task_mem):
        """(U, N) cpu/mem free fractions, featurize's guarded division."""
        cpu_frac = np.ones((task_cpu.size, cache.n))
        np.divide(cache.free_cpu[None, :], task_cpu[:, None], out=cpu_frac,
                  where=(task_cpu > 0)[:, None])
        mem_frac = np.ones((task_mem.size, cache.n))
        np.divide(cache.free_mem[None, :], task_mem[:, None], out=mem_frac,
                  where=(task_mem > 0)[:, None])
        return cpu_frac, mem_frac

    def _decide_numpy(self, cache, task_cpu, task_mem, feas, ints, t_pn,
                      e_pn, weights):
        """Column path: one task-independent (P, N) base per step, then an
        (N,) S_R row + flattened argmax per unique task — the scalar
        oracle's accumulation order, so selections are bit-exact."""
        w = weights.as_array()
        base_pn = (w[1] * (1.0 - cache.load)[None, :]
                   + w[2] * (1.0 / (1.0 + t_pn))
                   + w[3] * (1.0 / (1.0 + cache.running * 2.0))[None, :]
                   + w[4] * (1.0 / (1.0 + ints[None, :] * e_pn)))  # (P, N)
        cpu_frac, mem_frac = self._resource_fracs(cache, task_cpu, task_mem)
        s_r = 0.5 * np.minimum(1.0, cpu_frac) + 0.5 * np.minimum(1.0, mem_frac)
        N = cache.n
        cap = self.capture_scores
        runs: List[float] = []
        out: List[Optional[JointDecision]] = []
        for u in range(task_cpu.size):
            totals = np.where(feas[u][None, :],
                              w[0] * s_r[u][None, :] + base_pn, -np.inf)
            flat = int(np.argmax(totals))
            p, n = divmod(flat, N)
            val = totals[p, n]
            if cap:
                # runner-up over the flattened (P, N) plane, winner masked
                t2 = totals.ravel().copy()
                t2[flat] = -np.inf
                runs.append(float(t2.max()) if t2.size > 1 else -np.inf)
            out.append(JointDecision(cache.names[n], self.profile.cuts[p],
                                     p, float(val), float(self._rf[p]),
                                     float(self._cs[p]))
                       if val > 0.0 else None)
        if cap:
            self._cap_run.append(np.asarray(runs))
        return out

    def _decide_pallas(self, cache, task_cpu, task_mem, feas, ints, t_pn,
                       e_pn, weights):
        """Fused path: build the widened (U, P, N, 8) feature tensor once,
        pad to power-of-two buckets, and reduce on-chip."""
        import jax.numpy as jnp

        from repro.kernels import ops

        U, N = feas.shape
        P = self._rf.size
        cpu_frac, mem_frac = self._resource_fracs(cache, task_cpu, task_mem)
        F = np.zeros((U, P, N, FEATURE_DIM), np.float32)
        F[:, :, :, COL_CPU_FREE] = cpu_frac[:, None, :]
        F[:, :, :, COL_MEM_FREE] = mem_frac[:, None, :]
        F[:, :, :, COL_LOAD] = cache.load[None, None, :]
        F[:, :, :, COL_TIME_S] = t_pn[None, :, :]
        F[:, :, :, COL_RUNNING] = cache.running[None, None, :]
        F[:, :, :, COL_IXE] = np.where(feas[:, None, :],
                                       (ints[None, :] * e_pn)[None, :, :],
                                       0.0)
        F[:, :, :, COL_VALID] = feas[:, None, :].astype(np.float32)
        bucket = VectorizedPolicy._bucket
        Up, Pp, Np = bucket(U), bucket(P), bucket(N)
        if (Up, Pp, Np) != (U, P, N):
            Fp = np.zeros((Up, Pp, Np, FEATURE_DIM), np.float32)
            Fp[:U, :P, :N] = F         # pad cells: valid=0 -> masked out
            F = Fp
        w8 = np.zeros(FEATURE_DIM, np.float32)
        w8[:5] = weights.as_array()
        pidx, nidx, val = ops.select_best_node_joint(jnp.asarray(F),
                                                     jnp.asarray(w8))
        pidx = np.asarray(pidx)[:U]
        nidx = np.asarray(nidx)[:U]
        val = np.asarray(val, np.float64)[:U]
        if self.capture_scores:
            # fused winner-only fold: runner-up not materialized
            self._cap_run.append(np.full(U, np.nan))
        return [JointDecision(cache.names[n], self.profile.cuts[p], int(p),
                              float(v), float(self._rf[p]),
                              float(self._cs[p]))
                if v > 0.0 else None
                for p, n, v in zip(pidx, nidx, val)]

    # -- SchedulingPolicy interface ----------------------------------------
    def select_batch(self, cluster, tasks: Sequence[Task], weights: Weights,
                     provider: Optional[CarbonIntensityProvider] = None,
                     now_hour: float = 0.0) -> List[Optional[str]]:
        decisions = self.decide_batch(cluster, tasks, weights, provider,
                                      now_hour)
        self.last_decisions = decisions
        eff = np.array([d.effective_latency_ms(t.base_latency_ms)
                        if d is not None else t.base_latency_ms
                        for t, d in zip(tasks, decisions)])
        self._last_eff = eff
        if self.capture_scores:
            self.last_scores = {
                "score": np.array([d.score if d is not None else np.nan
                                   for d in decisions]),
                "runner_up": self._cap_run_tasks,
                "cut": np.array([d.cut_index if d is not None else -1
                                 for d in decisions], dtype=np.int32),
            }
        return [d.node if d is not None else None for d in decisions]

    def select(self, cluster, task: Task, weights: Weights, provider=None,
               now_hour: float = 0.0) -> Optional[str]:
        return self.select_batch(cluster, [task], weights, provider,
                                 now_hour)[0]

    def execution_latency_ms(self, tasks: Sequence[Task]
                             ) -> Optional[np.ndarray]:
        """Engine hook: per-task effective base latency for the batch the
        last ``select_batch`` decided — the offloaded segment's compute
        share plus the uplink transfer. Returns None if the batch doesn't
        line up (a wrapper re-grouped tasks), in which case the engine
        bills the full base latency."""
        if self._last_eff is None or len(self._last_eff) != len(tasks):
            return None
        return self._last_eff

    def set_link_mbps(self, link_mbps: float) -> None:
        """Retune the uplink bandwidth mid-run (a link flap, DESIGN.md
        §10): recomputes the per-cut comm column and rotates the
        FeatureCache block key so the next score sees the new link —
        restoring the original value restores bit-identical columns."""
        self.link_mbps = float(link_mbps)
        self._cs = self.profile.comm_seconds(self.link_mbps)
        self._block_key = (self.profile, self.link_mbps)

    def fallback_latency_ms(self, task: Task) -> float:
        """Engine failover hook (DESIGN.md §10): when a task's offload
        target died after selection, the split is stranded — re-bill the
        whole model on the replacement node through the cut-0
        (full-offload) column: base latency scaled by remote_frac[0]
        (= 1.0) plus the full-payload transfer."""
        return float(task.base_latency_ms * self._rf[0]
                     + self._cs[0] * 1000.0)
