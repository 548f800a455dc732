"""CarbonEdge engine (DESIGN.md §1): the policy protocol and the engine.

Intensity providers live one layer down, in :mod:`repro.core.providers`
(the only way schedulers, routers and the CarbonMonitor read grid
intensity); the scoring policies in :mod:`repro.core.policy`.

- :class:`SchedulingPolicy` (protocol) — one scoring rule (paper Eq. 3/4,
  Algorithm 1), three implementations in :mod:`repro.core.policy`:
  ``WeightedScoringPolicy`` (scalar oracle), ``VectorizedPolicy`` (float64
  numpy, or the Pallas column select kernel on TPU — the default), and
  ``TemporalPolicy`` (slot-grid deferral as a time-indexed feature column).

- :class:`CarbonEdgeEngine` — the facade: ``submit``/``step``/``run``/
  ``report``. ``step`` scores B pending tasks against N nodes in a single
  scorer call (one Pallas kernel launch on TPU) instead of one Python loop
  per task.
"""
from __future__ import annotations

import warnings
from typing import Dict, List, Optional, Protocol, Sequence, runtime_checkable

import numpy as np

from repro.core.carbon import CarbonMonitor
from repro.core.cluster import EdgeCluster, TaskResult
from repro.core.energy import carbon_g
from repro.core.policy import VectorizedPolicy
from repro.core.providers import (CarbonIntensityProvider, StaticProvider,
                                  intensity_batch, intensity_interval_batch)
from repro.core.scheduler import MODES, Task, Weights
from repro.obs.profiler import span


# ---------------------------------------------------------------------------
# Scheduling policy protocol (implementations: repro/core/policy.py)
# ---------------------------------------------------------------------------


@runtime_checkable
class SchedulingPolicy(Protocol):
    """One scoring rule (Eq. 3/4), pluggable execution strategy."""

    name: str

    def select(self, cluster: EdgeCluster, task: Task, weights: Weights,
               provider: Optional[CarbonIntensityProvider] = None,
               now_hour: float = 0.0) -> Optional[str]:
        ...

    def select_batch(self, cluster: EdgeCluster, tasks: Sequence[Task],
                     weights: Weights,
                     provider: Optional[CarbonIntensityProvider] = None,
                     now_hour: float = 0.0) -> List[Optional[str]]:
        ...


# ---------------------------------------------------------------------------
# Engine facade
# ---------------------------------------------------------------------------


class NoFeasibleNodeError(RuntimeError):
    """A task in the batch had no feasible placement.

    ``executed`` holds the TaskResults of batch tasks that completed (and
    were billed) before the failure; the failing task and the unexecuted
    tail are back at the head of the engine queue.
    """

    def __init__(self, executed: List[TaskResult]):
        super().__init__("no feasible node")
        self.executed = executed


class CarbonEdgeEngine:
    """Batched carbon-aware scheduling engine (DESIGN.md §1.3).

    Owns a cluster, a policy, an intensity provider and a CarbonMonitor.
    ``step()`` drains up to ``batch_size`` pending tasks, scoring the whole
    batch against all N nodes in one vectorised/Pallas call, then executes
    placements and bills energy per region through the provider.
    """

    def __init__(self, cluster: EdgeCluster, *, mode: str = "green",
                 weights: Optional[Weights] = None,
                 policy: Optional[SchedulingPolicy] = None,
                 provider: Optional[CarbonIntensityProvider] = None,
                 monitor: Optional[CarbonMonitor] = None,
                 batch_size: Optional[int] = None,
                 batch_execute: bool = True,
                 obs=None, resilience=None, max_requeues: int = 5):
        self.cluster = cluster
        # Batched execute+billing fast path (DESIGN.md §6), on by default;
        # False forces the per-task loop — the bit-exact parity oracle
        # (same pattern as featurize vs featurize_cached).
        self.batch_execute = batch_execute
        self.weights = weights if weights is not None else MODES[mode]
        self.provider = provider or StaticProvider.from_cluster(cluster)
        if policy is None:
            policy = VectorizedPolicy()
        self.policy = policy
        # Multi-tenant admission protocol (DESIGN.md §7): a policy exposing
        # plan()/charge() (e.g. repro.tenancy.TenantPolicy) gets per-task
        # admit/defer/reject decisions applied before selection, and
        # executed carbon charged back per tenant.
        self._tenancy = (policy if callable(getattr(policy, "plan", None))
                         and callable(getattr(policy, "charge", None))
                         else None)
        self.batch_size = batch_size
        self.queue: List[Task] = []
        # Budget-deferred tasks parked until their tenant's next accounting
        # period: (wake_hour, task) in decision order. Drained by
        # pop_ripe() (the sim driver) or automatically by run_until().
        self.deferred: List[tuple] = []
        # Per-drained-task outcomes of the last step(), for drivers that
        # must track rejected/deferred work: a list of
        # ("done", TaskResult) | ("reject", reason) | ("defer", wake_hour)
        # in drained order — or None, meaning every drained task produced
        # a TaskResult in order (the tenancy-free fast path pays no
        # per-task Python to say so). After a step that raised, entries
        # cover the consumed tasks and None marks requeued ones.
        self.last_outcomes: Optional[List[tuple]] = None
        self.monitor = monitor or CarbonMonitor(provider=self.provider)
        if self.monitor.provider is None:
            # Caller-supplied provider-less monitor: adopt the engine's
            # provider so both ledgers (cluster execution and monitor
            # billing) read the same, possibly time-varying, signal.
            self.monitor.provider = self.provider
        elif self.monitor.provider is not self.provider:
            # A monitor wired to a DIFFERENT provider would silently bill
            # from the wrong grid signal; that is only sound if every
            # cluster region is pre-registered with a pinned intensity.
            for name in cluster.nodes:
                acc = self.monitor.regions.get(name)
                if acc is None or not acc.pinned:
                    raise ValueError(
                        "caller-supplied monitor is wired to a different "
                        f"CarbonIntensityProvider and region {name!r} is "
                        "not pinned; share the engine's provider or pin "
                        "every cluster region explicitly")
        for name in cluster.nodes:
            if name not in self.monitor.regions:
                # same PUE as the cluster's execution ledger, so totals and
                # per_region carbon agree
                self.monitor.register_region(name, pue=cluster.pue)
        # Cheap always-on step accounting (surfaced by report()): steps
        # drained and cumulative done/reject/defer verdict totals ("dead"
        # and "retry" keys appear only once such an outcome occurred, so
        # pre-resilience report consumers see an unchanged dict).
        self._steps = 0
        self._outcome_totals = {"done": 0, "reject": 0, "defer": 0}
        # Requeue-loop guard (DESIGN.md §10): a task failing at the queue
        # head `max_requeues` consecutive times stops re-raising and is
        # consumed as a ("dead", reason) outcome instead — submitted work
        # is never silently lost, but a permanently infeasible/unknown-node
        # task can no longer livelock retrying callers. The first
        # max_requeues-1 failures raise exactly as before.
        if max_requeues < 1:
            raise ValueError("max_requeues must be >= 1")
        self.max_requeues = max_requeues
        self._fail_task = None
        self._fail_count = 0
        self.dead_letters: List[tuple] = []     # (task, reason)
        # Failure-aware scheduling (DESIGN.md §10): a repro.resilience.
        # Resilience attaches the availability mask / circuit breakers to
        # the cluster's FeatureCache, gates every placement against the
        # ground-truth down set (failover re-placement), and converts
        # unplaceable tasks into backoff retries that dead-letter after
        # max_attempts. None (the default) keeps every path bit-identical.
        self.resilience = resilience
        self._attempts: Dict[int, int] = {}     # id(task) -> attempts so far
        if resilience is not None:
            resilience.bind(self)
        # Observability hub (DESIGN.md §9): a repro.obs.Observability with
        # any pillar enabled; None (the default) keeps every path
        # bit-identical at the cost of one `is not None` check per phase.
        self.obs = obs if obs is not None and obs.enabled else None
        self._exec_snapshot = None
        # Per-step execution columns (DESIGN.md §11): after a fully
        # successful batched-execute step, ``(uniq_nodes, inverse,
        # latency_ms, energy_kwh, carbon_g)`` arrays carrying the same
        # floats the step's TaskResults do — the sim driver's columnar
        # record path consumes them instead of re-gathering O(B)
        # attributes. None whenever the last step used the scalar path,
        # partially failed, or went through tenancy admission.
        self.last_exec = None
        # Original-batch positions the resilience gate re-placed off a
        # down/unknown node in the last step (DESIGN.md §12) — the sim
        # driver's JourneyTrace counts failover hops from this. None when
        # the gate did not fire or nothing needed re-placement.
        self.last_failover_pos = None
        if self.obs is not None:
            self._wire_obs()

    def _wire_obs(self) -> None:
        """Attach the enabled obs pillars to the policy's duck-typed hooks
        (`capture_scores` publishes winning/runner-up totals on
        ``policy.last_scores``; `profiler` receives featurize/score
        spans), and resolve the engine's mode index for the trace."""
        obs, pol = self.obs, self.policy
        if obs.trace is not None and hasattr(pol, "capture_scores"):
            pol.capture_scores = True
        if obs.profiler is not None and hasattr(pol, "profiler"):
            pol.profiler = obs.profiler
        # == repro.obs.MODE_LABELS == repro.tenancy.spec.MODE_ORDER
        labels = ("performance", "balanced", "green")
        self._mode_idx = next((i for i, m in enumerate(labels)
                               if MODES[m] == self.weights), -1)

    # -- request lifecycle -------------------------------------------------
    def submit(self, task: Task) -> "CarbonEdgeEngine":
        self.queue.append(task)
        return self

    def submit_many(self, tasks: Sequence[Task]) -> "CarbonEdgeEngine":
        self.queue.extend(tasks)
        return self

    def peek(self, limit: Optional[int] = None) -> List[Task]:
        """The tasks the next :meth:`step` would drain, without dequeuing —
        a public inspection hook for drivers and operators (the bundled
        sim driver mirrors the queue itself and steps with ``limit``)."""
        b = limit if limit is not None else (self.batch_size or len(self.queue))
        return list(self.queue[:b])

    def step(self, now_hour: float = 0.0,
             limit: Optional[int] = None) -> List[TaskResult]:
        """Place and execute one batch of pending tasks.

        Selection for the whole batch is a single ``select_batch`` call —
        with the default VectorizedPolicy that is one (B, N, 8) featurize
        plus one kernel/scorer invocation, not B Python loops. ``limit``
        overrides ``batch_size`` for this call (partial drain — the sim
        driver steps exactly the tasks whose arrival events have fired).
        """
        self.last_outcomes = None
        self._exec_snapshot = None
        self.last_exec = None
        self.last_failover_pos = None
        if not self.queue:
            return []
        b = limit if limit is not None else (self.batch_size or len(self.queue))
        batch, self.queue = self.queue[:b], self.queue[b:]
        results: List[TaskResult] = []
        self._steps += 1
        if self._tenancy is not None:
            return self._step_tenancy(batch, now_hour, results)
        obs = self.obs
        prof = obs.profiler if obs is not None else None
        res = self.resilience
        outcomes = exec_pos = None   # set iff the resilience gate fired
        exec_batch: Sequence[Task] = batch
        try:
            if res is not None:
                res.tick(now_hour)
            with span(prof, "select"):
                choices = self.policy.select_batch(
                    self.cluster, batch, self.weights,
                    provider=self.provider, now_hour=now_hour)
            # Partitioned-execution hook (DESIGN.md §8): a policy exposing
            # execution_latency_ms (e.g. repro.partition.PartitionPolicy)
            # makes the engine execute and bill only the offloaded
            # segment's effective latency. Both execute paths consume the
            # same array, preserving batched/scalar parity.
            eff_fn = getattr(self.policy, "execution_latency_ms", None)
            base_override = eff_fn(batch) if eff_fn is not None else None
            # Failure-aware gate (DESIGN.md §10): only when something is
            # actually wrong — a ground-truth down node or an unplaceable
            # task — otherwise the zero-fault path is untouched.
            if res is not None and (res.down or None in choices):
                outcomes = [None] * len(batch)
                (exec_batch, choices, base_override,
                 exec_pos, _, _) = self._apply_resilience(
                     batch, choices, base_override, now_hour, outcomes,
                     list(range(len(batch))))
            if self.batch_execute:
                self._execute_batched(exec_batch, choices, now_hour,
                                      results, base_override)
            else:
                self._execute_scalar(exec_batch, choices, now_hour, results,
                                     base_override)
            if res is not None:
                if res.health.suspect:
                    res.note_success(set(choices[:len(results)]))
                if self._attempts:
                    for t in exec_batch:
                        self._attempts.pop(id(t), None)
        except BaseException as err:
            tail = list(exec_batch[len(results):])
            self._outcome_totals["done"] += len(results)
            dead = (tail[0] if tail and self._note_failure(tail[0])
                    else None)
            if dead is None:
                # On ANY failure (infeasible node, provider KeyError,
                # execution error) put everything not successfully executed
                # back at the head of the queue, so submitted work is never
                # silently lost.
                self.queue = tail + self.queue
                if outcomes is not None:
                    for j, r in zip(exec_pos, results):
                        outcomes[j] = ("done", r)
                    self.last_outcomes = outcomes
                raise
            # max_requeues-th consecutive failure of the same head task:
            # consume it as a dead letter instead of requeuing it into an
            # infinite raise/requeue loop (DESIGN.md §10)
            reason = f"{type(err).__name__}: {err}"
            self._record_dead(dead, reason)
            if outcomes is None:
                self.queue = tail[1:] + self.queue
                self.last_outcomes = ([("done", r) for r in results]
                                      + [("dead", reason)])
            else:
                # gate-fired step: park the unexecuted survivors as
                # immediate retries so every consumed position carries an
                # outcome (drivers stay aligned with the drained batch)
                for j, r in zip(exec_pos, results):
                    outcomes[j] = ("done", r)
                outcomes[exec_pos[len(results)]] = ("dead", reason)
                for j, t in zip(exec_pos[len(results) + 1:], tail[1:]):
                    self.deferred.append((now_hour, t))
                    self._outcome_totals["retry"] = \
                        self._outcome_totals.get("retry", 0) + 1
                    outcomes[j] = ("retry", now_hour)
                self.last_outcomes = outcomes
            return results
        self._outcome_totals["done"] += len(results)
        if outcomes is not None:
            for j, r in zip(exec_pos, results):
                outcomes[j] = ("done", r)
            self.last_outcomes = outcomes
        if obs is not None:
            # success-only (failed steps requeue and re-trace on retry)
            self._obs_record_step(obs, results, now_hour)
        return results

    def _note_failure(self, task) -> bool:
        """Track the consecutive-failure streak of the task at the failure
        point; True once it has exhausted ``max_requeues`` attempts."""
        if task is self._fail_task:
            self._fail_count += 1
        else:
            self._fail_task = task
            self._fail_count = 1
        if self._fail_count < self.max_requeues:
            return False
        self._fail_task = None
        self._fail_count = 0
        return True

    def _record_dead(self, task, reason: str) -> None:
        self._outcome_totals["dead"] = \
            self._outcome_totals.get("dead", 0) + 1
        self.dead_letters.append((task, reason))
        self._attempts.pop(id(task), None)

    def _apply_resilience(self, tasks, choices, base_override, now_hour,
                          outcomes, pos):
        """The failure-aware gate between selection and execution
        (DESIGN.md §10). Two stages:

        1. **failover**: any task placed onto a ground-truth-down (or
           unknown) node is a *contact failure* — breaker accounting plus
           detection-by-contact masking — and its subset is re-scored in
           one batched ``select_batch`` against the updated availability
           mask. A partition policy re-bills failed-over tasks through
           ``fallback_latency_ms`` (the cut-0 full-offload column): the
           stranded split is discarded and the whole model re-runs on the
           new node.
        2. **retry/dead-letter**: tasks still unplaceable park on
           ``self.deferred`` with capped exponential backoff (a
           ``("retry", wake)`` outcome) until ``max_attempts``, then
           dead-letter.

        ``outcomes`` (full original-batch length) is written in place at
        the removed tasks' ``pos`` entries. Returns the placed subset:
        ``(tasks, choices, base_override, pos, keep, removed)`` with
        ``keep``/``removed`` indexing the *incoming* lists.
        """
        res = self.resilience
        down = res.down
        nodes = self.cluster.nodes
        choices = list(choices)
        bad = [i for i, ch in enumerate(choices)
               if ch is not None and (ch in down or ch not in nodes)]
        if bad:
            self.last_failover_pos = [pos[i] for i in bad]
            for n in {choices[i] for i in bad}:
                res.contact_failure(n, now_hour)
            sub = [tasks[i] for i in bad]
            sub_choices = self.policy.select_batch(
                self.cluster, sub, self.weights, provider=self.provider,
                now_hour=now_hour)
            fb = getattr(self.policy, "fallback_latency_ms", None)
            if base_override is not None:
                base_override = np.array(base_override, dtype=float)
            for k, i in enumerate(bad):
                choices[i] = sub_choices[k]
                if (sub_choices[k] is not None and fb is not None
                        and base_override is not None):
                    base_override[i] = fb(tasks[i])
        keep = list(range(len(tasks)))
        removed: List[int] = []
        if None in choices:
            for i, ch in enumerate(choices):
                if ch is not None:
                    continue
                t = tasks[i]
                attempt = self._attempts.pop(id(t), 0) + 1
                if attempt >= res.max_attempts:
                    reason = f"no feasible node after {attempt} attempts"
                    self._record_dead(t, reason)
                    outcomes[pos[i]] = ("dead", reason)
                else:
                    self._attempts[id(t)] = attempt
                    wake = now_hour + res.backoff_hours(attempt)
                    self.deferred.append((wake, t))
                    self._outcome_totals["retry"] = \
                        self._outcome_totals.get("retry", 0) + 1
                    outcomes[pos[i]] = ("retry", wake)
                removed.append(i)
            keep = [i for i, ch in enumerate(choices) if ch is not None]
            tasks = [tasks[i] for i in keep]
            choices = [choices[i] for i in keep]
            if base_override is not None:
                base_override = np.asarray(base_override, dtype=float)[keep]
            pos = [pos[i] for i in keep]
        return tasks, choices, base_override, pos, keep, removed

    def _step_tenancy(self, batch: Sequence[Task], now_hour: float,
                      results: List[TaskResult]) -> List[TaskResult]:
        """Admission-controlled step (DESIGN.md §7): the tenant policy
        plans admit/defer/reject for the drained batch, rejected tasks
        are dropped (counted in the registry), deferred tasks park on
        ``self.deferred`` until their wake hour, and only the admitted
        subset is placed (mode-escalated), executed and billed — with the
        executed prefix's carbon charged back per tenant even when the
        batch fails mid-way."""
        obs = self.obs
        prof = obs.profiler if obs is not None else None
        res = self.resilience
        try:
            if res is not None:
                res.tick(now_hour)
            with span(prof, "plan"):
                plan = self.policy.plan(self.cluster, batch,
                                        provider=self.provider,
                                        now_hour=now_hour)
        except BaseException:
            # admission itself failed (e.g. a partial-coverage provider
            # KeyError): nothing was consumed, so the whole batch requeues
            # — the same never-silently-lost invariant as the
            # tenancy-free path
            self.queue = list(batch) + self.queue
            raise
        outcomes: List[tuple] = [None] * len(batch)
        if plan.all_admitted:
            aidx = None
            exec_tasks: Sequence[Task] = batch
        else:
            from repro.tenancy.policy import DEFER as _DEFER
            from repro.tenancy.policy import REJECT as _REJECT
            aidx = plan.admitted_index()
            exec_tasks = [batch[i] for i in aidx]
            rej = np.nonzero(plan.actions == _REJECT)[0]
            deferred = np.nonzero(plan.actions == _DEFER)[0]
            for i in rej:
                outcomes[i] = ("reject", "carbon budget exhausted")
            for i in deferred:
                w = float(plan.wake_hour[i])
                self.deferred.append((w, batch[i]))
                outcomes[i] = ("defer", w)
            # rejected/deferred verdicts are consumed whatever happens next
            self._outcome_totals["reject"] += int(rej.size)
            self._outcome_totals["defer"] += int(deferred.size)
        # admitted tenant ids / original-batch positions, kept consistent
        # with exec_tasks through the resilience gate's rewrites
        sel = np.asarray(plan.tenant_idx if aidx is None
                         else plan.tenant_idx[aidx])
        pos = (list(range(len(batch))) if aidx is None
               else [int(i) for i in aidx])
        gate_fired = False
        dead_reason = None
        try:
            with span(prof, "select"):
                full = self.policy.select_admitted(
                    self.cluster, batch, plan, self.weights,
                    provider=self.provider, now_hour=now_hour)
            choices = (full if aidx is None
                       else [full[i] for i in aidx])
            if res is not None and (res.down or None in choices):
                gate_fired = True
                (exec_tasks, choices, _, pos,
                 keep, removed) = self._apply_resilience(
                     exec_tasks, choices, None, now_hour, outcomes, pos)
                if removed:
                    # retried/dead tasks get re-planned (or never run):
                    # reverse their admitted counting now
                    self.policy.registry.uncount_admitted(sel[removed])
                    sel = sel[keep]
            if self.batch_execute:
                self._execute_batched(exec_tasks, choices, now_hour, results)
            else:
                self._execute_scalar(exec_tasks, choices, now_hour, results)
            if res is not None:
                if res.health.suspect:
                    res.note_success(set(choices[:len(results)]))
                if self._attempts:
                    for t in exec_tasks:
                        self._attempts.pop(id(t), None)
        except BaseException as err:
            requeued = list(exec_tasks[len(results):])
            if requeued:
                # requeued tasks get re-planned (and re-counted) on the
                # retry, so reverse this plan's admitted counting for them
                self.policy.registry.uncount_admitted(sel[len(results):])
            dead = (requeued[0] if requeued
                    and self._note_failure(requeued[0]) else None)
            if dead is None:
                self.queue = requeued + self.queue
                raise
            # attempt cap reached: consume the poisoned head as a dead
            # letter (DESIGN.md §10) and keep the step's results
            dead_reason = f"{type(err).__name__}: {err}"
            self._record_dead(dead, dead_reason)
            # park the unexecuted survivors as immediate retries so every
            # consumed position carries an outcome — admitted positions can
            # precede deferred/rejected ones, so a silent requeue would
            # desynchronize outcome-tracking drivers from the drained batch
            for j, t in zip(pos[len(results) + 1:], requeued[1:]):
                self.deferred.append((now_hour, t))
                self._outcome_totals["retry"] = \
                    self._outcome_totals.get("retry", 0) + 1
                outcomes[j] = ("retry", now_hour)
        finally:
            # charge exactly the executed prefix — on a mid-batch failure
            # that is the same set the cluster/monitor ledgers billed
            if results:
                self.policy.charge(sel[:len(results)],
                                   [r.carbon_g for r in results], now_hour)
            # publish verdicts even when execution raised mid-batch:
            # rejected/deferred tasks were consumed, so a caller tracking
            # per-request state must still see them; None marks the
            # requeued admitted tail
            for j, r in zip(pos, results):
                outcomes[j] = ("done", r)
            if dead_reason is not None:
                outcomes[pos[len(results)]] = ("dead", dead_reason)
            self.last_outcomes = outcomes
            self._outcome_totals["done"] += len(results)
        if dead_reason is not None:
            return results
        if obs is not None:
            # success-only, like the tenancy-free path
            self._obs_record_tenancy(obs, batch, plan, results, now_hour,
                                     aidx,
                                     exec_pos=pos if gate_fired else None)
        return results

    def pop_ripe(self, now_hour: float) -> List[Task]:
        """Remove and return budget-deferred tasks whose wake hour has
        arrived, in park order — the caller resubmits them (the sim
        driver does this on its tenancy DEFER_WAKE event;
        :meth:`run_until` does it automatically)."""
        if not self.deferred:
            return []
        ripe = [t for w, t in self.deferred if w <= now_hour]
        if ripe:
            self.deferred = [(w, t) for w, t in self.deferred
                             if w > now_hour]
        return ripe

    def _execute_scalar(self, batch: Sequence[Task],
                        choices: Sequence[Optional[str]], now_hour: float,
                        results: List[TaskResult],
                        base_override=None) -> None:
        """Per-task execute+bill loop — the parity oracle the batched path
        is bit-identical to (cluster/monitor ledgers, log, requeue state).
        ``base_override`` replaces each task's base latency (the policy's
        partitioned effective latency), same array the batched path uses."""
        for i, (task, node) in enumerate(zip(batch, choices)):
            if node is None:
                # Already-executed results travel on the exception; the
                # infeasible task and the tail are requeued by step().
                raise NoFeasibleNodeError(results)
            st = self.cluster.nodes[node]
            # Resolve every billing input BEFORE executing, so a
            # provider/monitor lookup failure cannot leave a task
            # executed in the cluster ledger yet requeued for a retry
            # (which would double-execute it).
            exec_intensity = self.provider.intensity(node, now_hour)
            self.monitor.billing_intensity(node, now_hour)
            base = (task.base_latency_ms if base_override is None
                    else float(base_override[i]))
            st.running += 1
            try:
                res = self.cluster.execute(
                    node, base, distributed=True,
                    intensity=exec_intensity)
            finally:
                st.running -= 1
            self.monitor.record_energy(node, res.energy_kwh,
                                       hour=now_hour)
            results.append(res)

    def _probe_intensities(self, nodes: Sequence[str], now_hour: float):
        """Scalar-order resolution fallback: probe node-by-node *in first-
        appearance order* so a failure cuts the batch at exactly the task
        the scalar loop would have failed on. Returns
        ``(exec_int, bill_int, n_ok, error)``: dicts covering the nodes of
        the first ``n_ok`` tasks, plus the captured per-node exception."""
        exec_int, bill_int = {}, {}
        for i, n in enumerate(nodes):
            if n in exec_int:
                continue
            try:
                # exactly the scalar loop's resolution order: node lookup,
                # provider read, monitor billing probe
                self.cluster.nodes[n]
                ei = self.provider.intensity(n, now_hour)
                bi = self.monitor.billing_intensity(n, now_hour)
            except Exception as err:
                return exec_int, bill_int, i, err
            exec_int[n] = ei
            bill_int[n] = bi
        return exec_int, bill_int, len(nodes), None

    def _execute_batched(self, batch: Sequence[Task],
                         choices: Sequence[Optional[str]], now_hour: float,
                         results: List[TaskResult],
                         base_override=None) -> None:
        """Vectorized execute+bill (DESIGN.md §6): one
        ``cluster.execute_batch`` + one ``monitor.record_energy_batch`` for
        the feasible prefix — O(distinct nodes) Python work per step
        instead of O(B) — preserving the scalar loop's mid-batch failure
        semantics: tasks before the first infeasible/unresolvable one are
        executed and billed, the rest requeue via step()'s handler.

        Every billing input resolves BEFORE anything executes (the scalar
        loop's commit rule): execution intensity through one batched
        provider read over the distinct chosen nodes, billing intensity
        through one ``monitor.billing_intensity_batch`` — degrading to the
        per-node probe (``_probe_intensities``) when any node is unknown
        or uncovered, so the failing task index matches the scalar loop's.
        """
        # Cut at the first infeasible task: the scalar loop executes
        # everything before it, then raises with those results attached.
        try:
            cut = choices.index(None)
            failure = NoFeasibleNodeError(results)
        except ValueError:
            cut, failure = len(batch), None
        nodes = list(choices[:cut])
        groups = ev = bv = None
        if nodes:
            groups = np.unique(np.asarray(nodes, dtype=object),
                               return_inverse=True)
            uniq, inverse = groups
            try:
                for n in uniq:
                    if n not in self.cluster.nodes:
                        raise KeyError(n)
                ev = np.asarray(intensity_batch(self.provider, list(uniq),
                                                now_hour), dtype=float)
                bv = self.monitor.billing_intensity_batch(list(uniq),
                                                          now_hour)
            except Exception:
                exec_int, bill_int, n_ok, err = self._probe_intensities(
                    nodes, now_hour)
                if err is None:
                    # batch read failed but every per-node probe succeeded
                    # (inconsistent custom provider): use the probed values
                    ev = np.array([exec_int[n] for n in uniq], dtype=float)
                    bv = np.array([bill_int[n] for n in uniq], dtype=float)
                else:
                    cut, failure = n_ok, err
                    nodes = nodes[:cut]
                    if nodes:
                        groups = np.unique(np.asarray(nodes, dtype=object),
                                           return_inverse=True)
                        uniq, inverse = groups
                        ev = np.array([exec_int[n] for n in uniq],
                                      dtype=float)
                        bv = np.array([bill_int[n] for n in uniq],
                                      dtype=float)
        if nodes:
            obs = self.obs
            prof = obs.profiler if obs is not None else None
            base = (np.array([t.base_latency_ms for t in batch[:cut]],
                             dtype=float)
                    if base_override is None
                    else np.asarray(base_override[:cut], dtype=float))
            with span(prof, "execute"):
                res = self.cluster.execute_batch(
                    nodes, base, distributed=True, intensities=ev[inverse],
                    groups=groups)
            # The billed energy is recomputed through the cluster's own
            # cost model (the same call execute_batch makes) rather than
            # gathered back out of the B result objects — same floats, no
            # O(B) attribute reads, one source of truth for the math.
            with span(prof, "bill"):
                lat_ms, e_kwh = self.cluster.latency_energy(base,
                                                            distributed=True)
                self.monitor.record_energy_batch(
                    nodes, e_kwh, hour=now_hour, intensities=bv[inverse],
                    groups=groups)
            results.extend(res)
            if failure is None:
                # whole batch executed: publish the step's execution
                # columns for the sim driver's columnar record path
                # (DESIGN.md §11). carbon_g here is the same elementwise
                # expression execute_batch evaluated, so the arrays carry
                # the exact floats the TaskResults do.
                self.last_exec = (uniq, inverse, lat_ms, e_kwh,
                                  carbon_g(e_kwh, ev[inverse],
                                           self.cluster.pue))
            if obs is not None and (obs.trace is not None
                                    or obs.metrics is not None
                                    or obs.rollups is not None):
                # stash the already-computed batched arrays so the trace/
                # metrics record after a successful step adds no provider
                # re-reads or O(B) Python (DESIGN.md §9)
                self._exec_snapshot = (uniq, inverse, ev, bv, e_kwh)
        if failure is not None:
            # `results` is the shared list step() requeues against, so the
            # exception's executed-prefix view matches the scalar loop's.
            raise failure

    def run(self, tasks: Optional[Sequence[Task]] = None, *,
            task: Optional[Task] = None, iterations: int = 1,
            now_hour: float = 0.0) -> Dict:
        """Submit ``tasks`` (or ``iterations`` copies of ``task``, default
        one), drain the queue in batched steps, and return :meth:`report`.

        .. deprecated:: the whole queue is drained at a single frozen
           ``now_hour``, which silently mis-bills time-varying providers
           (every batch reads the grid at the submission instant, however
           long the drain takes). With a non-static provider prefer
           :meth:`run_until` (minimal time-advancing drain) or the full
           event-driven :class:`repro.sim.AsyncEngineDriver`; this shim
           stays exact for the static paper scenarios.
        """
        if not isinstance(self.provider, StaticProvider):
            warnings.warn(
                "CarbonEdgeEngine.run drains the queue at one frozen "
                "now_hour; with a time-varying CarbonIntensityProvider use "
                "run_until() or repro.sim.AsyncEngineDriver so billing "
                "tracks simulated time", DeprecationWarning, stacklevel=2)
        if tasks is not None:
            self.submit_many(tasks)
        if task is not None:
            self.submit_many([task] * iterations)
        while self.queue:
            self.step(now_hour)
        if self.deferred:
            # run() freezes the clock, so budget-deferred work can never
            # reach its wake hour here — tell the caller instead of
            # silently dropping it (run_until()/pop_ripe() resume it)
            warnings.warn(
                f"CarbonEdgeEngine.run left {len(self.deferred)} "
                "budget-deferred task(s) parked: the frozen now_hour "
                "never reaches their accounting-period wake; use "
                "run_until() or pop_ripe() to resume them",
                RuntimeWarning, stacklevel=2)
        return self.report()

    def run_until(self, end_hour: float, *, start_hour: float = 0.0,
                  limit: Optional[int] = None) -> Dict:
        """Drain the queue in batched steps while *advancing simulated
        time*: each batch is billed at the hour the previous batches'
        measured service time has accumulated to (the cluster is a serial
        executor, so a batch of total latency L ms advances the clock by
        L / 3.6e6 hours). Stops when the queue is empty or the clock
        passes ``end_hour`` (the remainder stays queued). Returns
        :meth:`report` plus the final clock under ``"end_hour"``.

        This is the minimal time-advancing replacement for :meth:`run`;
        arrival dynamics, deferral and queueing metrics live in the full
        event-driven :class:`repro.sim.AsyncEngineDriver`.
        """
        now = start_hour
        while now < end_hour:
            self.queue[:0] = self.pop_ripe(now)
            if not self.queue:
                # idle but budget-deferred work exists: jump the clock to
                # the earliest wake inside the window
                wake = min((w for w, _ in self.deferred if w < end_hour),
                           default=None)
                if wake is None:
                    break
                now = max(now, wake)
                continue
            qlen = len(self.queue)
            results = self.step(now, limit=limit)
            if not results and len(self.queue) >= qlen:
                # zero-size limit or a step that drained nothing: no
                # progress is possible, bail instead of spinning forever
                break
            now += sum(r.latency_ms for r in results) / 3.6e6
        rep = self.report()
        rep["end_hour"] = now
        return rep

    # -- observability (DESIGN.md §9) --------------------------------------
    def _obs_metrics_nodes(self, metrics, uniq, inverse, carbon) -> None:
        """Per-node task and carbon counters from the step's grouped
        arrays: O(distinct nodes) label interning, scatter-add updates."""
        counts = np.bincount(inverse, minlength=len(uniq))
        csum = np.bincount(inverse, weights=carbon, minlength=len(uniq))
        for name, help_, vals in (
                ("engine_tasks_total", "tasks executed per node", counts),
                ("engine_carbon_g_total",
                 "carbon billed per node (gCO2)", csum)):
            fam = metrics.counter(name, help_, ("node",))
            fam.inc_at(fam.rows([(str(n),) for n in uniq]), vals)

    def _obs_metrics_depths(self, metrics) -> None:
        metrics.gauge("engine_queue_depth",
                      "tasks pending in the engine queue"
                      ).set(float(len(self.queue)))
        metrics.gauge("engine_deferred_depth",
                      "budget-deferred tasks parked"
                      ).set(float(len(self.deferred)))

    def _obs_intervals(self, uniq, inverse, now_hour):
        """Conformal (lo, hi) per task when the provider carries a
        calibrator, else (None, None) — zero-width intervals from plain
        providers carry no information, so skip the extra read."""
        if getattr(self.provider, "conformal", None) is None:
            return None, None
        lo, hi = intensity_interval_batch(self.provider, list(uniq),
                                          now_hour)
        return (np.asarray(lo, dtype=float)[inverse],
                np.asarray(hi, dtype=float)[inverse])

    def _obs_record_step(self, obs, results, now_hour: float) -> None:
        """Trace + metrics for one successful tenancy-free step, fed from
        the batched-execute snapshot (no per-task Python; the scalar
        parity oracle falls back to gathering from its B results)."""
        trace, metrics = obs.trace, obs.metrics
        roll = obs.rollups
        if trace is None and metrics is None and roll is None:
            return
        B = len(results)
        if B == 0:
            return
        with span(obs.profiler, "observe"):
            snap = self._exec_snapshot
            if snap is not None:
                uniq, inverse, ev, bv, e_kwh = snap
                ev_t = ev[inverse]
                # same expression execute_batch billed with — identical floats
                carbon = carbon_g(e_kwh, ev_t, self.cluster.pue)
            else:
                uniq, inverse = np.unique(
                    np.asarray([r.node for r in results], dtype=object),
                    return_inverse=True)
                ev = np.asarray(intensity_batch(self.provider, list(uniq),
                                                now_hour), dtype=float)
                ev_t = ev[inverse]
                bv = np.asarray(self.monitor.billing_intensity_batch(
                    list(uniq), now_hour), dtype=float)
                carbon = np.asarray([r.carbon_g for r in results], dtype=float)
                e_kwh = (np.asarray([r.energy_kwh for r in results], dtype=float)
                         if roll is not None else None)
            if roll is not None:
                roll.fold_exec(now_hour, carbon, e_kwh)
                roll.fold_verdicts(now_hour, (B, 0, 0, 0, 0))  # all done
            if trace is not None:
                lo, hi = self._obs_intervals(uniq, inverse, now_hour)
                score = runner = cut = None
                ls = getattr(self.policy, "last_scores", None)
                if ls is not None and ls.get("score") is not None \
                        and len(ls["score"]) == B:
                    score, runner = ls["score"], ls.get("runner_up")
                    cut = ls.get("cut")
                trace.record_batch(
                    step=self._steps, hour=now_hour,
                    verdict=np.zeros(B, dtype=np.int8),   # all done
                    node=trace.intern_names(uniq)[inverse],
                    cut=cut, mode=self._mode_idx,
                    score=score, runner_up=runner,
                    intensity=ev_t, interval_lo=lo, interval_hi=hi,
                    intensity_billed=bv[inverse], carbon_g=carbon)
            if metrics is not None:
                self._obs_metrics_nodes(metrics, uniq, inverse, carbon)
                metrics.counter("engine_outcomes_total",
                                "step outcomes by verdict", ("verdict",)
                                ).inc(B, labels=("done",))
                self._obs_metrics_depths(metrics)

    def _obs_record_tenancy(self, obs, batch, plan, results, now_hour,
                            aidx, exec_pos=None) -> None:
        """Trace + metrics for one successful admission-controlled step:
        full-length rows (rejected/deferred tasks get their verdict with
        no placement), executed columns scattered at the admitted
        positions from the batched-execute snapshot. ``exec_pos`` (set
        when the resilience gate rewrote the admitted subset) overrides
        the executed positions and sources verdicts from the published
        outcomes, so retried/dead rows trace as such."""
        trace, metrics = obs.trace, obs.metrics
        roll = obs.rollups
        if trace is None and metrics is None and roll is None:
            return
        with span(obs.profiler, "observe"):
            from repro.tenancy.policy import ADMIT as _ADMIT
            from repro.tenancy.policy import REJECT as _REJECT
            B = len(batch)
            if exec_pos is not None:
                from repro.obs.trace import VERDICT_LABELS
                codes = {k: c for c, k in enumerate(VERDICT_LABELS)}
                verdict = np.array([codes[o[0]] for o in self.last_outcomes],
                                   dtype=np.int8)
                pos_exec = np.asarray(exec_pos[:len(results)], dtype=int)
            else:
                # explicit action -> trace-verdict map (the two encodings order
                # DEFER/REJECT differently)
                verdict = np.where(
                    plan.actions == _ADMIT, 0,
                    np.where(plan.actions == _REJECT, 1, 2)).astype(np.int8)
                pos_exec = (np.arange(len(results)) if aidx is None
                            else np.asarray(aidx))
            uniq = inverse = carbon = e_kwh = None
            if results:
                snap = self._exec_snapshot
                if snap is not None:
                    uniq, inverse, ev, bv, e_kwh = snap
                    ev_t = ev[inverse]
                    carbon = carbon_g(e_kwh, ev_t, self.cluster.pue)
                else:
                    uniq, inverse = np.unique(
                        np.asarray([r.node for r in results], dtype=object),
                        return_inverse=True)
                    ev = np.asarray(intensity_batch(self.provider, list(uniq),
                                                    now_hour), dtype=float)
                    ev_t = ev[inverse]
                    bv = np.asarray(self.monitor.billing_intensity_batch(
                        list(uniq), now_hour), dtype=float)
                    carbon = np.asarray([r.carbon_g for r in results],
                                        dtype=float)
                    e_kwh = (np.asarray([r.energy_kwh for r in results],
                                        dtype=float)
                             if roll is not None else None)
            if roll is not None:
                if results:
                    roll.fold_exec(now_hour, carbon, e_kwh)
                    reg = getattr(self.policy, "registry", None)
                    index = getattr(reg, "index", None)
                    if index:
                        names = np.asarray(sorted(index, key=index.get),
                                           dtype=object)
                        tmap = roll.intern_tenants(names)
                        tidx = np.asarray(plan.tenant_idx)[pos_exec]
                        tagged = tidx >= 0
                        if tagged.any():
                            roll.fold_tenant_spend(now_hour, tmap[tidx[tagged]],
                                                   carbon[tagged])
                roll.fold_verdicts(
                    now_hour, np.bincount(verdict, minlength=5)[:5])
            if trace is not None:
                node = np.full(B, -1, dtype=np.int32)
                intens = np.full(B, np.nan)
                billed = np.full(B, np.nan)
                carb = np.full(B, np.nan)
                ilo = ihi = None
                if results:
                    node[pos_exec] = trace.intern_names(uniq)[inverse]
                    intens[pos_exec] = ev_t
                    billed[pos_exec] = bv[inverse]
                    carb[pos_exec] = carbon
                    lo, hi = self._obs_intervals(uniq, inverse, now_hour)
                    if lo is not None:
                        ilo = np.full(B, np.nan)
                        ihi = np.full(B, np.nan)
                        ilo[pos_exec] = lo
                        ihi[pos_exec] = hi
                # -1 (untagged / no escalation) means the engine's own mode
                modes = np.where(plan.modes >= 0, plan.modes,
                                 self._mode_idx).astype(np.int8)
                tenant = None
                reg = getattr(self.policy, "registry", None)
                index = getattr(reg, "index", None)
                if index:
                    names = np.asarray(sorted(index, key=index.get),
                                       dtype=object)
                    tmap = trace.intern_names(names, kind="tenant")
                    tidx = np.asarray(plan.tenant_idx)
                    tenant = np.where(tidx >= 0,
                                      tmap[np.maximum(tidx, 0)],
                                      -1).astype(np.int32)
                score = runner = cut = None
                ls = getattr(self.policy, "last_scores", None)
                if ls is not None and ls.get("score") is not None \
                        and len(ls["score"]) == B:
                    score, runner = ls["score"], ls.get("runner_up")
                    cut = ls.get("cut")
                trace.record_batch(
                    step=self._steps, hour=now_hour, verdict=verdict,
                    node=node, cut=cut, mode=modes, tenant=tenant,
                    score=score, runner_up=runner,
                    intensity=intens, interval_lo=ilo, interval_hi=ihi,
                    intensity_billed=billed, carbon_g=carb,
                    expected_g=plan.expected_g)
            if metrics is not None:
                if results:
                    self._obs_metrics_nodes(metrics, uniq, inverse, carbon)
                fam = metrics.counter("engine_outcomes_total",
                                      "step outcomes by verdict", ("verdict",))
                for code, label in enumerate(
                        ("done", "reject", "defer", "dead", "retry")):
                    n = int((verdict == code).sum())
                    if n:
                        fam.inc(n, labels=(label,))
                self._obs_metrics_depths(metrics)

    # -- reporting ---------------------------------------------------------
    def report(self, deep: bool = False) -> Dict:
        rep = {
            "totals": self.cluster.totals(),
            "distribution": self.cluster.distribution(),
            "policy": self.policy.name,
            "per_region": self.monitor.report(),
            "steps": self._steps,
            "outcomes": dict(self._outcome_totals),
            "deferred_depth": len(self.deferred),
        }
        if self._tenancy is not None:
            rep["tenants"] = self._tenancy.registry.report()
        if self.resilience is not None or self.dead_letters:
            rep["resilience"] = {
                "dead_letters": len(self.dead_letters),
                "retrying": len(self._attempts),
            }
            if self.resilience is not None:
                rep["resilience"].update(self.resilience.report())
        if deep:
            rep["deep"] = self._report_deep()
        return rep

    def _report_deep(self) -> Dict:
        """Structured diagnostics (DESIGN.md §9): obs pillar summaries
        plus partition / deferral / conformal-coverage aggregates. A
        diagnostic call — may do O(retained-trace) work."""
        deep: Dict = {}
        obs = self.obs
        if obs is not None:
            if obs.profiler is not None:
                deep["profiler"] = obs.profiler.summary()
            if obs.trace is not None:
                deep["trace"] = obs.trace.stats()
                deep["conformal"] = obs.trace.conformal_coverage()
                cuts = obs.trace.cut_histogram()
                if cuts:
                    deep["partition"] = {"cut_histogram": cuts}
            if obs.journeys is not None:
                deep["journeys"] = obs.journeys.stats()
            if obs.rollups is not None:
                deep["rollups"] = obs.rollups.stats()
            if obs.alerts is not None:
                deep["alerts"] = obs.alerts.stats()
            if obs.metrics is not None:
                deep["metrics"] = obs.metrics.snapshot()
        deep["deferral"] = {
            "parked": len(self.deferred),
            "deferred_total": self._outcome_totals["defer"],
            "next_wake": (min(w for w, _ in self.deferred)
                          if self.deferred else None),
        }
        # last-batch partition decisions work without tracing too
        decisions = getattr(self.policy, "last_decisions", None)
        if decisions:
            hist: Dict[int, int] = {}
            for d in decisions:
                if d is not None:
                    hist[d.cut_index] = hist.get(d.cut_index, 0) + 1
            deep.setdefault("partition", {})["last_batch_cuts"] = hist
        return deep
