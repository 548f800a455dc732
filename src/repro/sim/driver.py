"""Async engine driver: the discrete-event serving loop (DESIGN.md §2).

:class:`AsyncEngineDriver` interleaves an arrival process with batched
executor steps through simulated time:

- ``ARRIVAL`` events materialise tasks (via ``task_factory``) and enqueue
  them on the executor; deferrable tasks (``deadline_hours > 0``) are
  instead *planned* through :func:`repro.core.temporal.plan_wake` against
  the driver's forecast provider and parked until their ``DEFER_WAKE``
  (fleet-scale: the planner reads the whole (slots x nodes) grid in one
  batched provider call — DESIGN.md §3.6);
- ``BATCH_READY`` events drain up to ``max_batch`` pending tasks in one
  ``executor.step(now_hour=clock.hour, limit=...)`` call — with the
  default :class:`~repro.core.api.CarbonEdgeEngine` that is one (B, N, 8)
  featurize + one vectorized/Pallas scorer invocation per event batch,
  not one per task, and since DESIGN.md §6 the execute+billing half is
  batched too (one ``cluster.execute_batch`` + one
  ``monitor.record_energy_batch`` per drained batch, bit-identical to the
  per-task loop, so ``metrics.to_text`` is byte-stable across both
  execution paths) — honouring the executor's busy time so queueing
  delay emerges from load rather than being assumed;
- ``INTENSITY_TICK`` events sample the carbon-vs-latency timeline.

``now_hour`` is always the virtual clock, so every provider read (policy
scoring, cluster billing, monitor billing) tracks simulated time — the
property :meth:`CarbonEdgeEngine.run` cannot offer (it freezes the hour
for the whole drain).

Event queues (DESIGN.md §11): ``event_queue="calendar"`` (the default)
runs the loop over the array-based :class:`EventCalendar` — same-kind
event runs pop as numpy slices, client verdicts and metric records move
in column batches, so driver overhead is O(batches).
``event_queue="heap"`` keeps the original scalar loop over
:class:`EventHeap`, retained as the bit-exact parity oracle: both modes
produce byte-identical ``metrics.to_text()`` for the same scenario
(``gate_sim_scale`` pins this in CI).

Executors: anything with ``submit(task)`` and
``step(now_hour, limit) -> results`` — ``CarbonEdgeEngine`` natively, and
``runtime.serving.ServingEngine`` through its ``step`` alias. Results
expose either ``latency_ms`` (serial cluster: service times accumulate)
or ``service_s`` (parallel serving batch: the batch occupies the executor
for its max service time). Note the determinism contract (DESIGN.md
§2.2) covers modelled executors only: a ServingEngine measures real
wall-clock service, so its runs repeat only up to host timing noise.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import (Callable, Dict, List, Optional, Protocol, Sequence,
                    runtime_checkable)

import numpy as np

from repro.core.providers import intensity_batch
from repro.obs.journey import PARK_DEFER, PARK_RETRY
from repro.obs.profiler import span
from repro.sim.arrivals import ArrivalProcess, ClosedLoopClientPool
from repro.sim.clock import VirtualClock, hours_to_s, ms_to_hours, s_to_hours
from repro.sim.events import (KIND_CODE, EventCalendar, EventHeap, EventKind)
from repro.sim.metrics import MetricsCollector, TaskRecord, TimelineSample


@runtime_checkable
class BatchExecutor(Protocol):
    """What the driver needs from an engine."""

    def submit(self, task) -> object: ...

    def step(self, now_hour: float = 0.0,
             limit: Optional[int] = None) -> Sequence: ...


@dataclass
class _Pending:
    uid: int
    submit_hour: float
    deferred_hours: float = 0.0
    tenant: str = ""
    client: Optional[int] = None     # closed-loop client id, if any


class _PendFifo:
    """The pending-submission FIFO in column form (DESIGN.md §11): one
    list per :class:`_Pending` field plus a head cursor, so draining a
    batch is a slice — not an O(queue) list copy per event batch, which
    at 10^6 backlogged clients turned the driver quadratic. Used by both
    queue modes; the scalar path materializes `_Pending` objects from the
    columns on take, so its record loop is unchanged."""

    __slots__ = ("_uid", "_sub", "_def", "_ten", "_cli", "_head")

    def __init__(self):
        self._uid: List[int] = []
        self._sub: List[float] = []
        self._def: List[float] = []
        self._ten: List[str] = []
        self._cli: List[int] = []    # -1 = not a closed-loop request
        self._head = 0

    def __len__(self) -> int:
        return len(self._uid) - self._head

    def __bool__(self) -> bool:
        return len(self._uid) > self._head

    def append(self, p: _Pending) -> None:
        self._uid.append(p.uid)
        self._sub.append(p.submit_hour)
        self._def.append(p.deferred_hours)
        self._ten.append(p.tenant)
        self._cli.append(-1 if p.client is None else p.client)

    def append_arrays(self, uids, submit_hours, tenants,
                      client_ids=None) -> None:
        self._uid.extend(uids.tolist())
        self._sub.extend(submit_hours.tolist())
        self._def.extend([0.0] * len(tenants))
        self._ten.extend(tenants)
        if client_ids is None:
            self._cli.extend([-1] * len(tenants))
        else:
            self._cli.extend(client_ids.tolist())

    def _compact(self) -> None:
        h = self._head
        if h > 1024 and h * 2 > len(self._uid):
            del self._uid[:h], self._sub[:h], self._def[:h]
            del self._ten[:h], self._cli[:h]
            self._head = 0

    def take_list(self, n: int) -> List[_Pending]:
        """Drain the first ``n`` entries as `_Pending` objects (the
        scalar record path)."""
        a = self._head
        z = min(a + n, len(self._uid))
        self._head = z
        out = [_Pending(u, s, d, t, None if c < 0 else c)
               for u, s, d, t, c in zip(
                   self._uid[a:z], self._sub[a:z], self._def[a:z],
                   self._ten[a:z], self._cli[a:z])]
        self._compact()
        return out

    def take_arrays(self, n: int):
        """Drain the first ``n`` entries as columns:
        ``(uids, submit_hours, deferred_hours, tenants, client_ids)``."""
        a = self._head
        z = min(a + n, len(self._uid))
        self._head = z
        out = (np.asarray(self._uid[a:z], dtype=np.int64),
               np.asarray(self._sub[a:z], dtype=float),
               np.asarray(self._def[a:z], dtype=float),
               self._ten[a:z],
               np.asarray(self._cli[a:z], dtype=np.int64))
        self._compact()
        return out


_CR = KIND_CODE[EventKind.CLIENT_READY]
_RT = KIND_CODE[EventKind.RETRY]
_AR = KIND_CODE[EventKind.ARRIVAL]


class AsyncEngineDriver:
    """Drive a batch executor through simulated time under an arrival
    process, producing queueing/SLO/carbon metrics.

    ``task_factory(uid, hour)`` builds the submitted object (a ``Task``,
    ``DeferrableTask`` or serving ``Request``). When ``forecast`` is given
    (any provider; a :class:`~repro.core.providers.ForecastProvider` uses its
    ``window``), tasks with ``deadline_hours > 0`` are deferred to the
    minimum-forecast-intensity slot within their deadline.
    """

    def __init__(self, executor: BatchExecutor,
                 arrivals: Optional[ArrivalProcess],
                 task_factory: Callable[..., object], *,
                 start_hour: float = 0.0, horizon_hours: float = 1.0,
                 max_batch: int = 8, batch_window_hours: float = 0.0,
                 forecast=None, slot_hours: float = 0.5,
                 slo_latency_s: Optional[float] = None,
                 tick_hours: float = 0.0,
                 clients: Optional[ClosedLoopClientPool] = None,
                 risk_coverage: Optional[float] = None,
                 obs=None, faults=None,
                 event_queue: str = "calendar"):
        if arrivals is None and clients is None:
            raise ValueError("need an arrival process, a closed-loop "
                             "client pool, or both")
        if event_queue not in ("calendar", "heap"):
            raise ValueError("event_queue must be 'calendar' or 'heap', "
                             f"got {event_queue!r}")
        self.executor = executor
        self.arrivals = arrivals
        self.task_factory = task_factory
        self.start_hour = start_hour
        self.horizon_hours = horizon_hours
        self.max_batch = max_batch
        self.batch_window_hours = batch_window_hours
        self.forecast = forecast
        self.slot_hours = slot_hours
        # Risk-bounded deferral planning (DESIGN.md §8): with a coverage
        # level set, deferrable arrivals are planned through
        # plan_wake_risk — a task parks only when the forecast's conformal
        # interval says the future slot beats executing now even at the
        # interval's pessimistic end. None keeps point-forecast planning.
        if risk_coverage is not None and not 0.0 < risk_coverage < 1.0:
            raise ValueError("risk_coverage must be in (0, 1) or None")
        self.risk_coverage = risk_coverage
        self.tick_hours = tick_hours
        # Closed-loop mode (DESIGN.md §7): `clients` drives CLIENT_READY /
        # RETRY events and the task_factory is called as
        # factory(uid, hour, tenant) — for EVERY task source, so mixing an
        # open-loop arrival process with client populations keeps one
        # factory signature (ARRIVAL events pass tenant=""). New requests
        # stop at the horizon; in-flight ones drain.
        self.clients = clients
        # Observability (DESIGN.md §9, §12): spans around the
        # step/record/plan phases of each event batch plus per-EventKind
        # counters; the journeys pillar records each uid's causal path at
        # the enqueue/drain/outcome hooks, and the rollups pillar gets the
        # driver-side folds (SLO misses, availability — the engine folds
        # carbon/energy/verdicts/tenant spend, so sharing one hub between
        # both layers never double-counts). Off (None / disabled) leaves
        # the event loop byte-identical — every hook sits behind a single
        # `is not None` check. Pass the same Observability to the engine
        # and the driver to get one unified view across both layers.
        self.obs = obs if obs is not None and obs.enabled else None
        # Fault injection (DESIGN.md §10): a repro.resilience.FaultInjector
        # whose schedule is surfaced as NODE_DOWN/NODE_UP/PROVIDER_OUTAGE
        # events and applied to the executor when each fires. None (the
        # default) leaves the event loop byte-identical.
        self.faults = faults
        self.clock = VirtualClock(start_hour)
        self._vectorized = event_queue == "calendar"
        self.heap = EventCalendar() if self._vectorized else EventHeap()
        self.metrics = MetricsCollector(slo_latency_s=slo_latency_s)
        self._pending = _PendFifo()          # FIFO, mirrors executor queue
        self._parked: List[tuple] = []       # budget-deferred (wake, _Pending)
        # Earliest armed BATCH_READY hour, or None. Single-flush
        # discipline: _schedule_flush pushes only when nothing is armed
        # or the new flush fires strictly earlier (the superseded event
        # then pops as a harmless extra drain). An unconditional push per
        # fill-triggering enqueue looks equivalent but is quadratic under
        # sustained saturation: every pop re-arms one flush, so the
        # BATCH_READY population grows by one per enqueue and each
        # 256-task drain drags the whole population of same-time events
        # along with it (~10^9 pops at 10^6 closed-loop clients).
        self._flush_at: Optional[float] = None
        self._busy_until = start_hour
        self._uid = 0
        self.events_processed = 0

    # -- planning ------------------------------------------------------------
    def _plan(self, task, now: float) -> float:
        """Wake hour for a deferrable task (== now when not deferrable or
        no forecast/cluster to plan against)."""
        if self.forecast is None or getattr(task, "deadline_hours", 0.0) <= 0:
            return now
        cluster = getattr(self.executor, "cluster", None)
        if cluster is None:
            return now
        prof = self.obs.profiler if self.obs is not None else None
        with span(prof, "sim_plan"):
            if self.risk_coverage is not None:
                from repro.core.temporal import plan_wake_risk
                wake = plan_wake_risk(self.forecast, cluster, task, now,
                                      slot_hours=self.slot_hours,
                                      coverage=self.risk_coverage)
            else:
                from repro.core.temporal import plan_wake
                wake = plan_wake(self.forecast, cluster, task, now,
                                 slot_hours=self.slot_hours)
        return wake

    # -- event handlers ------------------------------------------------------
    def _enqueue(self, uid: int, task, submit_hour: float,
                 deferred_hours: float, now: float,
                 client: Optional[int] = None) -> None:
        # Keep the executor's own clock on sim time: a serving Request
        # not pre-stamped by the factory would otherwise get a *wall*
        # submission stamp and mix clocks in Completion.wait_s.
        if hasattr(task, "submitted_s") and task.submitted_s is None:
            task.submitted_s = hours_to_s(submit_hour)
        self.executor.submit(task)
        self._pending.append(_Pending(uid, submit_hour, deferred_hours,
                                      getattr(task, "tenant", ""), client))
        jt = self.obs.journeys if self.obs is not None else None
        if jt is not None:
            jt.enqueue((uid,), now)
        if len(self._pending) >= self.max_batch:
            # Flush immediately, even past an already-scheduled window
            # flush — the superseded event then drains whatever is
            # pending (or nothing) and reschedules harmlessly.
            self._schedule_flush(now)
        else:
            self._schedule_flush(now + self.batch_window_hours)

    def _enqueue_batch(self, tasks: List, uids: np.ndarray,
                       times: np.ndarray,
                       client_ids: Optional[np.ndarray]) -> None:
        """Batched :meth:`_enqueue` over one same-kind event run
        (nondecreasing ``times``). Replicates the scalar loop's flush
        pushes exactly (DESIGN.md §11 windowing rule): the run's first
        task would have scheduled the window flush, its last can trigger
        at most one immediate flush — ``pop_run``'s limit guarantees the
        batch never overshoots ``max_batch`` mid-run."""
        hours = times.tolist()
        if hasattr(tasks[0], "submitted_s"):
            for task, h in zip(tasks, hours):
                if task.submitted_s is None:
                    task.submitted_s = hours_to_s(h)
        submit_many = getattr(self.executor, "submit_many", None)
        if submit_many is not None:
            submit_many(tasks)
        else:
            for task in tasks:
                self.executor.submit(task)
        tenants = [getattr(task, "tenant", "") for task in tasks]
        pend0 = len(self._pending)
        self._pending.append_arrays(uids, times, tenants, client_ids)
        jt = self.obs.journeys if self.obs is not None else None
        if jt is not None:
            jt.enqueue(uids, times)
        k = len(tasks)
        # window flush: armed while processing the run's first event
        # (pend0 + 1 < max_batch is guaranteed by pop_run's room limit);
        # intermediate enqueues would arm at later hours — no-ops under
        # the strictly-earlier rule, so only the first is replayed here
        self._schedule_flush(hours[0] + self.batch_window_hours)
        # immediate flush: the run's last event filled the batch
        if pend0 + k >= self.max_batch:
            self._schedule_flush(hours[-1])

    def _schedule_flush(self, at_hour: float) -> None:
        if self._flush_at is None or at_hour < self._flush_at - 1e-12:
            self._flush_at = at_hour
            self.heap.push(at_hour, EventKind.BATCH_READY)

    def _on_arrival(self, now: float) -> None:
        self._uid += 1
        uid = self._uid
        # one factory arity per driver: 3-arg whenever a client pool is
        # attached (open-loop arrivals are the untenanted source)
        task = (self.task_factory(uid, now) if self.clients is None
                else self.task_factory(uid, now, ""))
        jt = self.obs.journeys if self.obs is not None else None
        if jt is not None:
            jt.begin((uid,), now)
        wake = self._plan(task, now)
        if wake > now + 1e-12:
            if jt is not None:
                jt.plan_defer(uid, wake - now)
            self.heap.push(wake, EventKind.DEFER_WAKE,
                           payload=(uid, task, now, wake - now))
        else:
            self._enqueue(uid, task, now, 0.0, now)

    def _on_arrivals_batch(self, times: np.ndarray) -> None:
        """A run of ARRIVAL events with nothing to plan against
        (``_plan`` degenerates to ``now``): build and enqueue the tasks
        in one batch."""
        n = times.size
        uids = np.arange(self._uid + 1, self._uid + n + 1, dtype=np.int64)
        self._uid += n
        factory = self.task_factory
        if self.clients is None:
            tasks = [factory(u, h)
                     for u, h in zip(uids.tolist(), times.tolist())]
        else:
            tasks = [factory(u, h, "")
                     for u, h in zip(uids.tolist(), times.tolist())]
        jt = self.obs.journeys if self.obs is not None else None
        if jt is not None:
            jt.begin(uids, times)
        self._enqueue_batch(tasks, uids, times, None)

    def _on_client_ready(self, client_id: int, now: float,
                         retry: bool = False) -> None:
        """A closed-loop client issues its next request (first try or
        retry). Clients stop issuing new requests at the horizon so the
        event loop drains; in-flight work completes normally. A *retry*
        that lands past the horizon is a request that dies with the sim —
        it counts as abandoned rather than silently vanishing."""
        if now >= self.start_hour + self.horizon_hours:
            if retry:
                self.metrics.count_abandoned(
                    self.clients.tenant_of(client_id))
                self.clients.give_up(client_id)
            return
        self._uid += 1
        uid = self._uid
        tenant = self.clients.on_ready(client_id)
        task = self.task_factory(uid, now, tenant)
        jt = self.obs.journeys if self.obs is not None else None
        if jt is not None:
            jt.begin((uid,), now)
        self._enqueue(uid, task, now, 0.0, now, client=client_id)

    def _on_clients_batch(self, times: np.ndarray, ids: np.ndarray,
                          retry_mask: np.ndarray) -> None:
        """Batched :meth:`_on_client_ready` over a CLIENT_READY/RETRY
        run. ``times`` is nondecreasing, so past-horizon drops are a
        suffix: retries there count as abandoned (same bookkeeping as the
        scalar path), first tries vanish silently."""
        pool = self.clients
        live = int(np.searchsorted(times,
                                   self.start_hour + self.horizon_hours,
                                   side="left"))
        if live < times.size:
            for cid in ids[live:][retry_mask[live:]].tolist():
                self.metrics.count_abandoned(pool.tenant_of(cid))
                pool.give_up(cid)
        if live == 0:
            return
        times, ids = times[:live], ids[:live]
        uids = np.arange(self._uid + 1, self._uid + live + 1,
                         dtype=np.int64)
        self._uid += live
        tcodes = pool.on_ready_batch(ids)
        tnames = pool.tenant_names
        factory = self.task_factory
        tasks = [factory(u, h, tnames[c])
                 for u, h, c in zip(uids.tolist(), times.tolist(),
                                    tcodes.tolist())]
        jt = self.obs.journeys if self.obs is not None else None
        if jt is not None:
            jt.begin(uids, times)
        self._enqueue_batch(tasks, uids, times, ids)

    def _client_verdict(self, client_id: int, verdict: str,
                        at_hour: float, tenant: str) -> None:
        """Translate a pool verdict into the next client event + counters."""
        if verdict == "retry":
            self.metrics.count_retry(tenant)
            self.heap.push(at_hour, EventKind.RETRY, payload=client_id)
        else:
            if verdict == "abandon":
                self.metrics.count_abandoned(tenant)
            self.heap.push(at_hour, EventKind.CLIENT_READY,
                           payload=client_id)

    def _on_tenancy_wake(self, now: float) -> None:
        """A budget-deferred task's next accounting period arrived: pop
        every ripe task off the executor's parking lot and re-enqueue it,
        matching our parked pending entries by the same wake filter in
        park order (both sides are FIFO over identical wake hours)."""
        pop = getattr(self.executor, "pop_ripe", None)
        if pop is None:
            return
        ripe = pop(now)
        if not ripe:
            return
        take, rest = [], []
        for entry in self._parked:
            if entry[0] <= now and len(take) < len(ripe):
                take.append(entry)
            else:
                rest.append(entry)
        self._parked = rest
        # Tasks the ENGINE parked before this driver attached (direct
        # engine.step use, or a reused engine) have no parked record of
        # ours; they precede our own in the lot's FIFO, so the unmatched
        # head is exactly them — adopt each with a fresh uid at the wake.
        jt = self.obs.journeys if self.obs is not None else None
        extra = len(ripe) - len(take)
        adopted: List[int] = []
        for task in ripe[:extra]:
            self._uid += 1
            self.executor.submit(task)
            self._pending.append(_Pending(self._uid, now, 0.0,
                                          getattr(task, "tenant", ""),
                                          None))
            adopted.append(self._uid)
        for task, (wake, parked_at, p) in zip(ripe[extra:], take):
            self.executor.submit(task)
            p.deferred_hours += now - parked_at
            self._pending.append(p)
        if jt is not None:
            if adopted:
                jt.begin(adopted, now)
                jt.enqueue(adopted, now)
            if take:
                woke = [p.uid for _, _, p in take]
                jt.wake(woke, now)
                jt.enqueue(woke, now)
        if len(self._pending) >= self.max_batch:
            self._schedule_flush(now)
        else:
            self._schedule_flush(now + self.batch_window_hours)

    def _monitor(self):
        """The executor's CarbonMonitor: directly on a CarbonEdgeEngine,
        behind the router on a ServingEngine."""
        m = getattr(self.executor, "monitor", None)
        if m is None:
            m = getattr(getattr(self.executor, "router", None),
                        "monitor", None)
        return m

    def _record_batch(self, results: Sequence, exec_hour: float,
                      batch_energy_kwh: Optional[float] = None,
                      outcomes: Optional[Sequence] = None) -> float:
        """Emit TaskRecords for ``results`` against the pending FIFO head;
        returns the hour the executor frees up. ``batch_energy_kwh``
        (the monitor's delta across the step) backfills executors whose
        results carry no per-task energy, apportioned evenly like their
        per-batch carbon.

        ``outcomes`` (an admission-controlled executor's
        ``last_outcomes``, DESIGN.md §7) maps the drained FIFO prefix to
        per-task verdicts: completions are recorded as before, rejections
        are counted (and fed back to the closed-loop client, which
        retries or abandons), deferrals park the pending entry until the
        executor's wake event. ``None`` means every drained task
        completed in order — the pre-tenancy contract.
        """
        if outcomes is None:
            outcomes = [("done", r) for r in results]
        done, free = self._pending.take_list(len(outcomes)), exec_hour
        pool = self.clients
        obs = self.obs
        jt = obs.journeys if obs is not None else None
        roll = obs.rollups if obs is not None else None
        # per-verdict journey/rollup gathers, scattered batched after the
        # loop (the loop itself is the pre-existing scalar record path)
        j_rej: List[tuple] = []              # (uid, tenant)
        j_defer: List[int] = []
        j_retry: List[int] = []
        j_dead: List[tuple] = []             # (uid, tenant)
        j_done: List[tuple] = []             # (uid, finish, node, tenant, sub)
        t = exec_hour
        for p, (kind, val) in zip(done, outcomes):
            if kind == "reject":
                self.metrics.count_rejected(p.tenant)
                if jt is not None:
                    j_rej.append((p.uid, p.tenant))
                if pool is not None and p.client is not None:
                    verdict, at = pool.on_reject(p.client, exec_hour)
                    self._client_verdict(p.client, verdict, at, p.tenant)
                continue
            if kind == "defer" or kind == "retry":
                # a resilience retry parks on the executor exactly like a
                # budget deferral: wake at `val`, resubmit, re-plan
                if jt is not None:
                    (j_defer if kind == "defer" else j_retry).append(p.uid)
                self._parked.append((val, exec_hour, p))
                self.heap.push(val, EventKind.DEFER_WAKE, payload=None)
                continue
            if kind == "dead":
                # dead letter (DESIGN.md §10): the executor consumed the
                # task permanently; a closed-loop client sees a rejection
                self.metrics.count_dead(p.tenant)
                if jt is not None:
                    j_dead.append((p.uid, p.tenant))
                if pool is not None and p.client is not None:
                    verdict, at = pool.on_reject(p.client, exec_hour)
                    self._client_verdict(p.client, verdict, at, p.tenant)
                continue
            res = val
            if hasattr(res, "latency_ms"):        # serial cluster result
                t += ms_to_hours(res.latency_ms)
                finish = t
                free = t
            else:                                 # parallel serving batch
                finish = exec_hour + s_to_hours(getattr(res, "service_s", 0.0))
                free = max(free, finish)
            energy = getattr(res, "energy_kwh", None)
            if energy is None:
                energy = (batch_energy_kwh / len(results)
                          if batch_energy_kwh is not None else 0.0)
            rec = TaskRecord(
                uid=p.uid, submit_hour=p.submit_hour, start_hour=exec_hour,
                finish_hour=finish,
                node=getattr(res, "node", getattr(res, "pod", "")),
                carbon_g=getattr(res, "carbon_g", 0.0),
                energy_kwh=energy,
                deferred_hours=p.deferred_hours, tenant=p.tenant)
            self.metrics.add(rec)
            if jt is not None or roll is not None:
                j_done.append((p.uid, finish, rec.node, p.tenant,
                               p.submit_hour))
            if pool is not None and p.client is not None:
                verdict, at = pool.on_complete(p.client, rec.latency_s,
                                               finish)
                self._client_verdict(p.client, verdict, at, p.tenant)
        if jt is not None:
            if j_rej:
                jt.reject([u for u, _ in j_rej], exec_hour,
                          jt.intern_tenants([tn for _, tn in j_rej]))
            if j_defer:
                jt.park(j_defer, exec_hour, PARK_DEFER)
            if j_retry:
                jt.park(j_retry, exec_hour, PARK_RETRY)
            if j_dead:
                jt.dead([u for u, _ in j_dead], exec_hour,
                        jt.intern_tenants([tn for _, tn in j_dead]))
            if j_done:
                jt.done([e[0] for e in j_done], exec_hour,
                        [e[1] for e in j_done],
                        node_ids=jt.intern_names([e[2] for e in j_done]),
                        tenant_ids=jt.intern_tenants(
                            [e[3] for e in j_done]))
            fo = getattr(self.executor, "last_failover_pos", None)
            if fo:
                jt.failover([done[i].uid for i in fo])
        if roll is not None and j_done:
            base = (self.metrics.slo_latency_s
                    if self.metrics.slo_latency_s is not None
                    else float("inf"))
            fins = np.asarray([e[1] for e in j_done])
            subs = np.asarray([e[4] for e in j_done])
            thr = np.asarray([self.metrics.tenant_slo_s.get(e[3], base)
                              for e in j_done])
            roll.fold_slo(fins, (fins - subs) * 3600.0 > thr)
        return free

    def _record_batch_vec(self, results: Sequence,
                          exec_hour: float) -> float:
        """Columnar :meth:`_record_batch` for the all-completed serial
        case (DESIGN.md §11): gathers the step's per-task arrays (the
        engine's ``last_exec`` snapshot when available — the same floats
        its result objects carry — else one fromiter pass), folds finish
        hours with the scalar loop's exact left-to-right accumulation,
        records one ``add_batch``, and feeds every closed-loop client its
        verdict through one ``on_complete_batch``."""
        n = len(results)
        uids, subs, defs, tenants, clis = self._pending.take_arrays(n)
        metrics = self.metrics
        snap = getattr(self.executor, "last_exec", None)
        if snap is not None and len(snap[2]) == n:
            uniq, inverse, lat_ms, e_kwh, c_g = snap
            node_codes = metrics.intern_array(uniq)[inverse]
        else:
            lat_ms = np.fromiter((r.latency_ms for r in results), float, n)
            e_kwh = np.fromiter((r.energy_kwh for r in results), float, n)
            c_g = np.fromiter((getattr(r, "carbon_g", 0.0)
                               for r in results), float, n)
            node_codes = np.fromiter(
                (metrics.intern(getattr(r, "node", getattr(r, "pod", "")))
                 for r in results), np.int64, n)
        # serial finish hours: exactly the scalar `t += ms_to_hours(lat)`
        # fold (np.add.accumulate is sequential, so bit-identical)
        acc = np.add.accumulate(
            np.concatenate(([exec_hour], lat_ms / 3.6e6)))
        finishes = acc[1:]
        tenant_codes = np.fromiter((metrics.intern(t) for t in tenants),
                                   np.int64, n)
        metrics.add_batch(uids, subs, exec_hour, finishes, node_codes,
                          c_g, e_kwh, defs, tenant_codes)
        obs = self.obs
        jt = obs.journeys if obs is not None else None
        roll = obs.rollups if obs is not None else None
        if jt is not None:
            if snap is not None and len(snap[2]) == n:
                node_ids = jt.intern_names(uniq)[inverse]
            else:
                node_ids = jt.intern_names(
                    [getattr(r, "node", getattr(r, "pod", ""))
                     for r in results])
            jt.done(uids, exec_hour, finishes, node_ids=node_ids,
                    tenant_ids=jt.intern_tenants(tenants))
        if roll is not None:
            thr = metrics.slo_for_codes()
            roll.fold_slo(finishes,
                          (finishes - subs) * 3600.0 > thr[tenant_codes])
        pool = self.clients
        if pool is not None:
            pos = np.flatnonzero(clis >= 0)
            if pos.size:
                ids = clis[pos]
                fin = finishes[pos]
                lat_s = (fin - subs[pos]) * 3600.0
                retry, abandon, next_h = pool.on_complete_batch(
                    ids, lat_s, fin)
                for j in np.flatnonzero(retry).tolist():
                    metrics.count_retry(tenants[pos[j]])
                for j in np.flatnonzero(abandon).tolist():
                    metrics.count_abandoned(tenants[pos[j]])
                kinds = np.where(retry, _RT, _CR)
                self.heap.push_batch(next_h, kinds, ids)
        return float(acc[-1])

    def _on_batch_ready(self, now: float) -> None:
        if self._flush_at is not None and now >= self._flush_at - 1e-12:
            self._flush_at = None           # the armed flush fired (or we
        # popped a same-time superseded one — the armed event then drains
        # nothing and falls through the re-arm below, which is harmless)
        if not self._pending:
            return
        if now < self._busy_until - 1e-12:        # executor still serving
            self._schedule_flush(self._busy_until)
            return
        n = min(len(self._pending), self.max_batch)
        monitor = self._monitor()
        e0 = monitor.total_energy_kwh() if monitor is not None else None
        prof = self.obs.profiler if self.obs is not None else None
        with span(prof, "sim_step"):
            results = self.executor.step(now_hour=now, limit=n)
        e_batch = (monitor.total_energy_kwh() - e0
                   if monitor is not None else None)
        outcomes = getattr(self.executor, "last_outcomes", None)
        with span(prof, "sim_record"):
            if (self._vectorized and outcomes is None and results
                    and hasattr(results[0], "latency_ms")
                    and getattr(results[0], "energy_kwh", None) is not None):
                self._busy_until = self._record_batch_vec(results, now)
            else:
                self._busy_until = self._record_batch(results, now, e_batch,
                                                      outcomes)
        if len(self._pending) >= self.max_batch:
            # saturated: drain back-to-back the moment the executor frees
            # up instead of idling a whole window on a full batch
            self._schedule_flush(max(self._busy_until, now))
        elif self._pending:
            self._schedule_flush(max(self._busy_until,
                                     now + self.batch_window_hours))

    def _on_tick(self, now: float) -> None:
        cluster = getattr(self.executor, "cluster", None)
        provider = getattr(self.executor, "provider", None)
        mean_int = 0.0
        if cluster is not None and provider is not None:
            names = list(cluster.nodes)
            try:
                # fleet-scale: one batched provider read per tick, not N
                # Python calls (DESIGN.md §3.2); the mean stays ndarray math
                arr = np.asarray(intensity_batch(provider, names, now),
                                 dtype=float)
                if arr.size:
                    mean_int = float(arr.sum() / arr.size)
            except KeyError:
                # partial-coverage provider: sample per node, skip holes
                vals = []
                for name in names:
                    try:
                        vals.append(provider.intensity(name, now))
                    except KeyError:
                        pass
                if vals:
                    mean_int = float(sum(vals) / len(vals))
        monitor = self._monitor()
        carbon = monitor.total_carbon_g() if monitor is not None else \
            self.metrics.carbon_g_total()
        self.metrics.add_sample(TimelineSample(
            hour=now, completed=self.metrics.n_records,
            carbon_g_cum=float(carbon), mean_intensity=mean_int))

    # -- main loop -----------------------------------------------------------
    def _dispatch(self, ev, now: float) -> None:
        """Scalar dispatch of one popped event (both queue modes)."""
        if ev.kind is EventKind.ARRIVAL:
            self._on_arrival(now)
        elif (ev.kind is EventKind.CLIENT_READY
              or ev.kind is EventKind.RETRY):
            self._on_client_ready(ev.payload, now,
                                  retry=ev.kind is EventKind.RETRY)
        elif ev.kind is EventKind.DEFER_WAKE:
            if ev.payload is None:            # budget-deferred wake
                self._on_tenancy_wake(now)
            else:                             # forecast-planned wake
                uid, task, submit_hour, deferred = ev.payload
                self._enqueue(uid, task, submit_hour, deferred, now)
        elif ev.kind is EventKind.BATCH_READY:
            self._on_batch_ready(now)
        elif ev.kind is EventKind.INTENSITY_TICK:
            self._on_tick(now)
        elif (ev.kind is EventKind.NODE_DOWN
              or ev.kind is EventKind.NODE_UP
              or ev.kind is EventKind.PROVIDER_OUTAGE):
            self.faults.apply(ev.payload, self.executor)
            roll = self.obs.rollups if self.obs is not None else None
            if roll is not None:
                res = getattr(self.executor, "resilience", None)
                cluster = getattr(self.executor, "cluster", None)
                if res is not None and cluster is not None and cluster.nodes:
                    roll.note_availability(
                        now, res.availability(len(cluster.nodes)))

    def _run_loop_calendar(self, ev_counts: Optional[Dict[str, int]]) -> None:
        """The O(batches) event loop (DESIGN.md §11): a same-kind run of
        CLIENT_READY/RETRY (or plan-free ARRIVAL) events pops as one
        numpy slice, bounded by the windowing rule — up to the batch-size
        room so at most the run's last event triggers an immediate flush,
        and (when no flush is scheduled yet) up to the window the run's
        first event would have opened. Everything else dispatches
        scalar, so fault/defer/tick semantics are untouched."""
        q = self.heap
        clock = self.clock
        pool = self.clients
        arrivals_plain = (self.forecast is None
                          or getattr(self.executor, "cluster", None) is None)
        while True:
            key = q.peek_key()
            if key is None:
                break
            t0k, code = key
            batchable = ((code == _CR or code == _RT)
                         if pool is not None else False)
            if not batchable and code == _AR and arrivals_plain:
                batchable = True
            room = self.max_batch - len(self._pending)
            if room <= 1:
                # saturated: a one-element array run costs more than the
                # scalar path, which processes the same single event with
                # identical semantics (no RNG is drawn before the flush)
                batchable = False
            if batchable:
                limit = room
                # an already-armed flush is a physical BATCH_READY event
                # in the queue, so the same-kind run stops at it for
                # free; the cap covers the one flush the run's FIRST
                # enqueue may arm (strictly-earlier rule) that the queue
                # cannot know about yet
                max_t = t0k + self.batch_window_hours
                codes = (_CR, _RT) if code != _AR else (_AR,)
                times, payloads, kinds = q.pop_run(codes, limit, max_t)
                clock.advance_run(times)
                self.events_processed += times.size
                if ev_counts is not None:
                    nr = int(np.count_nonzero(kinds == _RT))
                    nc = times.size - nr
                    name = ("ARRIVAL" if code == _AR
                            else EventKind.CLIENT_READY.name)
                    if nc:
                        ev_counts[name] = ev_counts.get(name, 0) + nc
                    if nr:
                        ev_counts["RETRY"] = ev_counts.get("RETRY", 0) + nr
                if code == _AR:
                    self._on_arrivals_batch(times)
                else:
                    self._on_clients_batch(times, payloads, kinds == _RT)
            else:
                ev = q.pop()
                now = clock.advance_to(ev.time_hours)
                self.events_processed += 1
                if ev_counts is not None:
                    k = ev.kind.name
                    ev_counts[k] = ev_counts.get(k, 0) + 1
                self._dispatch(ev, now)

    def run(self) -> MetricsCollector:
        if self.faults is not None:
            # pushed before arrivals so a fault and an arrival at the same
            # instant resolve fault-first (heap ties break by push order)
            for f in self.faults.schedule:
                self.heap.push(float(f.hour), f.event_kind, payload=f)
        if self.arrivals is not None:
            ts = self.arrivals.times(self.start_hour, self.horizon_hours)
            if self._vectorized:
                self.heap.push_batch(np.asarray(ts, dtype=float),
                                     EventKind.ARRIVAL)
            else:
                for t in ts:
                    self.heap.push(float(t), EventKind.ARRIVAL)
        if self.clients is not None:
            if self._vectorized:
                ats, cids = self.clients.initial_events_arrays(
                    self.start_hour)
                self.heap.push_batch(ats, EventKind.CLIENT_READY, cids)
            else:
                for at, cid in self.clients.initial_events(self.start_hour):
                    self.heap.push(at, EventKind.CLIENT_READY, payload=cid)
            # advertise per-tenant SLO classes to the metrics layer
            for pop in self.clients.populations:
                if pop.slo_latency_s != float("inf"):
                    self.metrics.tenant_slo_s[pop.tenant] = pop.slo_latency_s
        # the executor's tenant registry (if any) supplies spec-level SLO
        # classes: latency targets (client populations take precedence)
        # and miss tolerances
        reg = getattr(getattr(self.executor, "policy", None),
                      "registry", None)
        if reg is not None and hasattr(reg, "miss_tolerance"):
            for name, i in reg.index.items():
                if reg.slo_latency_s[i] != float("inf"):
                    self.metrics.tenant_slo_s.setdefault(
                        name, float(reg.slo_latency_s[i]))
                if reg.miss_tolerance[i] > 0:
                    self.metrics.tenant_miss_tolerance[name] = float(
                        reg.miss_tolerance[i])
        if self.tick_hours > 0:
            n_ticks = int(self.horizon_hours / self.tick_hours)
            for k in range(1, n_ticks + 1):
                self.heap.push(self.start_hour + k * self.tick_hours,
                               EventKind.INTENSITY_TICK)
        # Per-EventKind counters (obs metrics only): a plain dict on the
        # loop, folded into one `sim_events_total` family after the drain
        # so the hot loop never touches the registry.
        ev_counts: Optional[Dict[str, int]] = (
            {} if self.obs is not None and self.obs.metrics is not None
            else None)
        if self._vectorized:
            self._run_loop_calendar(ev_counts)
        else:
            while self.heap:
                ev = self.heap.pop()
                now = self.clock.advance_to(ev.time_hours)
                self.events_processed += 1
                if ev_counts is not None:
                    k = ev.kind.name
                    ev_counts[k] = ev_counts.get(k, 0) + 1
                self._dispatch(ev, now)
        assert not self._pending, "event loop ended with tasks still queued"
        if ev_counts is not None:
            fam = self.obs.metrics.counter(
                "sim_events_total", "Events processed by the sim loop",
                labels=("kind",))
            for k in sorted(ev_counts):
                fam.inc(ev_counts[k], (k,))
            self.metrics.export_obs(self.obs.metrics)
        # Alert evaluation (DESIGN.md §12): one vectorized pass over the
        # run's complete rollup windows. With no rules configured, default
        # fleet rules plus the tenant policy's per-tenant carbon-pace
        # rules (when the executor carries one) are installed first.
        obs = self.obs
        if (obs is not None and obs.alerts is not None
                and obs.rollups is not None):
            alerts = obs.alerts
            if not alerts.rules:
                from repro.obs.alerts import default_rules
                rules = default_rules()
                mk = getattr(getattr(self.executor, "policy", None),
                             "alert_rules", None)
                if mk is not None:
                    rules += mk(obs.rollups.window_hours)
                alerts.add_rules(rules)
            alerts.evaluate(obs.rollups)
            if obs.metrics is not None:
                alerts.export(obs.metrics)
        return self.metrics
