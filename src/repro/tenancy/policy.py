"""Budget-aware per-tenant scheduling as a policy wrapper (DESIGN.md §7).

:class:`TenantPolicy` composes over any
:class:`~repro.core.api.SchedulingPolicy` and a
:class:`~repro.tenancy.spec.TenantRegistry`. It replaces the deprecated
``BudgetedRouter``'s weight-swapping with three explicit, batched phases
the engine drives per step:

1. :meth:`plan` — **admission**: for each drained task, decide
   admit / defer / reject from the tenant's *current-period* budget, and
   compute the tenant's **effective mode** (performance → balanced →
   green) from budget pressure. All of it is column math over the
   registry's vectorized tenant state: O(B) numpy plus O(distinct
   tenants) Python per step, never O(B) Python.
2. :meth:`select_admitted` — **placement**: admitted tasks are grouped by
   effective mode (≤ 3 groups) and each group goes through the wrapped
   policy's batched ``select_batch`` with that mode's weights. A tenant
   whose mode-chosen placements would overrun its remaining budget has
   its *whole group this step* re-placed on the greenest feasible node
   (the reservation admission made), so actual spend can never exceed the
   allowance — the per-request special case of this rule is exactly the
   old BudgetedRouter's greenest-pod fallback.
3. :meth:`charge` — **billing**: executed carbon is folded into the
   registry per distinct tenant in task order
   (:func:`~repro.core.energy.ledger_add`), bit-identical to a scalar
   ``spent += c`` loop — the same contract as the batched cluster/monitor
   ledgers (DESIGN.md §6).

Admission semantics (per tenant, per step): tasks are considered in batch
order; each is admitted while the cumulative expected carbon of the
tenant's admitted prefix — expected = the task's energy on the
minimum-intensity ("greenest") feasible node — still fits the remaining
allowance. Expected carbon is cumulative and non-negative, so denial is
always a suffix of the tenant's slice of the batch. A denied task is
DEFERred to the tenant's next period start when the spec allows it, the
period is finite and a fresh period's allowance could cover the task;
otherwise it is REJECTed. Tasks that are feasible nowhere are admitted
with zero expected carbon — resource infeasibility is the selection
layer's verdict, not admission's.

``TenantPolicy`` also satisfies the plain ``SchedulingPolicy`` protocol:
``select``/``select_batch`` apply mode escalation (no admission, no
charging), so it can drop into any engine or router as a scoring policy.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import numpy as np

from repro.core.energy import carbon_g
from repro.core.providers import intensity_interval_batch
from repro.core.scheduler import MODES, Task, Weights, node_feasible
from repro.tenancy.spec import (ESCALATION_BOUNDS, MODE_ORDER, TenantRegistry,
                                TenantSpec)

# Admission actions (AdmissionPlan.actions values).
ADMIT, DEFER, REJECT = 0, 1, 2


@dataclass
class AdmissionPlan:
    """Struct-of-arrays admission decisions for one drained batch.

    ``modes`` indexes :data:`~repro.tenancy.spec.MODE_ORDER`; -1 means
    "engine default weights" (untagged / unregistered tenant).
    ``wake_hour`` is only meaningful where ``actions == DEFER``.
    ``expected_g`` is the greenest-feasible expected carbon admission
    reserved (0 for untagged or nowhere-feasible tasks).
    """

    actions: np.ndarray        # (B,) int8
    modes: np.ndarray          # (B,) int8
    tenant_idx: np.ndarray     # (B,) int64, -1 = untagged
    expected_g: np.ndarray     # (B,) float
    greenest: np.ndarray       # (B,) int64 node index, -1 = none feasible
    wake_hour: np.ndarray      # (B,) float
    node_names: List[str]      # node order `greenest` indexes
    intensities: np.ndarray    # (N,) grid signal admission read
    energy_kwh: np.ndarray     # (B, 1) or (B, N) per-task energy model
    pue: float

    @property
    def all_admitted(self) -> bool:
        return bool((self.actions == ADMIT).all())

    def admitted_index(self) -> np.ndarray:
        return np.nonzero(self.actions == ADMIT)[0]


def cluster_energy_model(cluster, tasks: Sequence[Task],
                         names: Sequence[str]) -> np.ndarray:
    """Default expected-energy model: the execution cost model itself
    (``EdgeCluster.latency_energy`` — full host power over the measured
    distributed latency), so admission reservations equal the carbon the
    engine will actually bill. Node-independent: returns (B, 1)."""
    base = np.array([t.base_latency_ms for t in tasks], dtype=float)
    fn = getattr(cluster, "latency_energy", None)
    if fn is None:
        # duck-typed cluster without the execution cost model: admission
        # cannot price work, so everything is affordable (expected 0)
        return np.zeros((len(tasks), 1))
    _, e_kwh = fn(base, distributed=True)
    return np.asarray(e_kwh, dtype=float)[:, None]


class TenantPolicy:
    """Composable multi-tenant admission + escalation wrapper around any
    scheduling policy (see module docstring for the three-phase engine
    protocol; :class:`~repro.core.api.CarbonEdgeEngine` detects the
    ``plan``/``charge`` hooks and drives them automatically).

    ``energy_model(cluster, tasks, node_names) -> (B, 1) | (B, N) kWh``
    prices a task's execution for admission; the default is the cluster's
    own execution cost model. ``pue`` defaults to the cluster's.
    """

    name = "tenant"

    def __init__(self, inner=None, registry: Optional[TenantRegistry] = None,
                 *, energy_model: Optional[Callable] = None,
                 escalation_bounds: Sequence[float] = ESCALATION_BOUNDS,
                 defer_risk_coverage: Optional[float] = None):
        if inner is None:
            from repro.core.policy import VectorizedPolicy
            inner = VectorizedPolicy()
        self.inner = inner
        self.registry = registry if registry is not None else TenantRegistry()
        self.energy_model = energy_model or cluster_energy_model
        self._bounds = np.asarray(escalation_bounds, dtype=float)
        # Risk-bounded deferral (DESIGN.md §8): when set, a budget DEFER
        # must also be defensible against the provider's conformal
        # intensity interval at this coverage level, else it downgrades
        # to REJECT. None (default) keeps the point-forecast behaviour.
        if defer_risk_coverage is not None and not 0.0 < defer_risk_coverage < 1.0:
            raise ValueError("defer_risk_coverage must be in (0, 1) or None")
        self.defer_risk_coverage = defer_risk_coverage
        # Observability (DESIGN.md §9): full-length per-task score capture
        # assembled from the wrapped policy's per-mode sub-batches; the
        # capture/profiler switches themselves forward to `inner`.
        self.last_scores = None

    def register(self, spec: TenantSpec) -> TenantSpec:
        return self.registry.register(spec)

    def alert_rules(self, window_hours: float):
        """Per-tenant carbon-pace alert rules for the obs alerting engine
        (DESIGN.md §12): a tenant burning faster than
        ``allowance_g * window_hours / period_hours`` per rollup window is
        on pace to exhaust its budget before the period rolls. Tenants
        with infinite allowances or everlasting periods get no rule.
        Deterministically ordered by tenant name."""
        from repro.obs.alerts import AlertRule
        reg = self.registry
        rules = []
        for name in sorted(reg.index):
            i = reg.index[name]
            allow = float(reg.allowance_g[i])
            period = float(reg.period_hours[i])
            if not (np.isfinite(allow) and np.isfinite(period)):
                continue
            rules.append(AlertRule(
                name=f"carbon_pace[{name}]", kind="carbon_pace",
                threshold=allow * float(window_hours) / period,
                tenant=name))
        return rules

    # -- observability passthrough (DESIGN.md §9) --------------------------
    @property
    def capture_scores(self) -> bool:
        return bool(getattr(self.inner, "capture_scores", False))

    @capture_scores.setter
    def capture_scores(self, value: bool) -> None:
        if hasattr(self.inner, "capture_scores"):
            self.inner.capture_scores = bool(value)

    @property
    def profiler(self):
        return getattr(self.inner, "profiler", None)

    @profiler.setter
    def profiler(self, value) -> None:
        if hasattr(self.inner, "profiler"):
            self.inner.profiler = value

    # -- shared helpers ----------------------------------------------------
    def _latency_threshold(self) -> float:
        # admission must probe feasibility with the same filter the
        # wrapped policy selects with, or it would reserve on nodes the
        # selection layer will never use
        return getattr(self.inner, "latency_threshold_ms", 5000.0)

    def _feasibility(self, cluster, tasks: Sequence[Task], provider,
                     now_hour: float):
        """Greenest feasible node per task: returns ``(greenest_idx (B,),
        names, intensities (N,))`` with -1 where no node is feasible.
        Dedups (cpu, mem) resource profiles so the (U, N) mask — not a
        (B, N) one — is the only per-node work."""
        fc = getattr(cluster, "feature_cache", None)
        cache = fc() if callable(fc) else None
        keys = [(t.cpu, t.mem_mb) for t in tasks]
        uniq: dict = {}
        for k in keys:
            if k not in uniq:
                uniq[k] = len(uniq)
        prof = np.array([uniq[k] for k in keys], dtype=np.int64)
        cpu_u = np.array([k[0] for k in uniq], dtype=float)
        mem_u = np.array([k[1] for k in uniq], dtype=float)
        if cache is not None:
            names = cache.names
            feas = cache.feasible(cpu_u, mem_u, self._latency_threshold())
            ints = np.asarray(cache.intensities(provider, now_hour,
                                                need=feas.any(axis=0)),
                              dtype=float)
        else:
            # duck-typed cluster: scalar fallback (small fleets only)
            names = list(cluster.nodes)
            thresh = self._latency_threshold()
            feas = np.zeros((len(uniq), len(names)), dtype=bool)
            ints = np.zeros(len(names))
            probes = [Task(cpu=c, mem_mb=m) for c, m in uniq]
            for j, n in enumerate(names):
                st = cluster.nodes[n]
                col = np.array([st.avg_time_ms <= thresh
                                and node_feasible(st, p) for p in probes])
                feas[:, j] = col
                if col.any():
                    ints[j] = (provider.intensity(n, now_hour)
                               if provider is not None
                               else st.spec.carbon_intensity)
        masked = np.where(feas, ints[None, :], np.inf)
        g_u = np.where(feas.any(axis=1), np.argmin(masked, axis=1), -1)
        return g_u[prof], names, ints

    def _modes_from_util(self, util: np.ndarray,
                         tid: np.ndarray) -> np.ndarray:
        """Escalation stage from utilisation, floored at each tenant's
        preferred mode — vectorized ``BudgetedRouter._mode_for``."""
        stage = np.searchsorted(self._bounds, util, side="right")
        floor = self.registry.mode_floor[tid]
        return np.minimum(np.maximum(stage, floor),
                          len(MODE_ORDER) - 1).astype(np.int8)

    def effective_modes(self) -> dict:
        """Current effective mode per tenant from current-period
        utilisation — a side-effect-free observability read (the per-task
        modes :meth:`plan` assigns additionally account for the batch's
        own cumulative reservations)."""
        reg = self.registry
        tid = np.arange(reg.n, dtype=np.int64)
        modes = self._modes_from_util(reg.utilisation(), tid)
        return {name: MODE_ORDER[modes[i]]
                for name, i in reg.index.items()}

    # -- phase 1: admission ------------------------------------------------
    def plan(self, cluster, tasks: Sequence[Task], provider=None,
             now_hour: float = 0.0) -> AdmissionPlan:
        """Batched admit/defer/reject + effective-mode decisions for one
        drained batch (see module docstring for the semantics)."""
        reg = self.registry
        reg.roll(now_hour)
        B = len(tasks)
        tid = reg.ids(tasks)
        actions = np.zeros(B, dtype=np.int8)
        modes = np.full(B, -1, dtype=np.int8)
        expected = np.zeros(B)
        wake = np.full(B, np.inf)
        pue = float(getattr(cluster, "pue", 1.0))
        reg_pos = np.nonzero(tid >= 0)[0]
        if not reg_pos.size:
            # nothing to price: every task is untagged/unknown, so skip
            # the feasibility masks, provider reads and energy model
            return AdmissionPlan(actions, modes, tid, expected,
                                 np.full(B, -1, dtype=np.int64), wake,
                                 [], np.zeros(0), np.zeros((B, 1)), pue)
        greenest, names, ints = self._feasibility(cluster, tasks, provider,
                                                  now_hour)
        e_kwh = np.asarray(self.energy_model(cluster, tasks, names),
                           dtype=float)
        # expected carbon at the greenest feasible node (the admission
        # reservation); nowhere-feasible tasks price at 0 — selection,
        # not admission, is what fails them
        g = greenest[reg_pos]
        feas = g >= 0
        e_at_g = (e_kwh[reg_pos, 0] if e_kwh.shape[1] == 1
                  else e_kwh[reg_pos, np.maximum(g, 0)])
        exp = np.where(feas,
                       carbon_g(e_at_g, ints[np.maximum(g, 0)], pue), 0.0)
        expected[reg_pos] = exp
        # per-tenant segmented cumulative reservation, in batch order
        t = tid[reg_pos]
        order = np.argsort(t, kind="stable")
        ts, es = t[order], exp[order]
        cs = np.cumsum(es)
        new_seg = np.r_[True, ts[1:] != ts[:-1]]
        starts = np.nonzero(new_seg)[0]
        seg_id = np.cumsum(new_seg) - 1
        base = np.where(starts > 0, cs[np.maximum(starts - 1, 0)], 0.0)
        cum_incl = cs - base[seg_id]
        cum_excl = cum_incl - es
        allow = reg.allowance_g[ts]
        spent = reg.spent_g[ts]
        remaining = np.maximum(allow - spent, 0.0)
        util = np.ones(ts.size)
        np.divide(spent + cum_excl, allow, out=util, where=allow > 0)
        mode_s = self._modes_from_util(util, ts)
        ok = cum_incl <= remaining
        # a denied task defers only when fresh budget could ever cover
        # it; otherwise deferral is a busy-loop and we reject outright
        can_defer = (reg.defer_ok[ts] & np.isfinite(reg.period_hours[ts])
                     & (es <= allow))
        act_s = np.where(ok, ADMIT, np.where(can_defer, DEFER, REJECT))
        wake_s = np.where(act_s == DEFER,
                          reg.next_period_start()[ts], np.inf)
        if self.defer_risk_coverage is not None and provider is not None:
            act_s = self._risk_defer_gate(provider, names, act_s, wake_s,
                                          g[order], now_hour)
            wake_s[act_s != DEFER] = np.inf
        pos = reg_pos[order]
        actions[pos] = act_s
        modes[pos] = mode_s
        wake[pos] = wake_s
        np.add.at(reg.admitted, ts[act_s == ADMIT], 1)
        np.add.at(reg.deferred, ts[act_s == DEFER], 1)
        np.add.at(reg.rejected, ts[act_s == REJECT], 1)
        return AdmissionPlan(actions, modes, tid, expected, greenest, wake,
                             list(names), ints, e_kwh, pue)

    def _risk_defer_gate(self, provider, names, act: np.ndarray,
                         wake: np.ndarray, gidx: np.ndarray,
                         now_hour: float) -> np.ndarray:
        """Risk-bounded deferral (DESIGN.md §8): a budget DEFER survives
        only while the conformal intensity interval at its wake hour could
        still be at least as good as executing now on the task's greenest
        feasible node — ``lo_wake <= hi_now``. When even the optimistic
        wake-hour bound certainly loses (``lo_wake > hi_now``), deferral
        burns the client's time for provably worse carbon, so the task is
        REJECTed outright instead. Zero-width (measured/static) intervals
        keep every DEFER — the gate only bites when a calibrated forecast
        is confidently pessimistic about the wake window. One batched
        interval read per distinct wake hour; nowhere-feasible tasks
        (``gidx < 0``) are admission-priced at zero and pass through."""
        d = np.nonzero((act == DEFER) & (gidx >= 0) & np.isfinite(wake))[0]
        if not d.size:
            return act
        cov = self.defer_risk_coverage
        _, hi_now = intensity_interval_batch(provider, names, now_hour,
                                             coverage=cov)
        hi_now = np.asarray(hi_now, dtype=float)
        for h in np.unique(wake[d]):
            sel = d[wake[d] == h]
            lo_w, _ = intensity_interval_batch(provider, names, float(h),
                                               coverage=cov)
            lo_w = np.asarray(lo_w, dtype=float)
            gs = gidx[sel]
            act[sel[lo_w[gs] > hi_now[gs]]] = REJECT
        return act

    # -- phase 2: placement ------------------------------------------------
    def select_admitted(self, cluster, tasks: Sequence[Task],
                        plan: AdmissionPlan, weights: Weights, provider=None,
                        now_hour: float = 0.0) -> List[Optional[str]]:
        """Place the plan's admitted tasks: one wrapped ``select_batch``
        per distinct effective mode, then the budget fallback — a tenant
        whose mode-chosen placements would overrun its remaining
        allowance is re-placed wholesale on its greenest feasible nodes
        (the reservation admission checked). Returns a full-length choice
        list with ``None`` at non-admitted positions."""
        out: List[Optional[str]] = [None] * len(tasks)
        aidx = plan.admitted_index()
        if not aidx.size:
            return out
        self._select_by_modes(cluster, tasks, aidx, plan.modes[aidx],
                              weights, provider, now_hour, out)
        self._budget_fallback(plan, out, aidx)
        return out

    def _select_by_modes(self, cluster, tasks: Sequence[Task],
                         positions: np.ndarray, modes: np.ndarray,
                         weights: Weights, provider, now_hour: float,
                         out: List[Optional[str]]) -> None:
        """Scatter mode-grouped placements into ``out``: one wrapped
        ``select_batch`` per distinct effective mode (-1 = the caller's
        default weights)."""
        agg = None
        if self.capture_scores:
            B = len(tasks)
            agg = {"score": np.full(B, np.nan),
                   "runner_up": np.full(B, np.nan)}
        for m in np.unique(modes):
            sel = positions[modes == m]
            w = weights if m < 0 else MODES[MODE_ORDER[m]]
            sub = self.inner.select_batch(cluster, [tasks[i] for i in sel],
                                          w, provider=provider,
                                          now_hour=now_hour)
            for i, ch in zip(sel, sub):
                out[i] = ch
            if agg is not None:
                # scatter the sub-batch's capture into full-length columns
                # (NB: a later budget fallback may move a task off its
                # mode-chosen node; the captured score stays the mode
                # selection's — DESIGN.md §9)
                ls = getattr(self.inner, "last_scores", None)
                if ls is not None and len(ls.get("score", ())) == len(sel):
                    agg["score"][sel] = ls["score"]
                    if ls.get("runner_up") is not None:
                        agg["runner_up"][sel] = ls["runner_up"]
                    cut = ls.get("cut")
                    if cut is not None:
                        agg.setdefault(
                            "cut", np.full(len(tasks), -1,
                                           dtype=np.int32))[sel] = cut
        if agg is not None:
            self.last_scores = agg

    def _budget_fallback(self, plan: AdmissionPlan,
                         out: List[Optional[str]], aidx: np.ndarray) -> None:
        """Clamp spend to the admission reservation: if the sum of a
        tenant's *chosen-node* expected carbon this step exceeds its
        remaining allowance, every admitted task of that tenant moves to
        its greenest feasible node — whose cumulative cost admission
        already verified fits. The single-request case degenerates to the
        deprecated BudgetedRouter's greenest-pod fallback."""
        reg = self.registry
        tid = plan.tenant_idx[aidx]
        capped = tid >= 0
        if capped.any():
            capped &= np.isfinite(reg.allowance_g[np.maximum(tid, 0)])
        if not capped.any():
            return                      # unlimited tenants: nothing to clamp
        cpos = aidx[capped]
        placed = np.array([out[i] is not None for i in cpos])
        if not placed.any():
            return
        cpos = cpos[placed]
        nidx = {n: j for j, n in enumerate(plan.node_names)}
        chosen = np.array([nidx[out[i]] for i in cpos], dtype=np.int64)
        e = (plan.energy_kwh[cpos, 0] if plan.energy_kwh.shape[1] == 1
             else plan.energy_kwh[cpos, chosen])
        cost = carbon_g(e, plan.intensities[chosen], plan.pue)
        t = plan.tenant_idx[cpos]
        remaining = np.maximum(reg.allowance_g - reg.spent_g, 0.0)
        totals = np.zeros(reg.n)
        np.add.at(totals, t, cost)
        over = totals[t] > remaining[t]
        for i, g in zip(cpos[over], plan.greenest[cpos[over]]):
            if g >= 0:
                out[i] = plan.node_names[g]

    # -- phase 3: billing --------------------------------------------------
    def charge(self, tenant_idx: np.ndarray, carbon: np.ndarray,
               now_hour: float = 0.0) -> None:
        """Fold executed carbon into the registry (see module docstring).
        Safe to call with the executed *prefix* after a mid-batch
        failure — the engine does exactly that."""
        self.registry.roll(now_hour)
        self.registry.charge(tenant_idx, carbon)

    # -- SchedulingPolicy protocol (escalation only, no admission) ---------
    def select_batch(self, cluster, tasks: Sequence[Task], weights: Weights,
                     provider=None, now_hour: float = 0.0
                     ) -> List[Optional[str]]:
        """Mode-escalated placement without admission control or charging
        (protocol use — a router or engine that doesn't speak the
        plan/charge protocol still gets budget-pressure escalation)."""
        reg = self.registry
        reg.roll(now_hour)
        B = len(tasks)
        tid = reg.ids(tasks)
        modes = np.full(B, -1, dtype=np.int8)
        pos = np.nonzero(tid >= 0)[0]
        if pos.size:
            util = reg.utilisation()[tid[pos]]
            modes[pos] = self._modes_from_util(util, tid[pos])
        out: List[Optional[str]] = [None] * B
        self._select_by_modes(cluster, tasks, np.arange(B), modes, weights,
                              provider, now_hour, out)
        return out

    def select(self, cluster, task, weights, provider=None,
               now_hour: float = 0.0) -> Optional[str]:
        return self.select_batch(cluster, [task], weights, provider=provider,
                                 now_hour=now_hour)[0]
