"""Journeys, rollups, alerts (repro.obs v2, DESIGN.md §12).

The load-bearing contracts: every consumed request lands in exactly one
terminal state with its phase durations summing to end-to-end latency
(the chaos journey-identity test), rollup folds are bit-identical to
their scalar counterparts with memory O(windows), the alert event stream
is a deterministic transition log, and the PR 7 zero-overhead contract
extends to the three new pillars (each is ``None`` when off).
"""
import numpy as np
import pytest

from repro.obs import (AlertEngine, AlertRule, JourneyTrace,
                       MetricsRegistry, Observability, RollupStore,
                       StepProfiler, default_rules)
from repro.obs.journey import J_DONE, PARK_DEFER, PARK_RETRY
from repro.obs.rollup import VERDICT_COLS, _seq_sum


# ---------------------------------------------------------------------------
# JourneyTrace
# ---------------------------------------------------------------------------


def test_journey_phase_accounting_simple_done():
    jt = JourneyTrace()
    jt.begin([1, 2], 0.0)
    jt.enqueue([1, 2], 0.0)
    jt.done([1, 2], 0.5, [0.6, 0.7],
            node_ids=jt.intern_names(["a", "b"]),
            tenant_ids=jt.intern_tenants(["gold", ""]))
    j = jt.journey(1)
    assert j["state"] == "done"
    assert j["queue_wait_h"] == pytest.approx(0.5)
    assert j["service_h"] == pytest.approx(0.1)
    assert j["e2e_h"] == pytest.approx(0.6)
    assert j["node"] == "a" and j["tenant"] == "gold"
    assert jt.journey(2)["tenant"] is None       # "" stays untenanted
    cp = jt.critical_path()
    assert cp["journeys"] == 2
    assert cp["identity_max_abs_err_h"] < 1e-12
    assert cp["service_share"] + cp["queue_wait_share"] == pytest.approx(1.0)


def test_journey_park_wake_folds_by_kind():
    jt = JourneyTrace()
    jt.begin([1, 2], 0.0)
    jt.enqueue([1, 2], 0.0)
    jt.park([1], 0.1, PARK_DEFER)
    jt.park([2], 0.1, PARK_RETRY)
    jt.wake([1, 2], 0.3)
    jt.enqueue([1, 2], 0.3)
    jt.done([1, 2], 0.4, [0.5, 0.5])
    for uid, field in ((1, "budget_defer_h"), (2, "retry_backoff_h")):
        j = jt.journey(uid)
        assert j[field] == pytest.approx(0.2)
        # 0->0.1 waiting, then 0.3->0.4 after the wake re-enqueue
        assert j["queue_wait_h"] == pytest.approx(0.2)
        assert j["drains"] == 2
    assert jt.journey(1)["defers"] == 1 and jt.journey(1)["retries"] == 0
    assert jt.journey(2)["retries"] == 1 and jt.journey(2)["defers"] == 0
    assert jt.critical_path()["identity_max_abs_err_h"] < 1e-12


def test_journey_plan_defer_counts_toward_identity():
    jt = JourneyTrace()
    jt.begin([1], 0.0)
    jt.plan_defer(1, 2.0)                 # forecast parked it two hours
    jt.enqueue([1], 2.0)
    jt.done([1], 2.5, [2.75])
    j = jt.journey(1)
    assert j["plan_defer_h"] == pytest.approx(2.0)
    assert j["e2e_h"] == pytest.approx(2.75)
    assert jt.critical_path()["identity_max_abs_err_h"] < 1e-12


def test_journey_terminal_states_and_growth():
    jt = JourneyTrace(capacity=2)
    uids = np.arange(1, 40)
    jt.begin(uids, 0.0)
    jt.enqueue(uids, 0.0)
    jt.reject(uids[:10], 0.1, jt.intern_tenants(["t"] * 10))
    jt.dead(uids[10:20], 0.2)
    jt.done(uids[20:], 0.3, np.full(19, 0.4))
    sc = jt.state_counts()
    assert sc == {"open": 0, "reject": 10, "dead": 10, "done": 19}
    assert jt.max_uid == 39 and jt.capacity >= 40
    assert jt.journey(5)["state"] == "reject"
    assert jt.journey(15)["finish_hour"] == pytest.approx(0.2)
    # uid 0 is never assigned; out-of-range uids resolve to None
    assert jt.journey(0) is None and jt.journey(999) is None
    assert jt.explain_journey(999) is None


def test_journey_explain_renders_causal_path():
    jt = JourneyTrace()
    jt.begin([1], 0.0)
    jt.enqueue([1], 0.0)
    jt.park([1], 0.1, PARK_RETRY)
    jt.wake([1], 0.2)
    jt.enqueue([1], 0.2)
    jt.failover([1])
    jt.done([1], 0.25, [0.3], node_ids=jt.intern_names(["edge-3"]))
    text = jt.explain_journey(1)
    assert "retried 1x" in text and "failed over 1x" in text
    assert "'edge-3'" in text and "e2e" in text


def test_journey_to_text_deterministic_and_newline_terminated():
    def build():
        jt = JourneyTrace()
        jt.begin([1, 2, 3], [0.0, 0.1, 0.2])
        jt.enqueue([1, 2, 3], [0.0, 0.1, 0.2])
        jt.reject([2], 0.3)
        jt.done([1, 3], 0.4, [0.5, 0.6])
        return jt.to_text()

    a, b = build(), build()
    assert a == b and a.endswith("\n") and len(a.splitlines()) == 3
    assert JourneyTrace().to_text() == ""


def test_journey_intern_tenants_maps_empty_to_minus_one():
    jt = JourneyTrace()
    ids = jt.intern_tenants(["gold", "", "batch", "gold"])
    assert ids[1] == -1
    assert ids[0] == ids[3] != ids[2]
    # new names intern in sorted batch order (np.unique) — the property
    # that keeps intern ids identical across scalar/vec record paths
    assert jt.names("tenant") == ["batch", "gold"]


# ---------------------------------------------------------------------------
# RollupStore
# ---------------------------------------------------------------------------


def test_rollup_fold_exec_bit_identical_to_scalar_loop():
    rng = np.random.default_rng(3)
    carbon = rng.uniform(0.0, 2.0, 257)
    energy = rng.uniform(0.0, 1e-3, 257)
    roll = RollupStore(window_hours=0.5)
    roll.fold_exec(0.7, carbon, energy)
    acc_c = 0.0
    for x in carbon:
        acc_c += float(x)
    assert roll.carbon_g[1] == acc_c              # bit-identical, not approx
    assert roll.tasks[1] == 257 and roll.tasks[0] == 0
    assert _seq_sum(energy) == roll.energy_kwh[1]


def test_rollup_slo_scatter_by_finish_window():
    roll = RollupStore(window_hours=1.0)
    roll.fold_slo([0.5, 1.5, 1.6, 3.2], [True, True, True, False])
    assert roll.slo_miss[:4].tolist() == [1, 2, 0, 0]
    # zero-miss folds still grow the window span (coverage, not events)
    assert roll.n_windows == 4


def test_rollup_availability_forward_fill():
    roll = RollupStore(window_hours=1.0)
    roll.note_availability(1.5, 0.5)
    roll.note_availability(1.9, 0.25)              # same window: min wins
    roll.fold_slo([4.5], [False])                  # stretch to window 4
    assert roll.availability().tolist() == [1.0, 0.25, 0.25, 0.25, 0.25]


def test_rollup_tenant_spend_scatter_accumulates_duplicates():
    roll = RollupStore(window_hours=1.0)
    rows = roll.intern_tenants(["a", "b"])
    roll.fold_tenant_spend(0.5, np.asarray([rows[0], rows[1], rows[0]]),
                           [1.0, 2.0, 3.0])
    assert roll.tenant_spend[rows[0], 0] == pytest.approx(4.0)
    assert roll.tenant_spend[rows[1], 0] == pytest.approx(2.0)
    assert roll.tenant_names() == ["a", "b"]


def test_rollup_export_trims_and_labels_verdicts():
    roll = RollupStore(window_hours=0.25)
    roll.fold_exec(0.1, [1.0], [1e-4])
    roll.fold_verdicts(0.1, (1, 2, 0, 3, 0))
    out = roll.export()
    assert out["n_windows"] == 1
    assert len(out["tasks"]) == 1 and out["tasks"] == [1]
    assert out["verdict_reject"] == [2] and out["verdict_dead"] == [3]
    assert "tenant_spend_g" not in out            # no tenants interned
    assert set(VERDICT_COLS) == {
        k[len("verdict_"):] for k in out if k.startswith("verdict_")}


def test_rollup_memory_is_o_windows_not_o_tasks():
    roll = RollupStore(window_hours=1.0)
    before = None
    for k in range(200):                   # 2*10^5 tasks into 2 windows
        roll.fold_exec(float(k % 2), np.ones(1000), np.ones(1000))
        if k == 0:
            before = roll.nbytes
    assert roll.nbytes == before
    assert roll.n_windows == 2
    assert roll.stats()["tasks"] == 200_000


def test_rollup_window_geometry_and_validation():
    roll = RollupStore(window_hours=0.25)
    assert roll.window_of(0.0) == 0
    assert roll.window_of(0.249999) == 0
    assert roll.window_of(0.25) == 1
    with pytest.raises(ValueError):
        RollupStore(window_hours=0.0)


# ---------------------------------------------------------------------------
# AlertEngine
# ---------------------------------------------------------------------------


def _roll_with_miss_profile(miss_per_window, tasks_per_window=10):
    roll = RollupStore(window_hours=1.0)
    for w, miss in enumerate(miss_per_window):
        h = w + 0.5
        roll.fold_exec(h, np.ones(tasks_per_window),
                       np.zeros(tasks_per_window))
        if miss:
            roll.fold_slo(np.full(miss, h), np.ones(miss, dtype=bool))
        else:
            roll.fold_slo([h], [False])
    return roll


def test_alert_fire_and_resolve_transitions_once():
    eng = AlertEngine([AlertRule("burn", "slo_burn_rate", 0.2)])
    roll = _roll_with_miss_profile([0, 5, 6, 0, 0])
    events = eng.evaluate(roll)
    assert [(e.window, e.action) for e in events] == \
        [(1, "fire"), (3, "resolve")]             # w2 stays fired: no spam
    assert events[0].value == pytest.approx(0.5)
    assert events[0].hour == pytest.approx(2.0)   # end of window 1
    assert eng.active == []
    assert eng.counts() == {"burn": {"fire": 1, "resolve": 1}}


def test_alert_nan_windows_hold_state():
    # below min_tasks the rate has no signal: an active alert must not
    # resolve off a near-empty window
    eng = AlertEngine([AlertRule("burn", "slo_burn_rate", 0.2,
                                 min_tasks=8)])
    roll = _roll_with_miss_profile([5, 0, 0], tasks_per_window=10)
    roll.fold_exec(3.5, np.ones(2), np.zeros(2))  # w3: only 2 tasks
    eng.evaluate(roll)
    assert eng.active == ["burn"] or eng.active == []
    # deterministic expectation: w0 fires, w1 resolves, w3 (nan) holds
    assert [(e.window, e.action) for e in eng.events] == \
        [(0, "fire"), (1, "resolve")]


def test_alert_availability_trips_below_floor():
    eng = AlertEngine([AlertRule("avail", "availability", 0.9)])
    roll = RollupStore(window_hours=1.0)
    roll.note_availability(0.5, 0.5)
    roll.note_availability(2.5, 1.0)
    roll.fold_slo([3.5], [False])
    events = eng.evaluate(roll)
    assert [(e.window, e.action) for e in events] == \
        [(0, "fire"), (2, "resolve")]             # w1 forward-fills 0.5


def test_alert_carbon_pace_per_tenant_and_unknown_tenant():
    eng = AlertEngine([
        AlertRule("pace[a]", "carbon_pace", 1.0, tenant="a"),
        AlertRule("pace[ghost]", "carbon_pace", 1.0, tenant="ghost")])
    roll = RollupStore(window_hours=1.0)
    rows = roll.intern_tenants(["a"])
    roll.fold_tenant_spend(0.5, rows, [2.5])
    events = eng.evaluate(roll)
    assert [(e.rule, e.action) for e in events] == [("pace[a]", "fire")]
    assert events[0].value == pytest.approx(2.5)  # unknown tenant: no signal


def test_alert_evaluate_is_incremental():
    eng = AlertEngine([AlertRule("burn", "slo_burn_rate", 0.2)])
    roll = _roll_with_miss_profile([0, 5])
    assert len(eng.evaluate(roll)) == 1
    assert eng.evaluate(roll) == []               # nothing new yet
    roll.fold_exec(2.5, np.ones(10), np.zeros(10))
    roll.fold_slo([2.5], [False])
    events = eng.evaluate(roll)
    assert [(e.window, e.action) for e in events] == [(2, "resolve")]
    assert eng.stats()["windows_evaluated"] == 3


def test_alert_export_publishes_registry_counters_only():
    eng = AlertEngine([AlertRule("burn", "slo_burn_rate", 0.2)])
    eng.evaluate(_roll_with_miss_profile([5, 0]))
    reg = MetricsRegistry()
    eng.export(reg)
    fam = reg.get("repro_alert_events_total")
    assert fam.get(("burn", "fire")) == 1.0
    assert fam.get(("burn", "resolve")) == 1.0
    assert "repro_alert_events_total" in reg.to_text()


def test_alert_rule_rejects_unknown_kind():
    with pytest.raises(ValueError):
        AlertRule("x", "latency_spike", 1.0)


def test_alert_to_text_deterministic_transition_log():
    def build():
        eng = AlertEngine(default_rules(min_tasks=4))
        eng.evaluate(_roll_with_miss_profile([0, 5, 0]))
        return eng.to_text()

    a, b = build(), build()
    assert a == b
    assert "rule=slo_burn fire" in a and "rule=slo_burn resolve" in a
    assert AlertEngine().to_text() == ""


def test_tenant_policy_emits_sorted_carbon_pace_rules():
    from repro.tenancy import TenantPolicy, TenantRegistry, TenantSpec

    reg = TenantRegistry([
        TenantSpec("zeta", allowance_g=10.0, period_hours=2.0),
        TenantSpec("alpha", allowance_g=4.0, period_hours=1.0),
        TenantSpec("free", allowance_g=float("inf")),
    ])
    rules = TenantPolicy(registry=reg).alert_rules(window_hours=0.5)
    assert [r.tenant for r in rules] == ["alpha", "zeta"]  # inf: no rule
    assert all(r.kind == "carbon_pace" for r in rules)
    assert rules[0].threshold == pytest.approx(4.0 * 0.5 / 1.0)
    assert rules[1].threshold == pytest.approx(10.0 * 0.5 / 2.0)
    assert rules[0].name == "carbon_pace[alpha]"


# ---------------------------------------------------------------------------
# Histogram quantiles + profiler edges (registration-time granularity)
# ---------------------------------------------------------------------------


def test_family_quantile_snaps_to_bucket_upper_edge():
    reg = MetricsRegistry()
    fam = reg.histogram("lat", edges=[0.001, 0.01, 0.1, 1.0])
    fam.observe([0.0005] * 5 + [0.05] * 4 + [2.0])
    assert fam.quantile(0.5) == pytest.approx(0.001)   # rank 5 of 10
    assert fam.quantile(0.9) == pytest.approx(0.1)
    assert fam.quantile(1.0) == float("inf")           # overflow bucket
    assert np.isnan(reg.histogram("empty").quantile(0.5))
    with pytest.raises(ValueError):
        fam.quantile(1.5)
    with pytest.raises(ValueError):
        reg.counter("c").quantile(0.5)


def test_profiler_accepts_custom_edges():
    prof = StepProfiler(edges=10.0 ** np.arange(-6.0, 0.0, 1.0))
    prof.add("select", 3e-4)
    # finer edges than SPAN_EDGES_S: the 300 us span resolves to the
    # 1 ms bucket edge instead of a coarser default bucket
    assert prof.percentile_s("select", 0.5) == pytest.approx(1e-3)
    with pytest.raises(ValueError):
        StepProfiler(edges=[])


# ---------------------------------------------------------------------------
# Streaming JSONL export (DecisionTrace)
# ---------------------------------------------------------------------------


def test_export_jsonl_streaming_matches_to_jsonl(tmp_path):
    from repro.obs import DecisionTrace

    tr = DecisionTrace(capacity=64)
    rng = np.random.default_rng(0)
    for step in range(3):
        n = 5
        tr.record_batch(
            step=step, hour=0.25 * step,
            verdict=np.zeros(n, dtype=np.int8),
            node=tr.intern_names([f"n{i}" for i in range(n)]),
            score=rng.uniform(size=n), runner_up=rng.uniform(size=n),
            intensity=rng.uniform(100, 600, size=n),
            carbon_g=rng.uniform(size=n))
    path = tmp_path / "trace.jsonl"
    n = tr.export_jsonl(str(path), chunk_rows=4)   # forces chunking
    assert n == len(tr) == 15
    assert path.read_text() == tr.to_jsonl()
    n2 = tr.export_jsonl(str(path), append=True, chunk_rows=4)
    assert n2 == 15
    assert path.read_text() == tr.to_jsonl() * 2


# ---------------------------------------------------------------------------
# Hub wiring: six pillars, each None when off
# ---------------------------------------------------------------------------


def test_observability_pillars_none_when_off():
    off = Observability()
    for pillar in ("trace", "metrics", "profiler", "journeys", "rollups",
                   "alerts"):
        assert getattr(off, pillar) is None
    assert not off.enabled
    on = Observability.all(rollup_window_hours=0.1,
                           alert_rules=default_rules())
    for pillar in ("trace", "metrics", "profiler", "journeys", "rollups",
                   "alerts"):
        assert getattr(on, pillar) is not None
    assert on.rollups.window_hours == pytest.approx(0.1)
    assert len(on.alerts.rules) == 3
    solo = Observability(journeys=True)
    assert solo.enabled and solo.trace is None and solo.rollups is None
    rep = on.report()
    assert {"journeys", "rollups", "alerts"} <= set(rep)


# ---------------------------------------------------------------------------
# S4: chaos journey identity — every consumed uid in exactly one terminal
# state, phase durations summing to e2e latency
# ---------------------------------------------------------------------------


def _chaos_run(obs, event_queue="calendar"):
    """The scripted chaos drill from examples/chaos_serving.py: two
    closed-loop tenants through a lagged-detection crash + feed blackout,
    obs wired to BOTH the engine and the driver."""
    from repro.core.api import CarbonEdgeEngine
    from repro.core.cluster import EdgeCluster, PAPER_NODES
    from repro.core.providers import StaticProvider
    from repro.resilience import (Fault, FaultInjector, Resilience,
                                  ResilientProvider)
    from repro.sim import (AsyncEngineDriver, ClientPopulation,
                           ClosedLoopClientPool)
    from repro.tenancy import TenantPolicy, TenantRegistry, TenantSpec
    from repro.tenancy.spec import TenantTask

    faults = [Fault(0.004, "crash", "node-green", detected=False),
              Fault(0.008, "detect", "node-green"),
              Fault(0.010, "blackout"),
              Fault(0.016, "restore"),
              Fault(0.020, "recover", "node-green")]
    cluster = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
    cluster.profile(250.0)
    provider = ResilientProvider(StaticProvider(
        {n: cluster.nodes[n].spec.carbon_intensity for n in cluster.nodes}))
    registry = TenantRegistry([
        TenantSpec("gold", mode="green", priority=2),
        TenantSpec("batch", mode="green")])
    engine = CarbonEdgeEngine(
        cluster, mode="green", policy=TenantPolicy(registry=registry),
        provider=provider,
        resilience=Resilience(max_attempts=3, backoff_base_hours=0.002),
        obs=obs)
    pool = ClosedLoopClientPool(
        [ClientPopulation("gold", 6, mean_think_hours=0.0008,
                          slo_latency_s=2.0, priority=2),
         ClientPopulation("batch", 4, mean_think_hours=0.002,
                          slo_latency_s=10.0)],
        seed=4)
    driver = AsyncEngineDriver(
        engine, None,
        lambda uid, hour, tenant: TenantTask(cpu=0.05, mem_mb=16.0,
                                             base_latency_ms=250.0,
                                             tenant=tenant),
        horizon_hours=0.03, max_batch=8, slo_latency_s=5.0, clients=pool,
        faults=FaultInjector.scripted(faults), obs=obs,
        event_queue=event_queue)
    return driver.run(), obs


def test_chaos_every_uid_reaches_exactly_one_terminal_state():
    metrics, obs = _chaos_run(Observability.all(rollup_window_hours=0.005))
    jt = obs.journeys
    sc = jt.state_counts()
    # conservation: every request the drill consumed is in exactly one
    # terminal state — nothing open, nothing double-counted
    assert sc["open"] == 0
    assert sc["done"] + sc["reject"] + sc["dead"] == jt.max_uid
    assert sc["done"] == metrics.n_records
    # phase-sum identity over every completed journey
    cp = jt.critical_path()
    assert cp["journeys"] == sc["done"]
    assert cp["identity_max_abs_err_h"] < 1e-9
    # per-uid spot check of the same identity through the dict API
    uids = [u for u in range(1, jt.max_uid + 1)
            if jt.state[u] == J_DONE][:10]
    for u in uids:
        j = jt.journey(u)
        parts = (j["plan_defer_h"] + j["queue_wait_h"]
                 + j["budget_defer_h"] + j["retry_backoff_h"]
                 + j["service_h"])
        assert parts == pytest.approx(j["e2e_h"], abs=1e-9)


def test_chaos_rollups_conserve_totals_and_alerts_fire():
    obs = Observability.all(
        rollup_window_hours=0.005,
        alert_rules=default_rules(availability_floor=0.9, min_tasks=4))
    metrics, obs = _chaos_run(obs)
    roll = obs.rollups
    st = roll.stats()
    assert st["tasks"] == metrics.n_records        # engine fold, no dupes
    # availability dipped below 0.9 during the crash window and recovered
    avail = roll.availability()
    assert avail.min() < 0.9 and avail[-1] == pytest.approx(1.0)
    events = obs.alerts.events
    assert any(e.rule == "availability" and e.action == "fire"
               for e in events)
    assert any(e.rule == "availability" and e.action == "resolve"
               for e in events)
    # driver evaluated + exported at end of run: counters in the registry
    fam = obs.metrics.get("repro_alert_events_total")
    assert fam is not None and fam.get(("availability", "fire")) >= 1.0


def test_chaos_journeys_identical_across_event_queues():
    _, a = _chaos_run(Observability.all(rollup_window_hours=0.005),
                      event_queue="calendar")
    _, b = _chaos_run(Observability.all(rollup_window_hours=0.005),
                      event_queue="heap")
    assert a.journeys.to_text() == b.journeys.to_text()
    assert a.rollups.to_text() == b.rollups.to_text()
    assert a.alerts.to_text() == b.alerts.to_text()
