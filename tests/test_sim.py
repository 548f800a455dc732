"""Discrete-event serving simulator (repro.sim, DESIGN.md §2)."""
import numpy as np
import pytest

from repro.core.api import CarbonEdgeEngine
from repro.core.cluster import EdgeCluster, PAPER_NODES
from repro.core.providers import (ForecastProvider, StaticProvider,
                                  TraceProvider)
from repro.core.scheduler import Task
from repro.core.temporal import DeferrableTask, plan_wake, synthetic_trace
from repro.sim import (AsyncEngineDriver, ConstantRateArrivals,
                       DiurnalArrivals, EventHeap, EventKind, MMPPArrivals,
                       PoissonArrivals, TraceReplayArrivals, VirtualClock)

TASK = Task(cpu=0.05, mem_mb=16.0, base_latency_ms=250.0)


def fresh_cluster():
    c = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
    c.profile(250.0)
    return c


def duck_traces():
    return {
        "node-high": synthetic_trace("coal-heavy", 620.0, solar_dip=0.1),
        "node-medium": synthetic_trace("cn-average", 530.0, solar_dip=0.3),
        "node-green": synthetic_trace("hydro-rich", 380.0, solar_dip=0.5),
    }


def trace_engine(mode="green"):
    c = fresh_cluster()
    provider = TraceProvider(duck_traces(),
                             fallback=StaticProvider.from_cluster(c))
    return CarbonEdgeEngine(c, mode=mode, provider=provider)


def make_driver(engine, arrivals, *, factory=None, **kw):
    return AsyncEngineDriver(engine, arrivals,
                             factory or (lambda uid, hour: TASK), **kw)


# ---------------------------------------------------------------------------
# Clock and events
# ---------------------------------------------------------------------------


def test_clock_is_monotonic():
    clk = VirtualClock(5.0)
    assert clk.advance_to(6.5) == 6.5
    with pytest.raises(ValueError, match="backwards"):
        clk.advance_to(6.0)


def test_event_heap_orders_by_time_then_insertion():
    h = EventHeap()
    h.push(2.0, EventKind.BATCH_READY, "late")
    h.push(1.0, EventKind.ARRIVAL, "a")
    h.push(1.0, EventKind.DEFER_WAKE, "b")       # same instant: FIFO
    h.push(0.5, EventKind.INTENSITY_TICK, "first")
    got = [h.pop().payload for _ in range(len(h))]
    assert got == ["first", "a", "b", "late"]


# ---------------------------------------------------------------------------
# Arrival processes: determinism, windows, shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("proc", [
    PoissonArrivals(40.0, seed=3),
    DiurnalArrivals(40.0, seed=3),
    MMPPArrivals(10.0, 120.0, mean_sojourn_hours=0.5, seed=3),
])
def test_arrivals_deterministic_and_windowed(proc):
    a = proc.times(17.0, 6.0)
    b = proc.times(17.0, 6.0)
    np.testing.assert_array_equal(a, b)          # same seed, same stream
    assert np.all(np.diff(a) >= 0)
    assert a.size == 0 or (a[0] >= 17.0 and a[-1] < 23.0)
    c = type(proc)(**{**proc.__dict__, "seed": 4}).times(17.0, 6.0)
    assert a.shape != c.shape or not np.allclose(a, c)


def test_constant_rate_is_exact():
    ts = ConstantRateArrivals(50.0).times(2.0, 1.0)
    assert ts.shape == (50,)
    np.testing.assert_allclose(np.diff(ts), 1.0 / 50.0)
    assert ConstantRateArrivals(50.0).times(0.0, 0.0).size == 0


def test_diurnal_rate_tracks_profile():
    proc = DiurnalArrivals(200.0, seed=0)
    evening = proc.times(18.0, 2.0).size         # demand peak
    night = proc.times(3.0, 2.0).size            # demand trough
    assert evening > night


def test_trace_replay_clips_to_window():
    proc = TraceReplayArrivals([1.0, 2.5, 3.0, 9.0])
    np.testing.assert_array_equal(proc.times(2.0, 2.0), [2.5, 3.0])


def test_diurnal_rejects_profile_above_sampled_supremum():
    """A custom profile spikier than the sampling grid invalidates the
    thinning bound — rejected loudly; an explicit profile_sup fixes it."""
    spike = lambda h: 10.0 if 12.04 < h % 24 < 12.06 else 1.0
    bad = DiurnalArrivals(5000.0, seed=0, profile=spike)
    with pytest.raises(ValueError, match="profile_sup"):
        bad.times(12.0, 0.1)
    ok = DiurnalArrivals(5000.0, seed=0, profile=spike, profile_sup=10.0)
    assert ok.times(12.0, 0.1).size > 0


# ---------------------------------------------------------------------------
# Driver: parity, billing, queueing
# ---------------------------------------------------------------------------


def test_driver_static_parity_with_engine_run():
    """Constant-rate arrivals + StaticProvider through the driver must
    reproduce the paper-scenario engine numbers exactly (Table II/IV/V are
    a special case of the simulator)."""
    ref = CarbonEdgeEngine(fresh_cluster(), mode="green")
    ref_rep = ref.run(task=TASK, iterations=50)

    engine = CarbonEdgeEngine(fresh_cluster(), mode="green")
    m = make_driver(engine, ConstantRateArrivals(50.0),
                    horizon_hours=1.0, max_batch=16).run()
    sim_rep = engine.report()
    assert m.summary()["tasks"] == 50
    assert sim_rep["distribution"] == ref_rep["distribution"]
    assert sim_rep["totals"]["carbon_g_per_inf"] == \
        pytest.approx(ref_rep["totals"]["carbon_g_per_inf"], abs=1e-15)


def test_driver_advances_now_hour_into_billing():
    """Arrivals spread over the duck curve must bill each batch at its own
    hour: cluster and monitor ledgers agree, and the total differs from a
    frozen-hour drain of the same workload."""
    engine = trace_engine()
    m = make_driver(engine, ConstantRateArrivals(8.0),
                    start_hour=10.0, horizon_hours=8.0, max_batch=4).run()
    cluster_total = sum(r.carbon_g for r in engine.cluster.log)
    assert engine.monitor.total_carbon_g() == pytest.approx(cluster_total)
    assert sum(r.carbon_g for r in m.records) == pytest.approx(cluster_total)

    frozen = trace_engine()
    with pytest.warns(DeprecationWarning):
        frozen_rep = frozen.run(tasks=[TASK] * 64, now_hour=10.0)
    frozen_total = sum(r.carbon_g for r in frozen.cluster.log)
    assert cluster_total != pytest.approx(frozen_total, rel=1e-3)
    assert frozen_rep["totals"]["tasks"] == 64


def test_driver_queueing_delay_emerges_under_load():
    """Near-saturation arrivals must queue: p95 wait well above the
    light-load p95, SLO violations appearing."""
    def waits(rate):
        engine = CarbonEdgeEngine(fresh_cluster(), mode="green")
        m = make_driver(engine, PoissonArrivals(rate, seed=11),
                        horizon_hours=0.05, max_batch=16,
                        slo_latency_s=2.0).run()
        return m.summary()
    light, heavy = waits(500.0), waits(12000.0)
    assert heavy["wait_s_p95"] > light["wait_s_p95"]
    assert heavy["wait_s_p95"] > 0.5
    assert heavy["slo_violation_rate"] > light["slo_violation_rate"]
    # wait histogram counts every task exactly once
    assert sum(heavy["wait_histogram"]) == heavy["tasks"]


def test_driver_seed_determinism_byte_identical():
    """Satellite: two runs with the same seed produce byte-identical
    metric reports (arrivals, event ordering, billing all deterministic)."""
    def report():
        engine = trace_engine()
        m = make_driver(engine, MMPPArrivals(20.0, 200.0, 0.25, seed=9),
                        start_hour=17.0, horizon_hours=2.0, max_batch=8,
                        slo_latency_s=1.0, tick_hours=0.5).run()
        return m.to_text()
    a, b = report(), report()
    assert a.encode() == b.encode()
    assert "tick hour=" in a and "task uid=" in a


def test_driver_intensity_ticks_sample_timeline():
    engine = trace_engine()
    m = make_driver(engine, ConstantRateArrivals(4.0),
                    start_hour=10.0, horizon_hours=4.0, tick_hours=1.0).run()
    assert len(m.timeline) == 4
    hours = [t.hour for t in m.timeline]
    assert hours == [11.0, 12.0, 13.0, 14.0]
    # duck curve: fleet-mean intensity dips toward 13:00
    assert m.timeline[2].mean_intensity < m.timeline[0].mean_intensity
    assert m.timeline[-1].carbon_g_cum == pytest.approx(
        engine.monitor.total_carbon_g(), rel=1e-6)


# ---------------------------------------------------------------------------
# Forecast-driven deferral through the driver
# ---------------------------------------------------------------------------


def deferral_run(forecast, deadline=24.0):
    engine = trace_engine()
    factory = lambda uid, hour: DeferrableTask(
        cpu=0.05, mem_mb=16.0, base_latency_ms=250.0,
        deadline_hours=deadline, duration_hours=0.25)
    m = make_driver(engine, PoissonArrivals(30.0, seed=5), factory=factory,
                    start_hour=17.0, horizon_hours=2.0, max_batch=16,
                    forecast=forecast).run()
    return m


def test_deferral_accurate_forecast_beats_run_now():
    run_now = deferral_run(None)
    deferred = deferral_run(ForecastProvider(TraceProvider(duck_traces())))
    assert run_now.deferred_tasks == 0
    assert deferred.deferred_tasks == deferred.summary()["tasks"]
    assert deferred.summary()["carbon_g_total"] < \
        0.7 * run_now.summary()["carbon_g_total"]
    # deferral trades latency for carbon: waits include the parked time
    assert deferred.summary()["wait_s_p50"] > run_now.summary()["wait_s_p50"]


def test_deferral_forecast_error_degrades_monotonically():
    base = TraceProvider(duck_traces())
    totals = [deferral_run(ForecastProvider(base, lead_hours=b)
                           ).summary()["carbon_g_total"]
              for b in (0.0, 1.0, 2.0, 4.0)]
    assert all(a < b + 1e-12 for a, b in zip(totals, totals[1:])), totals


def test_deferred_tasks_respect_deadline():
    """A 6 h deadline from 17:00 cannot reach the next-day solar dip, so
    wakes stay within the window; early arrivals (for whom 17:00 is
    already the window minimum) legitimately run immediately."""
    m = deferral_run(ForecastProvider(TraceProvider(duck_traces())),
                     deadline=6.0)
    assert m.deferred_tasks > 0
    for r in m.records:
        assert r.start_hour - r.submit_hour <= 6.0 + 1e-9
        assert r.deferred_hours <= 6.0 - 0.25 + 0.5   # deadline - duration (+slot)


def test_plan_wake_edge_cases():
    c = fresh_cluster()
    provider = TraceProvider(duck_traces())
    urgent = Task(cpu=0.05, mem_mb=16.0)
    assert plan_wake(provider, c, urgent, 17.0) == 17.0   # no slack
    t = DeferrableTask(cpu=0.05, mem_mb=16.0, deadline_hours=24.0,
                       duration_hours=0.25)
    wake = plan_wake(provider, c, t, 17.0)
    assert 17.0 < wake <= 41.0
    # next-day solar dip is the global minimum within the window
    assert wake == pytest.approx(24.0 + 13.0, abs=1.0)
    # all nodes infeasible -> wake immediately
    for st in c.nodes.values():
        st.load = 0.95
    assert plan_wake(provider, c, t, 17.0) == 17.0


def test_plan_wake_window_matches_sampled():
    """A ForecastProvider (window path) and its base provider (per-slot
    sampling path) must plan the same wake slot when the forecast is
    exact."""
    c = fresh_cluster()
    base = TraceProvider(duck_traces())
    t = DeferrableTask(cpu=0.05, mem_mb=16.0, deadline_hours=12.0,
                       duration_hours=0.5)
    assert plan_wake(ForecastProvider(base), c, t, 19.0) == \
        plan_wake(base, c, t, 19.0)


# ---------------------------------------------------------------------------
# Batched plan_wake vs the scalar oracle (ISSUE 3 satellite)
# ---------------------------------------------------------------------------


def _random_wake_cluster(rng, n):
    from tests.test_policy_parity import random_cluster

    c = random_cluster(rng, n)
    for st in c.nodes.values():             # keep a good share feasible
        st.load = float(rng.uniform(0.0, 0.9))
    return c


@pytest.mark.parametrize("seed", range(8))
def test_plan_wake_batched_matches_scalar_randomized(seed):
    """plan_wake (batched (S, N) grid) == plan_wake_scalar (nodes x slots
    Python loop) on randomized fleets, providers and deadlines — exact
    equality, ties included."""
    from repro.core.temporal import plan_wake_scalar

    rng = np.random.default_rng(seed)
    c = _random_wake_cluster(rng, int(rng.integers(2, 16)))
    names = list(c.nodes)
    traces = {n: synthetic_trace(n, float(rng.uniform(100.0, 900.0)),
                                 seed=int(rng.integers(0, 100)))
              for n in names[:int(rng.integers(1, len(names) + 1))]}
    provider = TraceProvider(traces, fallback=StaticProvider.from_cluster(c))
    if seed % 3 == 1:
        provider = ForecastProvider(provider, lead_hours=0.5,
                                    smoothing_hours=1.0)
    elif seed % 3 == 2:
        provider = StaticProvider.from_cluster(c)   # constant: full tie
    t = DeferrableTask(cpu=float(rng.uniform(0.01, 0.5)),
                       mem_mb=float(rng.uniform(4.0, 64.0)),
                       deadline_hours=float(rng.uniform(0.0, 30.0)),
                       duration_hours=float(rng.uniform(0.0, 2.0)))
    now = float(rng.uniform(0.0, 24.0))
    assert plan_wake(provider, c, t, now) == \
        plan_wake_scalar(provider, c, t, now)


def test_plan_wake_tie_breaks_earliest_slot_first_node():
    """Exact ties: a constant signal must wake immediately (earliest
    slot), and when two nodes share the minimum the first (insertion
    order) node's earliest minimum slot must win."""
    from repro.core.temporal import IntensityTrace, plan_wake_scalar

    c = fresh_cluster()
    t = DeferrableTask(cpu=0.05, mem_mb=16.0, deadline_hours=6.0,
                       duration_hours=0.5)
    # constant everywhere -> every (slot, node) ties -> run now
    const = StaticProvider.from_cluster(c)
    assert plan_wake(const, c, t, 3.0) == 3.0
    # node-high (first) has its min at slot 4, node-medium the same min
    # value at slot 2: the scalar oracle keeps the FIRST node's slot.
    vals_high = [500.0] * 24
    vals_high[5] = 100.0                      # 3.0 + 4*0.5 = hour 5
    vals_med = [500.0] * 24
    vals_med[4] = 100.0                       # 3.0 + 2*0.5 = hour 4
    provider = TraceProvider({
        "node-high": IntensityTrace("a", tuple(vals_high)),
        "node-medium": IntensityTrace("b", tuple(vals_med)),
        "node-green": IntensityTrace("c", (500.0,) * 24),
    })
    want = plan_wake_scalar(provider, c, t, 3.0)
    assert want == 5.0                        # first node's earliest min
    assert plan_wake(provider, c, t, 3.0) == want


def test_plan_wake_duck_typed_cluster():
    """A cluster-like with .nodes but no feature_cache plumbing (custom
    executors) must still plan — via the scalar feasibility fallback."""
    from repro.core.temporal import plan_wake_scalar

    real = fresh_cluster()

    class DuckCluster:
        nodes = real.nodes

    provider = TraceProvider(duck_traces())
    t = DeferrableTask(cpu=0.05, mem_mb=16.0, deadline_hours=24.0,
                       duration_hours=0.25)
    assert plan_wake(provider, DuckCluster(), t, 17.0) == \
        plan_wake_scalar(provider, real, t, 17.0)


def test_fallback_provider_batch_splits_by_coverage():
    """FallbackProvider with a partial-coverage primary resolves the batch
    with covers()-split batched calls — values must equal the scalar path."""
    from repro.core.providers import FallbackProvider, intensity_batch

    c = fresh_cluster()
    primary = TraceProvider({"node-green": duck_traces()["node-green"]})
    provider = FallbackProvider(primary, StaticProvider.from_cluster(c))
    names = list(c.nodes)
    hours = np.array([0.0, 6.5, 13.0])
    grid = intensity_batch(provider, names, hours)
    for s, hr in enumerate(hours):
        for j, n in enumerate(names):
            assert grid[s, j] == provider.intensity(n, float(hr)), (n, hr)


def test_plan_wake_batch_matches_per_task():
    from repro.core.temporal import plan_wake_batch

    rng = np.random.default_rng(3)
    c = _random_wake_cluster(rng, 6)
    provider = TraceProvider(
        {n: synthetic_trace(n, 400.0 + 50.0 * i, seed=i)
         for i, n in enumerate(c.nodes)})
    tasks = [DeferrableTask(cpu=float(rng.uniform(0.01, 0.4)),
                            mem_mb=8.0,
                            deadline_hours=float(rng.uniform(0.0, 20.0)),
                            duration_hours=0.25)
             for _ in range(7)]
    batch = plan_wake_batch(provider, c, tasks, 17.0)
    singles = [plan_wake(provider, c, t, 17.0) for t in tasks]
    np.testing.assert_array_equal(batch, singles)


def test_sim_determinism_byte_identical_with_batched_plan_wake(monkeypatch):
    """The batched planner must preserve the sim determinism contract:
    to_text() byte-identical to a run forced through the scalar oracle."""
    import repro.core.temporal as temporal_mod

    text_batched = deferral_run(
        ForecastProvider(TraceProvider(duck_traces()))).to_text()
    monkeypatch.setattr(temporal_mod, "plan_wake",
                        temporal_mod.plan_wake_scalar)
    text_scalar = deferral_run(
        ForecastProvider(TraceProvider(duck_traces()))).to_text()
    assert text_batched == text_scalar


# ---------------------------------------------------------------------------
# Engine run_until / peek / partial drain
# ---------------------------------------------------------------------------


def test_engine_peek_and_partial_drain():
    eng = CarbonEdgeEngine(fresh_cluster(), mode="green")
    eng.submit_many([TASK] * 5)
    assert eng.peek(2) == [TASK, TASK]
    assert len(eng.queue) == 5                    # peek does not dequeue
    assert len(eng.step(limit=2)) == 2
    assert len(eng.queue) == 3


def test_engine_run_until_advances_billing_hour():
    """run_until bills successive batches at advancing hours; on a
    time-varying provider that differs from the frozen-hour run."""
    a = trace_engine()
    a.submit_many([TASK] * 400)
    rep = a.run_until(end_hour=24.0, start_hour=12.5, limit=50)
    assert rep["totals"]["tasks"] == 400
    assert rep["end_hour"] > 12.5
    assert a.monitor.total_carbon_g() == pytest.approx(
        sum(r.carbon_g for r in a.cluster.log))

    b = trace_engine()
    with pytest.warns(DeprecationWarning, match="frozen"):
        b.run(tasks=[TASK] * 400, now_hour=12.5)
    assert sum(r.carbon_g for r in a.cluster.log) != \
        pytest.approx(sum(r.carbon_g for r in b.cluster.log), rel=1e-6)


def test_engine_run_until_stops_at_end_hour():
    eng = CarbonEdgeEngine(fresh_cluster(), mode="green")
    eng.submit_many([TASK] * 10)
    rep = eng.run_until(end_hour=0.0, start_hour=0.0)
    assert rep["totals"] == {"tasks": 0} and len(eng.queue) == 10


def test_engine_run_until_no_progress_terminates():
    """Regression: a step that drains nothing (limit=0) must bail instead
    of spinning forever."""
    eng = CarbonEdgeEngine(fresh_cluster(), mode="green")
    eng.submit_many([TASK] * 3)
    rep = eng.run_until(end_hour=10.0, limit=0)
    assert rep["totals"] == {"tasks": 0} and len(eng.queue) == 3


def test_engine_run_static_provider_does_not_warn():
    import warnings
    eng = CarbonEdgeEngine(fresh_cluster(), mode="green")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng.run(task=TASK, iterations=3)


# ---------------------------------------------------------------------------
# ServingEngine as the driver's executor (wait/service split)
# ---------------------------------------------------------------------------


def serving_engine():
    import jax
    import numpy as np

    from repro.configs.registry import reduced_config
    from repro.core import costmodel, energy
    from repro.core.router import GreenRouter, PodSpec
    from repro.models import transformer
    from repro.runtime.serving import ServingEngine

    pods = [PodSpec("pod-high", 256, "coal-heavy", 620.0),
            PodSpec("pod-green", 256, "hydro-rich", 380.0)]
    cfg = reduced_config("qwen3-1.7b")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    router = GreenRouter(pods, mode="green")
    terms = energy.roofline(2.0 * cfg.active_param_count() * 2,
                            costmodel.step_hbm_bytes(cfg, 16, 2, "decode"),
                            0.0, 256)
    router.seed_profile({p.name: terms for p in pods})
    return cfg, ServingEngine(cfg, params, router, max_len=32, batch_size=4)


def test_serving_completion_splits_wait_and_service():
    """Satellite: queue wait (submit -> batch start) and per-request
    service (until *its own* last token) are reported separately; latency
    is their sum, and a short request no longer inherits the batch dt."""
    from repro.runtime.serving import Request

    cfg, eng = serving_engine()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
               for _ in range(2)]
    eng.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=2),
               now_s=10.0)
    eng.submit(Request(uid=1, prompt=prompts[1], max_new_tokens=8),
               now_s=25.0)
    eng.submit(Request(uid=2, prompt=prompts[0], max_new_tokens=0),
               now_s=25.0)
    short, long, zero = eng.run_batch(now_hour=0.0, now_s=40.0)
    assert short.wait_s == pytest.approx(30.0)
    assert long.wait_s == pytest.approx(15.0)
    assert 0.0 < short.service_s < long.service_s   # own last token, not batch dt
    assert short.latency_s == pytest.approx(short.wait_s + short.service_s)
    assert len(short.tokens) == 2 and len(long.tokens) == 8
    # a zero-token request's service ends at prefill, before any decode
    assert zero.tokens == [] and 0.0 < zero.service_s <= short.service_s


def test_serving_submit_preserves_virtual_time_zero():
    """Regression: a pre-stamped virtual submission time of exactly 0.0
    (an arrival at simulated hour 0) must not be clobbered by the wall
    clock."""
    from repro.runtime.serving import Request

    r = Request(uid=0, prompt=np.zeros(4, np.int32), submitted_s=0.0)
    cfg, eng = serving_engine()
    eng.submit(r)
    assert r.submitted_s == 0.0
    r2 = Request(uid=1, prompt=np.zeros(4, np.int32))
    eng.submit(r2)
    assert r2.submitted_s is not None and r2.submitted_s > 0.0  # wall stamp


def test_serving_engine_drives_through_sim():
    """ServingEngine satisfies the BatchExecutor protocol: the driver
    interleaves virtual-time arrivals with real prefill/decode batches."""
    from repro.runtime.serving import Request

    cfg, eng = serving_engine()
    rng = np.random.default_rng(1)

    def factory(uid, hour):
        # deliberately NOT pre-stamping submitted_s: the driver must stamp
        # virtual time so Completion.wait_s stays on the sim clock
        return Request(uid=uid,
                       prompt=rng.integers(0, cfg.vocab_size,
                                           size=6).astype(np.int32),
                       max_new_tokens=2)

    from repro.sim import TraceReplayArrivals
    m = AsyncEngineDriver(eng, TraceReplayArrivals([0.1, 0.1, 0.2]), factory,
                          start_hour=0.0, horizon_hours=1.0,
                          max_batch=2).run()
    assert m.summary()["tasks"] == 3
    assert {r.node for r in m.records} == {"pod-green"}
    assert all(r.carbon_g > 0 for r in m.records)
    # per-task energy backfilled from the router monitor's step delta, so
    # carbon > 0 never pairs with the impossible energy == 0
    assert all(r.energy_kwh > 0 for r in m.records)
    assert m.summary()["energy_kwh_total"] == pytest.approx(
        eng.router.monitor.total_energy_kwh())
    assert eng.report()["completed"] == 3
    # virtual-time waits, not wall/virtual clock mixing: requests batched
    # at their arrival instant waited ~0 virtual seconds
    assert all(c.wait_s < 60.0 for c in eng.completions)
