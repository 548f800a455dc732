"""CarbonEdgeEngine facade + intensity providers (core/api.py)."""
import numpy as np
import pytest

from repro.core.api import CarbonEdgeEngine
from repro.core.carbon import CarbonMonitor
from repro.core.cluster import EdgeCluster, PAPER_NODES
from repro.core.policy import (TemporalPolicy, VectorizedPolicy,
                               WeightedScoringPolicy)
from repro.core.providers import (ForecastProvider, StaticProvider,
                                  TraceProvider)
from repro.core.scheduler import MODES, Task, run_workload
from repro.core.temporal import synthetic_trace

TASK = Task(cpu=0.1, mem_mb=64, base_latency_ms=254.85)


def fresh(power=141.3, overhead=0.0674):
    c = EdgeCluster(nodes=PAPER_NODES, host_power_w=power,
                    distribution_overhead=overhead)
    c.profile(254.85)
    return c


# ---------------------------------------------------------------------------
# Providers
# ---------------------------------------------------------------------------


def test_static_provider_from_cluster():
    p = StaticProvider.from_cluster(fresh())
    assert p.intensity("node-green") == 380.0
    assert p.intensity("node-high", hour=13.0) == 620.0   # time-invariant
    with pytest.raises(KeyError):
        p.intensity("nope")


def test_trace_provider_fallback():
    tr = synthetic_trace("hydro-rich", 380.0, solar_dip=0.5)
    p = TraceProvider({"node-green": tr},
                      fallback=StaticProvider.from_cluster(fresh()))
    assert p.intensity("node-green", 13.0) == tr.at(13.0)
    assert p.intensity("node-high", 13.0) == 620.0        # fallback
    with pytest.raises(KeyError):
        TraceProvider({}).intensity("node-high")


def test_forecast_provider_composes():
    tr = synthetic_trace("r", 500.0)
    base = TraceProvider({"n": tr})
    lead = ForecastProvider(base, lead_hours=2.0)
    assert lead.intensity("n", 10.0) == pytest.approx(tr.at(12.0))
    # smoothing flattens the signal toward its mean
    smooth = ForecastProvider(base, smoothing_hours=24.0, samples=49)
    flat = [smooth.intensity("n", h) for h in (0.0, 6.0, 13.0, 19.0)]
    raw = [tr.at(h) for h in (0.0, 6.0, 13.0, 19.0)]
    assert np.std(flat) < np.std(raw)
    # composition: forecast over forecast still answers
    assert ForecastProvider(lead, lead_hours=1.0).intensity("n", 9.0) == \
        pytest.approx(tr.at(12.0))
    w = lead.window("n", 0.0, 4.0, 1.0)
    assert w.shape == (4,)


def test_monitor_reads_provider():
    tr = synthetic_trace("n", 600.0, solar_dip=0.5)
    m = CarbonMonitor(provider=TraceProvider({"n": tr}))
    m.register_region("n")                      # intensity from provider
    hi = m.record_energy("n", 1e-3, hour=19.0)  # evening peak
    lo = m.record_energy("n", 1e-3, hour=13.0)  # solar dip
    assert lo < hi
    assert m.regions["n"].tasks == 2
    # report shows what was actually billed (energy-weighted), not the
    # registration-time snapshot
    assert m.report()["n"]["intensity"] == pytest.approx(
        m.total_carbon_g() / m.total_energy_kwh())


def test_monitor_requires_intensity_without_provider():
    m = CarbonMonitor()
    with pytest.raises(ValueError):
        m.register_region("r")
    m.register_region("r", 500.0)               # classic path still works
    assert m.record_energy("r", 1e-3) == pytest.approx(0.5)


def test_monitor_explicit_registration_pins_intensity():
    """A region registered with an explicit intensity keeps it even when the
    monitor has a provider — and regions outside the provider's coverage
    still bill correctly."""
    tr = synthetic_trace("n", 600.0, solar_dip=0.5)
    m = CarbonMonitor(provider=TraceProvider({"n": tr}))
    m.register_region("n")                      # provider-driven
    m.register_region("extra", 500.0)           # pinned, not in provider
    assert m.record_energy("extra", 1e-3) == pytest.approx(0.5)
    m.register_region("n2", 100.0)              # pinned overrides provider
    assert m.record_energy("n2", 1e-3, hour=19.0) == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def test_engine_default_policy_is_vectorized():
    eng = CarbonEdgeEngine(fresh())
    assert isinstance(eng.policy, VectorizedPolicy)
    assert eng.report()["policy"] == "vectorized"


def test_engine_mode_ordering():
    """Acceptance: green < balanced <= performance carbon per inference
    through the engine API (paper Fig. 2 / Table II ordering)."""
    carbon = {}
    for mode in ("green", "balanced", "performance"):
        rep = CarbonEdgeEngine(fresh(), mode=mode).run(task=TASK,
                                                       iterations=50)
        carbon[mode] = rep["totals"]["carbon_g_per_inf"]
    assert carbon["green"] < carbon["balanced"] <= carbon["performance"]


def test_engine_matches_legacy_run_workload():
    """The engine (batched vectorized path) reproduces the scalar-loop
    workload exactly on the paper scenario."""
    legacy = run_workload(fresh(), TASK, MODES["green"], iterations=50,
                          policy=WeightedScoringPolicy())
    eng = CarbonEdgeEngine(fresh(), mode="green").run(task=TASK,
                                                      iterations=50)
    assert legacy["distribution"] == eng["distribution"]
    for k, v in legacy["totals"].items():
        assert eng["totals"][k] == pytest.approx(v)


def test_engine_batched_equals_serial_steps():
    one = CarbonEdgeEngine(fresh(), mode="green", batch_size=1).run(
        task=TASK, iterations=20)
    allb = CarbonEdgeEngine(fresh(), mode="green").run(task=TASK,
                                                       iterations=20)
    assert one["distribution"] == allb["distribution"]
    assert one["totals"]["carbon_g_per_inf"] == \
        pytest.approx(allb["totals"]["carbon_g_per_inf"])


def test_engine_bills_monitor_per_region():
    eng = CarbonEdgeEngine(fresh(), mode="green")
    rep = eng.run(task=TASK, iterations=10)
    per = rep["per_region"]
    assert per["node-green"]["tasks"] == 10
    assert per["node-high"]["tasks"] == 0
    # monitor total equals cluster-accounted total (same provider intensity)
    total = sum(r.carbon_g for r in eng.cluster.log)
    assert eng.monitor.total_carbon_g() == pytest.approx(total)


def test_engine_trace_provider_time_varying():
    """Same workload at the solar dip vs the evening ramp emits less carbon
    when intensity flows through a TraceProvider."""
    traces = {n.name: synthetic_trace(n.region, n.carbon_intensity,
                                      solar_dip=0.5) for n in PAPER_NODES}
    def run_at(hour):
        c = fresh()
        provider = TraceProvider(traces,
                                 fallback=StaticProvider.from_cluster(c))
        eng = CarbonEdgeEngine(c, mode="green", provider=provider)
        return eng.run(task=TASK, iterations=10,
                       now_hour=hour)["totals"]["carbon_g_per_inf"]
    assert run_at(13.0) < run_at(19.0)


def test_engine_infeasible_raises_and_requeues():
    """An infeasible task aborts the step but stays queued (with the rest of
    its batch), and the results executed before the failure travel on the
    exception, so the caller can retry after freeing capacity."""
    from repro.core.api import NoFeasibleNodeError

    eng = CarbonEdgeEngine(fresh())
    huge = Task(cpu=50.0, mem_mb=1e9)
    eng.submit(TASK).submit(huge).submit(TASK)
    with pytest.raises(RuntimeError, match="no feasible node") as ei:
        eng.step()
    # first task executed; the infeasible one and its tail are requeued
    assert eng.report()["totals"]["tasks"] == 1
    assert eng.queue == [huge, TASK]
    assert isinstance(ei.value, NoFeasibleNodeError)
    assert len(ei.value.executed) == 1          # the completed TaskResult


def test_requeue_preserves_fifo_order_across_retries():
    """Satellite: after a NoFeasibleNodeError the unexecuted tail keeps its
    FIFO order, later submissions land behind it, and a retry (after the
    operator drops the infeasible task) executes in the original order."""
    from repro.core.api import NoFeasibleNodeError

    t1, t2, t3, t4 = (Task(cpu=0.1, mem_mb=64, base_latency_ms=ms)
                      for ms in (100.0, 200.0, 300.0, 400.0))
    huge = Task(cpu=50.0, mem_mb=1e9)
    eng = CarbonEdgeEngine(fresh(overhead=0.0))
    eng.submit_many([t1, huge, t2, t3])
    with pytest.raises(NoFeasibleNodeError) as ei:
        eng.step()
    assert len(ei.value.executed) == 1
    assert eng.queue == [huge, t2, t3]         # tail order intact
    eng.submit(t4)
    assert eng.queue == [huge, t2, t3, t4]     # new work behind the tail
    eng.queue.remove(huge)                     # operator drops the blocker
    eng.step()
    # cluster log shows the original submission order (identified by
    # base latency; overhead=0 so measured == base)
    assert [r.latency_ms for r in eng.cluster.log] == [100.0, 200.0,
                                                       300.0, 400.0]


def test_fallback_provider_edge_cases():
    """Satellite: FallbackProvider covers primary hits, fallback hits,
    double misses, and chained composition."""
    from repro.core.providers import FallbackProvider

    tr = synthetic_trace("a", 100.0)
    p = FallbackProvider(TraceProvider({"a": tr}), StaticProvider({"b": 200.0}))
    assert p.intensity("a", 13.0) == tr.at(13.0)
    assert p.intensity("b") == 200.0
    with pytest.raises(KeyError):
        p.intensity("c")                       # both layers miss
    chained = FallbackProvider(p, StaticProvider({}, default=300.0))
    assert chained.intensity("c") == 300.0     # default catches everything


def test_forecast_window_edge_cases():
    """Satellite: empty window, zero smoothing samples, and partial trace
    coverage through the forecast wrapper."""
    from repro.core.providers import FallbackProvider

    tr = synthetic_trace("n", 500.0)
    base = TraceProvider({"n": tr})
    f = ForecastProvider(base)
    assert f.window("n", 5.0, 5.0).shape == (0,)        # empty window
    assert f.window("n", 5.0, 4.0).shape == (0,)        # inverted window
    # samples=0 with smoothing: clamped to a 2-point window, stays finite
    zs = ForecastProvider(base, smoothing_hours=2.0, samples=0)
    assert np.isfinite(zs.intensity("n", 1.0))
    # samples=0 without smoothing: exact pass-through
    assert ForecastProvider(base, samples=0).intensity("n", 3.0) == \
        pytest.approx(tr.at(3.0))
    # partial trace coverage surfaces as KeyError...
    with pytest.raises(KeyError):
        f.window("uncovered", 0.0, 2.0)
    # ...unless the base composes a fallback
    covered = ForecastProvider(
        FallbackProvider(base, StaticProvider({}, default=123.0)))
    np.testing.assert_allclose(covered.window("uncovered", 0.0, 2.0, 0.5),
                               123.0)


def test_engine_ledgers_agree_with_pue():
    """Regression: cluster and monitor must bill with the same PUE."""
    c = EdgeCluster(nodes=PAPER_NODES, host_power_w=141.3, pue=1.5)
    c.profile(254.85)
    eng = CarbonEdgeEngine(c, mode="green")
    eng.run(task=TASK, iterations=5)
    cluster_total = sum(r.carbon_g for r in c.log)
    assert eng.monitor.total_carbon_g() == pytest.approx(cluster_total)


def test_partial_coverage_provider_skips_filtered_nodes():
    """A provider with no entry for a filtered-out node must not fail the
    vectorized path (the scalar oracle never queries filtered nodes)."""
    c = fresh()
    c.nodes["node-high"].load = 0.9          # filtered by Algorithm 1 line 3
    partial = TraceProvider({n: synthetic_trace(n, 400.0)
                             for n in ("node-medium", "node-green")})
    with pytest.raises(KeyError):
        partial.intensity("node-high")       # genuinely uncovered
    mon = CarbonMonitor(provider=partial)
    mon.register_region("node-high", 620.0)  # pin the accounting gap
    eng = CarbonEdgeEngine(c, mode="green", provider=partial, monitor=mon)
    rep = eng.run(task=TASK, iterations=3)   # selection must not KeyError
    assert rep["totals"]["tasks"] == 3


def test_router_partial_provider_falls_back_to_pod_intensity():
    """A router with a partial trace feed keeps working: uncovered pods use
    their own static carbon_intensity (FallbackProvider)."""
    from repro.core.router import GreenRouter, PodSpec

    pods = [PodSpec("pod-high", 256, "coal-heavy", 620.0),
            PodSpec("pod-green", 256, "hydro-rich", 380.0)]
    partial = TraceProvider({"pod-green": synthetic_trace("hy", 380.0)})
    r = GreenRouter(pods, mode="green", provider=partial)
    assert r.provider.intensity("pod-high") == 620.0     # fallback
    for st in r.cluster.nodes.values():
        st.avg_time_ms = 10.0                            # seed history
    assert r.route() == "pod-green"


def test_engine_rejects_miswired_monitor():
    """A monitor wired to a different provider with unpinned regions would
    silently bill from the wrong grid signal — must raise."""
    other = StaticProvider({n.name: 1.0 for n in PAPER_NODES})
    mon = CarbonMonitor(provider=other)
    with pytest.raises(ValueError, match="different"):
        CarbonEdgeEngine(fresh(), monitor=mon)
    # fully pinned regions are sound regardless of the monitor's provider
    mon2 = CarbonMonitor(provider=other)
    for n in PAPER_NODES:
        mon2.register_region(n.name, n.carbon_intensity)
    eng = CarbonEdgeEngine(fresh(), mode="green", monitor=mon2)
    assert eng.run(task=TASK, iterations=2)["totals"]["tasks"] == 2


def test_engine_requeues_on_unexpected_failure():
    """Regression: a provider error mid-step must not lose submitted tasks."""
    bad = StaticProvider({"node-high": 620.0})    # missing two cluster nodes
    eng = CarbonEdgeEngine(fresh(), mode="green",
                           provider=StaticProvider.from_cluster(fresh()))
    eng.provider = bad                            # break it after construction
    eng.submit(TASK).submit(TASK)
    with pytest.raises(KeyError):
        eng.step()
    assert eng.queue == [TASK, TASK]              # nothing silently dropped


def test_temporal_scheduler_rejects_conflicting_slot_hours():
    from repro.core.temporal import TemporalScheduler

    c = fresh()
    with pytest.raises(ValueError, match="conflicting slot_hours"):
        TemporalScheduler(c, {}, MODES["green"], slot_hours=0.25,
                          policy=TemporalPolicy())
    # matching or omitted slot_hours is fine
    s = TemporalScheduler(c, {}, MODES["green"], slot_hours=0.5,
                          policy=TemporalPolicy())
    assert s.slot_hours == 0.5


def test_temporal_policy_backend_keeps_inf_threshold():
    """Forcing a backend must not silently reinstate the 5000 ms latency
    filter the temporal path documents as disabled."""
    p = TemporalPolicy(backend="pallas")
    assert p.scorer.latency_threshold_ms == float("inf")
    with pytest.raises(ValueError, match="conflicting latency_threshold_ms"):
        TemporalPolicy(scorer=VectorizedPolicy(),
                       latency_threshold_ms=float("inf"))
    with pytest.raises(ValueError, match="conflicting backend"):
        TemporalPolicy(scorer=VectorizedPolicy(backend="numpy"),
                       backend="pallas")


def test_temporal_policy_plain_task_respects_carbon_weight():
    """Regression: a plain Task (duration 0) must not neutralize the Eq. 4
    column — TemporalPolicy and the instantaneous policies must agree."""
    c = fresh()
    sel_t = TemporalPolicy().select(c, TASK, MODES["green"])
    sel_v = VectorizedPolicy().select(c, TASK, MODES["green"])
    assert sel_t == sel_v == "node-green"


def test_temporal_policy_partial_coverage_provider():
    """Regression: the slot grid must not query the provider for filtered
    nodes (same partial-coverage guarantee as the instantaneous policies)."""
    from repro.core.temporal import DeferrableTask

    c = fresh()
    c.nodes["node-high"].load = 0.9
    partial = TraceProvider({n: synthetic_trace(n, 400.0)
                             for n in ("node-medium", "node-green")})
    t = DeferrableTask(cpu=0.05, mem_mb=16, deadline_hours=4.0,
                       duration_hours=0.25)
    pl = TemporalPolicy().place(c, t, MODES["green"], partial, now_hour=19.0)
    assert pl is not None and pl.node != "node-high"


def test_engine_accepts_provider_less_monitor():
    """A caller-constructed CarbonMonitor without a provider adopts the
    engine's provider, so both ledgers read the same signal."""
    eng = CarbonEdgeEngine(fresh(), mode="green", monitor=CarbonMonitor())
    rep = eng.run(task=TASK, iterations=3)
    assert rep["per_region"]["node-green"]["tasks"] == 3
    assert rep["per_region"]["node-green"]["intensity"] == pytest.approx(380.0)


def test_engine_ledgers_agree_with_time_varying_provider():
    """Regression: with a TraceProvider and now_hour != 0, the cluster's
    execution ledger and the monitor's per-region ledger must bill the same
    carbon — including through a caller-supplied provider-less monitor."""
    traces = {n.name: synthetic_trace(n.region, n.carbon_intensity,
                                      solar_dip=0.5) for n in PAPER_NODES}
    c = fresh()
    provider = TraceProvider(traces, fallback=StaticProvider.from_cluster(c))
    eng = CarbonEdgeEngine(c, mode="green", provider=provider,
                           monitor=CarbonMonitor())
    eng.run(task=TASK, iterations=5, now_hour=13.0)
    cluster_total = sum(r.carbon_g for r in c.log)
    assert eng.monitor.total_carbon_g() == pytest.approx(cluster_total)


def test_engine_temporal_policy_plugs_in():
    """The TemporalPolicy satisfies the SchedulingPolicy interface and can
    drive the engine for urgent tasks."""
    eng = CarbonEdgeEngine(fresh(), mode="green", policy=TemporalPolicy())
    rep = eng.run(task=TASK, iterations=5)
    assert rep["policy"] == "temporal"
    assert rep["totals"]["tasks"] == 5


def test_sweep_endpoints_reproduce_mode_weights():
    """sweep_weights at the performance mode's own w_C reproduces the mode
    exactly (the non-carbon sum is computed, not hardcoded)."""
    from repro.core.scheduler import sweep_weights

    base = MODES["performance"]
    got = sweep_weights(base.w_c)
    np.testing.assert_allclose(got.as_array(), base.as_array(), atol=1e-12)
    # every sweep point stays normalised
    for w_c in np.arange(0.0, 0.95, 0.05):
        assert abs(sum(sweep_weights(float(w_c)).as_array()) - 1.0) < 1e-9
