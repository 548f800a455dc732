"""Fault injection & failure-aware scheduling (repro.resilience,
DESIGN.md §10).

Covers: deterministic fault-schedule generation (fixed seed -> identical
schedule; the no-lag oracle variant), the circuit-breaker state machine
(threshold open, doubling cooldown, half-open probe, success close /
failure re-open), the FeatureCache availability mask (literally-absent
when healthy, data_rev-bumped mutations, rebuild re-projection), the
last-known-good degraded provider (healthy bit-identity, blackout
persistence values, staleness-widened conformal intervals), the engine
gate (zero-fault bit-identity on both execute paths, contact-failure
failover, capped-backoff retry -> dead-letter, partition cut-0 re-bill)
and the sim driver's fault events (zero-fault schedule byte-identity,
fixed-fault-seed byte-identical repeats).
"""
import numpy as np
import pytest

from repro.core.api import CarbonEdgeEngine
from repro.core.cluster import EdgeCluster, NodeSpec
from repro.core.providers import StaticProvider, intensity_interval_batch
from repro.core.scheduler import Task
from repro.resilience import (Fault, FaultInjector, FleetHealth, Resilience,
                              ResilientProvider)
from repro.sim import AsyncEngineDriver, PoissonArrivals
from repro.sim.events import EventKind


def fleet(n=6, cpu=2.0):
    c = EdgeCluster(nodes=[])
    for i in range(n):
        c.add_node(NodeSpec(f"n{i}", cpu=cpu, mem_mb=16000.0,
                            carbon_intensity=100.0 + 40.0 * i))
    return c


def engine(cluster=None, *, resilience=None, batch_execute=True, **kw):
    return CarbonEdgeEngine(cluster if cluster is not None else fleet(),
                            resilience=resilience,
                            batch_execute=batch_execute, **kw)


# ---------------------------------------------------------------------------
# Fault schedules
# ---------------------------------------------------------------------------


def test_generate_is_deterministic_per_seed():
    nodes = [f"n{i}" for i in range(8)]
    kw = dict(crash_rate_per_hour=2.0, mttr_hours=0.1,
              detect_delay_hours=0.02, outage_rate_per_hour=1.0,
              straggle_rate_per_hour=1.0, flap_rate_per_hour=1.0)
    a = FaultInjector.generate(nodes, 1.0, seed=5, **kw)
    b = FaultInjector.generate(nodes, 1.0, seed=5, **kw)
    c = FaultInjector.generate(nodes, 1.0, seed=6, **kw)
    assert a.schedule == b.schedule
    assert a.schedule != c.schedule
    assert a.schedule          # the rates above must actually produce faults
    hours = [f.hour for f in a.schedule]
    assert hours == sorted(hours)


def test_schedule_shapes_and_event_kinds():
    inj = FaultInjector.generate(["n0"], 1.0, seed=0,
                                 crash_rate_per_hour=50.0, mttr_hours=0.01,
                                 detect_delay_hours=0.005)
    kinds = {f.kind for f in inj.schedule}
    assert kinds == {"crash", "detect", "recover"}
    for f in inj.schedule:
        if f.kind == "crash":
            assert not f.detected
            assert f.event_kind is EventKind.NODE_DOWN
        elif f.kind == "recover":
            assert f.event_kind is EventKind.NODE_UP
    # every crash has a matching later recover
    win = inj.crash_windows()
    assert len(win) == sum(1 for f in inj.schedule if f.kind == "crash")
    assert all(up > down for _, down, up in win)
    assert inj.mttr_hours() > 0.0
    assert 0.0 <= inj.fleet_availability(1, 1.0) < 1.0


def test_without_detection_lag_oracle():
    inj = FaultInjector.generate(["n0", "n1"], 1.0, seed=2,
                                 crash_rate_per_hour=5.0,
                                 detect_delay_hours=0.05)
    oracle = inj.without_detection_lag()
    assert all(f.kind != "detect" for f in oracle.schedule)
    assert all(f.detected for f in oracle.schedule if f.kind == "crash")
    assert oracle.crash_windows() == inj.crash_windows()


def test_blackout_fault_toggles_provider():
    prov = ResilientProvider(StaticProvider({"n0": 100.0}))
    eng = engine(fleet(1), provider=prov, resilience=Resilience())
    inj = FaultInjector.scripted([Fault(0.1, "blackout"),
                                  Fault(0.2, "restore")])
    prov.intensity("n0", 0.0)      # record a last-known-good
    inj.advance(0.15, eng)
    assert prov.blackout
    inj.advance(0.25, eng)
    assert not prov.blackout


def test_straggle_fault_restores_bit_exact():
    cl = fleet(2)
    eng = engine(cl, resilience=Resilience())
    orig = cl.nodes["n1"].avg_time_ms
    inj = FaultInjector.scripted([
        Fault(0.1, "straggle", "n1", factor=3.0),
        Fault(0.2, "unstraggle", "n1")])
    inj.advance(0.1, eng)
    assert cl.nodes["n1"].avg_time_ms == orig * 3.0
    inj.advance(0.2, eng)
    assert cl.nodes["n1"].avg_time_ms == orig


# ---------------------------------------------------------------------------
# Circuit breaker state machine
# ---------------------------------------------------------------------------


def test_breaker_opens_at_threshold_and_cooldown_doubles():
    cl = fleet(3)
    res = Resilience(health=FleetHealth(breaker_threshold=2,
                                        breaker_cooldown_hours=0.1,
                                        breaker_cooldown_cap_hours=1.0))
    eng = engine(cl, resilience=res)
    cache = cl.feature_cache()
    h = res.health
    h.record_failure("n0", 0.0, cache)
    assert "n0" not in h.blocked           # below threshold
    h.record_failure("n0", 0.0, cache)
    assert "n0" in h.blocked               # threshold reached -> OPEN
    assert h.open_until["n0"] == pytest.approx(0.1)
    # cooldown expiry -> half-open (unblocked, streak survives)
    h.tick(0.11, cache)
    assert "n0" not in h.blocked
    # failure in half-open re-opens with a doubled cooldown
    h.record_failure("n0", 0.2, cache)
    assert "n0" in h.blocked
    assert h.open_until["n0"] == pytest.approx(0.2 + 0.2)
    # success in half-open closes fully
    h.tick(0.5, cache)
    h.record_success("n0", cache)
    assert "n0" not in h.blocked and "n0" not in h.consec
    assert "n0" not in h.open_until
    # cooldown is capped
    for k in range(8):
        h.record_failure("n1", 0.0, cache)
    assert h.open_until["n1"] - 0.0 <= 1.0 + 1e-12


def test_manual_mask_outlives_breaker_and_success():
    cl = fleet(2)
    res = Resilience()
    engine(cl, resilience=res)
    cache = cl.feature_cache()
    h = res.health
    h.set_manual("n0", cache)
    assert "n0" in h.blocked
    # success must NOT unmask a manually-down node (only NODE_UP does)
    h.record_success("n0", cache)
    assert "n0" in h.blocked
    h.clear_manual("n0", cache, float("-inf"))
    assert "n0" not in h.blocked


def test_availability_mask_is_absent_when_healthy_and_bumps_data_rev():
    cl = fleet(4)
    res = Resilience()
    engine(cl, resilience=res)
    cache = cl.feature_cache()
    assert cache.avail is None             # literally absent: zero overhead
    rev = cache.data_rev
    res.node_down("n2")                    # detected -> masked
    assert cache.data_rev > rev
    assert cache.avail is not None and not cache.avail[cache.index["n2"]]
    assert cache.node_ok()[cache.index["n2"]] == False  # noqa: E712
    rev = cache.data_rev
    res.node_up("n2")
    assert cache.data_rev > rev
    assert cache.avail is None             # back to the zero-cost state


def test_rebuild_preserves_mask():
    cl = fleet(4)
    res = Resilience()
    engine(cl, resilience=res)
    res.node_down("n1")
    cl.remove_node("n3")                   # topology change -> full rebuild
    cache = cl.feature_cache()
    assert cache.avail is not None
    assert not cache.avail[cache.index["n1"]]
    assert cache.fail_count is None or len(cache.fail_count) == cache.n


# ---------------------------------------------------------------------------
# Degraded-mode provider
# ---------------------------------------------------------------------------


def test_resilient_provider_healthy_is_bit_identical():
    base = StaticProvider({"a": 123.0, "b": 456.0})
    prov = ResilientProvider(base)
    names = ["a", "b"]
    assert prov.intensity("a", 0.5) == base.intensity("a", 0.5)
    np.testing.assert_array_equal(prov.intensity_batch(names, 0.5),
                                  base.intensity_batch(names, 0.5))
    lo, hi = intensity_interval_batch(prov, names, 0.5)
    blo, bhi = intensity_interval_batch(base, names, 0.5)
    np.testing.assert_array_equal(lo, blo)
    np.testing.assert_array_equal(hi, bhi)
    assert prov.covers("a") and not prov.covers("zzz")


def test_blackout_serves_last_known_good_and_widens():
    base = StaticProvider({"a": 100.0, "b": 300.0})
    prov = ResilientProvider(base, widen_g_per_hour=10.0)
    prov.intensity_batch(["a", "b"], 1.0)  # LKG recorded at hour 1
    prov.begin_blackout()
    assert prov.blackout
    np.testing.assert_array_equal(prov.intensity_batch(["a", "b"], 4.0),
                                  [100.0, 300.0])
    assert prov.intensity("b", 9.0) == 300.0
    assert prov.served_stale > 0
    # staleness-widened interval: +-(widen * hours-stale) around the LKG
    lo, hi = prov.intensity_interval_batch(["a", "b"], 4.0)
    np.testing.assert_allclose(lo, [70.0, 270.0])
    np.testing.assert_allclose(hi, [130.0, 330.0])
    lo2, hi2 = prov.intensity_interval_batch(["a", "b"], 8.0)
    assert np.all(hi2 - lo2 > hi - lo)     # widening grows with staleness
    assert np.all(np.asarray(lo2) >= 0.0)
    prov.end_blackout()
    assert not prov.blackout
    assert prov.intensity("a", 10.0) == 100.0


def test_blackout_without_lkg_raises_keyerror():
    prov = ResilientProvider(StaticProvider({"a": 100.0}))
    prov.begin_blackout()
    with pytest.raises(KeyError):
        prov.intensity("a", 0.0)
    prov.end_blackout()
    prov.intensity("a", 0.0)
    prov.begin_blackout()
    assert prov.intensity("a", 1.0) == 100.0


def test_blackouts_nest():
    prov = ResilientProvider(StaticProvider({"a": 1.0}))
    prov.begin_blackout()
    prov.begin_blackout()
    prov.end_blackout()
    assert prov.blackout
    prov.end_blackout()
    assert not prov.blackout


# ---------------------------------------------------------------------------
# Engine gate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch_execute", [True, False])
def test_zero_fault_resilience_is_bit_identical(batch_execute):
    """With resilience attached but no faults, every result and report
    matches a resilience-free engine exactly on both execute paths."""
    tasks = [Task(cpu=0.1 * (1 + i % 3), base_latency_ms=50.0 + i)
             for i in range(32)]
    ref = engine(fleet(), batch_execute=batch_execute, batch_size=8)
    ref.submit_many(list(tasks))
    wired = engine(fleet(), batch_execute=batch_execute, batch_size=8,
                   resilience=Resilience())
    wired.submit_many(list(tasks))
    while ref.queue or wired.queue:
        ra = ref.step(0.25)
        rb = wired.step(0.25)
        assert [(r.node, r.latency_ms, r.energy_kwh, r.carbon_g)
                for r in ra] == \
               [(r.node, r.latency_ms, r.energy_kwh, r.carbon_g)
                for r in rb]
    ra, rb = ref.report(), wired.report()
    assert ra["totals"] == rb["totals"]
    assert ra["outcomes"] == rb["outcomes"]


@pytest.mark.parametrize("batch_execute", [True, False])
def test_undetected_crash_fails_over(batch_execute):
    cl = fleet()
    res = Resilience()
    eng = engine(cl, resilience=res, batch_execute=batch_execute)
    eng.submit_many([Task() for _ in range(4)])
    pref = eng.step(0.0)[0].node
    res.node_down(pref, detected=False)    # scheduler doesn't know yet
    eng.submit_many([Task() for _ in range(4)])
    out = eng.step(0.1)
    assert len(out) == 4
    assert all(r.node != pref for r in out)
    # contact failure was recorded and the node masked by contact
    assert res.health.fails_total.get(pref) == 1
    assert pref in res.health.blocked
    assert all(o[0] == "done" for o in eng.last_outcomes)


def test_detected_crash_avoids_contact_entirely():
    cl = fleet()
    res = Resilience()
    eng = engine(cl, resilience=res)
    eng.submit_many([Task() for _ in range(2)])
    pref = eng.step(0.0)[0].node
    res.node_down(pref, detected=True)
    eng.submit_many([Task() for _ in range(4)])
    out = eng.step(0.1)
    assert all(r.node != pref for r in out)
    assert res.health.fails_total.get(pref) is None  # never contacted


@pytest.mark.parametrize("batch_execute", [True, False])
def test_retry_backoff_then_dead_letter(batch_execute):
    cl = fleet(2)
    res = Resilience(max_attempts=3, backoff_base_hours=0.01,
                     backoff_cap_hours=0.5)
    eng = engine(cl, resilience=res, batch_execute=batch_execute)
    res.node_down("n0")
    res.node_down("n1")
    eng.submit_many([Task() for _ in range(2)])
    assert eng.step(0.0) == []
    assert [o[0] for o in eng.last_outcomes] == ["retry", "retry"]
    wake = eng.last_outcomes[0][1]
    assert wake == pytest.approx(0.01)     # base backoff
    assert len(eng.deferred) == 2
    # second attempt: doubled backoff
    eng.submit_many(eng.pop_ripe(wake))
    assert eng.step(wake) == []
    assert eng.last_outcomes[0][1] - wake == pytest.approx(0.02)
    # third attempt == max_attempts: dead-letter
    ripe = eng.pop_ripe(1.0)
    eng.submit_many(ripe)
    assert eng.step(1.0) == []
    assert [o[0] for o in eng.last_outcomes] == ["dead", "dead"]
    assert len(eng.dead_letters) == 2
    rep = eng.report()
    assert rep["outcomes"]["dead"] == 2
    assert rep["outcomes"]["retry"] == 4
    assert rep["resilience"]["dead_letters"] == 2
    # recovery drains normally again
    res.node_up("n0")
    eng.submit_many([Task()])
    assert len(eng.step(2.0)) == 1


def test_backoff_is_capped():
    res = Resilience(backoff_base_hours=0.1, backoff_cap_hours=0.3)
    assert res.backoff_hours(1) == pytest.approx(0.1)
    assert res.backoff_hours(2) == pytest.approx(0.2)
    assert res.backoff_hours(3) == pytest.approx(0.3)
    assert res.backoff_hours(9) == pytest.approx(0.3)


def test_run_until_drains_retries_to_dead_letter():
    cl = fleet(2)
    res = Resilience(max_attempts=3, backoff_base_hours=0.01)
    eng = engine(cl, resilience=res)
    res.node_down("n0")
    res.node_down("n1")
    eng.submit_many([Task() for _ in range(3)])
    rep = eng.run_until(2.0)
    assert rep["outcomes"]["dead"] == 3
    assert not eng.deferred and not eng.queue


def test_partition_fallback_rebills_cut0():
    from repro.partition import PartitionPolicy, profile_costs
    prof = profile_costs([25.0, 25.0, 25.0, 25.0],
                         boundary_bytes=[4e6, 2e6, 1e6, 5e5, 0.0],
                         name="m")
    cl = fleet()
    pol = PartitionPolicy(prof, backend="numpy")
    res = Resilience()
    eng = engine(cl, policy=pol, resilience=res)
    task = Task(base_latency_ms=400.0)
    eng.submit_many([task])
    first = eng.step(0.0)[0]
    res.node_down(first.node, detected=False)
    eng.submit_many([Task(base_latency_ms=400.0)])
    out = eng.step(0.1)[0]
    assert out.node != first.node
    # failed-over task re-bills the whole model through the cut-0 column
    expected = pol.fallback_latency_ms(task)
    st = eng.cluster.nodes[out.node]
    lat, _ = eng.cluster.latency_energy(expected, distributed=True)
    assert out.latency_ms == pytest.approx(float(lat))


def test_tenancy_gate_failover_and_retry():
    from repro.tenancy import TenantPolicy, TenantRegistry, TenantSpec
    from repro.tenancy.spec import TenantTask
    reg = TenantRegistry([TenantSpec("t0")])
    cl = fleet()
    res = Resilience(max_attempts=2, backoff_base_hours=0.01)
    eng = engine(cl, policy=TenantPolicy(registry=reg), resilience=res)
    eng.submit_many([TenantTask(tenant="t0") for _ in range(2)])
    pref = eng.step(0.0)[0].node
    res.node_down(pref, detected=False)
    eng.submit_many([TenantTask(tenant="t0") for _ in range(2)])
    out = eng.step(0.1)
    assert len(out) == 2 and all(r.node != pref for r in out)
    assert all(o[0] == "done" for o in eng.last_outcomes)
    # all nodes down -> retries, with the admitted counting reversed
    for n in list(cl.nodes):
        res.node_down(n)
    admitted_before = int(reg.admitted[0])
    eng.submit_many([TenantTask(tenant="t0")])
    assert eng.step(0.2) == []
    assert eng.last_outcomes[0][0] == "retry"
    assert int(reg.admitted[0]) == admitted_before


# ---------------------------------------------------------------------------
# Sim driver integration
# ---------------------------------------------------------------------------


def sim_text(faults=None, *, resilient=True, seed=11):
    cl = fleet()
    prov = StaticProvider({n: cl.nodes[n].spec.carbon_intensity
                           for n in cl.nodes})
    eng = CarbonEdgeEngine(cl, provider=prov,
                           resilience=Resilience() if resilient else None)
    drv = AsyncEngineDriver(
        eng, PoissonArrivals(240.0, seed=seed),
        lambda uid, hour: Task(base_latency_ms=40.0),
        horizon_hours=0.5, max_batch=8, slo_latency_s=2.0, faults=faults)
    return drv.run().to_text()


def test_sim_zero_fault_schedule_is_byte_identical():
    plain = sim_text(None, resilient=False)
    wired = sim_text(FaultInjector.scripted([]), resilient=True)
    assert plain == wired


def test_sim_fixed_fault_seed_repeats_byte_identical():
    def inj():
        # seed 2 crashes n0 — the all-tasks-preferred node — so the fault
        # run observably diverges from the zero-fault one
        return FaultInjector.generate(
            [f"n{i}" for i in range(6)], 0.5, seed=2,
            crash_rate_per_hour=3.0, mttr_hours=0.08,
            detect_delay_hours=0.02, outage_rate_per_hour=1.0,
            outage_hours=0.1)
    a = sim_text(inj())
    b = sim_text(inj())
    assert a == b
    assert a != sim_text(None)             # the faults actually bite


def test_sim_driver_fires_fault_events():
    cl = fleet()
    res = Resilience()
    eng = CarbonEdgeEngine(cl, resilience=res)
    inj = FaultInjector.scripted([
        Fault(0.05, "crash", "n0", detected=False),
        Fault(0.07, "detect", "n0"),
        Fault(0.3, "recover", "n0")])
    drv = AsyncEngineDriver(
        eng, PoissonArrivals(100.0, seed=1),
        lambda uid, hour: Task(base_latency_ms=40.0),
        horizon_hours=0.5, max_batch=4, faults=inj)
    m = drv.run()
    assert len(m.records) > 0
    assert not res.down                    # recovered by the end
    assert all(r.node != "n0" or not (0.07 <= r.start_hour < 0.3)
               for r in m.records)
