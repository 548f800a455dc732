"""Joint partition+placement subsystem (repro.partition, DESIGN.md §8).

Covers: cut-profile column semantics, the (B, P, N) joint selection's
bit-exact parity with the cut-major scalar oracle (numpy column path) and
its agreement with the fused Pallas reduction — including constructed
exact ties, which must resolve to the lowest flattened (p, n) on every
path — the FeatureCache partition block's recompute-on-data_rev-only
contract, engine integration (effective-latency billing of the offloaded
segment, batched-vs-scalar execute parity), split-conformal calibration
(finite-sample quantile, held-out coverage >= nominal - 3%), provider
interval dispatch, and the risk-bounded deferral invariants: the temporal
planner never defers when the interval lower bound loses to executing
now, and the tenancy gate downgrades DEFER to REJECT only when the wake
window certainly loses.
"""
import numpy as np
import pytest

from repro.core.api import CarbonEdgeEngine
from repro.core.cluster import EdgeCluster, NodeSpec, PAPER_NODES
from repro.core.policy import VectorizedPolicy, get_cache
from repro.core.providers import (ForecastProvider, StaticProvider,
                                  TraceProvider, intensity_interval_batch)
from repro.core.scheduler import MODES, Task
from repro.core.temporal import (DeferrableTask, plan_wake_batch,
                                 plan_wake_risk, plan_wake_risk_batch,
                                 synthetic_trace)
from repro.partition import (ConformalProvider, CutProfile, JointDecision,
                             PartitionPolicy, SplitConformal,
                             calibrate_intensity, calibrate_latency,
                             profile_cnn, profile_costs, select_joint_scalar)
from repro.tenancy import (ADMIT, DEFER, REJECT, TenantPolicy,
                           TenantRegistry, TenantSpec, TenantTask)

GREEN = MODES["green"]


def random_cluster(rng, n):
    nodes = [NodeSpec(f"n{i}", float(rng.uniform(0.1, 4.0)),
                      int(rng.integers(64, 2048)),
                      float(rng.uniform(10.0, 1200.0)))
             for i in range(n)]
    c = EdgeCluster(nodes=nodes, host_power_w=142.0)
    c.profile(float(rng.uniform(50.0, 1000.0)))
    for st_ in c.nodes.values():
        st_.load = float(rng.uniform(0.0, 0.9))
        st_.mem_used_mb = float(rng.uniform(0.0, st_.spec.mem_mb * 0.5))
        st_.running = int(rng.integers(0, 4))
    return c


def random_task(rng):
    return Task(cpu=float(rng.uniform(0.01, 1.0)),
                mem_mb=float(rng.uniform(4.0, 256.0)),
                base_latency_ms=float(rng.uniform(50.0, 500.0)))


def random_profile(rng, L=6):
    costs = rng.uniform(1.0, 50.0, L)
    bb = np.append(rng.uniform(1e4, 1e7, L - 1), 0.0)
    return profile_costs(costs, boundary_bytes=bb, name="rand")


# ---------------------------------------------------------------------------
# cut profiles
# ---------------------------------------------------------------------------


def test_profile_columns():
    prof = profile_costs([10.0, 20.0, 30.0, 40.0],
                         boundary_bytes=[100.0, 200.0, 300.0, 0.0])
    assert prof.cuts == (0, 1, 2, 3)
    # cut 0 = full offload: everything remote, no boundary to ship
    rf = prof.remote_frac()
    assert rf[0] == 1.0
    np.testing.assert_allclose(rf, [1.0, 0.9, 0.7, 0.4])
    # comm bytes: the activation crossing boundary c (bb[0] = the model
    # input a full offload ships)
    np.testing.assert_allclose(prof.comm_seconds(100.0),
                               np.array([100.0, 200.0, 300.0, 0.0])
                               / (100.0 * 125000.0))


def test_profile_thinning_keeps_cut_zero():
    L = 100
    rng = np.random.default_rng(0)
    prof = profile_costs(rng.uniform(1, 10, L),
                         boundary_bytes=np.append(
                             rng.uniform(1e5, 1e8, L - 1), 0.0),
                         max_cuts=8)
    assert prof.num_cuts == 8
    assert prof.cuts[0] == 0                       # full offload always kept
    assert list(prof.cuts) == sorted(prof.cuts)    # ascending layer order


def test_profile_cnn_real_model():
    from repro.configs.cnn_zoo import get_cnn_config
    prof = profile_cnn(get_cnn_config("mobilenetv2"))
    assert prof.num_cuts >= 2
    rf = prof.remote_frac()
    # monotone non-increasing (zero-cost layers step flat), and a late
    # cut genuinely keeps most compute local
    assert rf[0] == 1.0 and np.all(np.diff(rf) <= 0) and rf[-1] < 0.5
    assert prof.name == "mobilenetv2"


def test_profile_hashable_for_cache_keys():
    p1 = profile_costs([1.0, 2.0], boundary_bytes=[10.0, 0.0])
    p2 = profile_costs([1.0, 2.0], boundary_bytes=[10.0, 0.0])
    assert hash(p1) == hash(p2) and p1 == p2


# ---------------------------------------------------------------------------
# joint selection parity: scalar oracle vs numpy columns vs Pallas
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("mode", ["green", "balanced", "performance"])
def test_numpy_matches_scalar_oracle_bit_exact(seed, mode):
    rng = np.random.default_rng(seed)
    c = random_cluster(rng, int(rng.integers(3, 40)))
    prof = random_profile(rng)
    prov = StaticProvider.from_cluster(c)
    pol = PartitionPolicy(prof, backend="numpy")
    tasks = [random_task(rng) for _ in range(5)]
    got = pol.decide_batch(c, tasks, MODES[mode], provider=prov)
    for t, d in zip(tasks, got):
        ref = select_joint_scalar(c, t, prof, MODES[mode], provider=prov)
        if ref is None:
            assert d is None
            continue
        assert (d.node, d.cut, d.cut_index) == (ref.node, ref.cut,
                                                ref.cut_index)
        assert d.score == ref.score               # bit-exact, not approx


def test_exact_ties_resolve_to_lowest_p_n():
    # identical nodes x identical cuts -> a (P, N) plane of exact ties;
    # every path must pick flattened argmax position (0, 0)
    nodes = [NodeSpec(f"n{i}", 1.0, 512, 300.0) for i in range(4)]
    c = EdgeCluster(nodes=nodes)
    c.profile(250.0)
    prof = CutProfile("tie", total_cost=100.0, cuts=(0, 1, 2),
                      local_cost=(0.0, 0.0, 0.0),
                      remote_cost=(100.0, 100.0, 100.0),
                      comm_bytes=(0.0, 0.0, 0.0))
    t = Task(cpu=0.1, mem_mb=16.0)
    ref = select_joint_scalar(c, t, prof, GREEN,
                              provider=StaticProvider.from_cluster(c))
    assert (ref.cut_index, ref.node) == (0, "n0")
    for backend in ("numpy", "pallas"):
        d = PartitionPolicy(prof, backend=backend).decide(
            c, t, GREEN, provider=StaticProvider.from_cluster(c))
        assert (d.cut_index, d.node) == (0, "n0"), backend


@pytest.mark.parametrize("seed", range(4))
def test_pallas_interpret_matches_numpy(seed):
    rng = np.random.default_rng(100 + seed)
    c = random_cluster(rng, int(rng.integers(3, 20)))
    prof = random_profile(rng, L=5)
    prov = StaticProvider.from_cluster(c)
    tasks = [random_task(rng) for _ in range(4)]
    dn = PartitionPolicy(prof, backend="numpy").decide_batch(
        c, tasks, GREEN, provider=prov)
    dp = PartitionPolicy(prof, backend="pallas").decide_batch(
        c, tasks, GREEN, provider=prov)
    for a, b in zip(dn, dp):
        if a is None:
            assert b is None
            continue
        # float32 kernel vs float64 columns: argmax agreement is only
        # guaranteed outside ulp-scale score gaps — compare decisions and
        # bound the score drift instead of requiring bit-equality
        if abs(a.score - b.score) > 1e-5:
            assert (a.node, a.cut) == (b.node, b.cut)
        assert b.score == pytest.approx(a.score, rel=1e-5)


def test_infeasible_everywhere_returns_none():
    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)
    prof = profile_costs([5.0, 5.0], boundary_bytes=[100.0, 0.0])
    huge = Task(cpu=1e9, mem_mb=1e9)
    for backend in ("numpy", "pallas"):
        pol = PartitionPolicy(prof, backend=backend)
        assert pol.decide(c, huge, GREEN) is None
        assert pol.select(c, huge, GREEN) is None


def test_green_mode_prefers_smaller_remote_on_dirty_grid():
    # one node, dirty grid: green weights should shift the cut toward a
    # smaller offloaded share than performance weights do (less remote
    # energy to multiply with the high intensity)
    c = EdgeCluster(nodes=[NodeSpec("n0", 1.0, 512, 900.0)])
    c.profile(400.0)
    L = 8
    costs = np.full(L, 10.0)
    bb = np.append(np.full(L - 1, 1e4), 0.0)     # cheap uplink
    prof = profile_costs(costs, boundary_bytes=bb)
    prov = StaticProvider.from_cluster(c)
    d_perf = PartitionPolicy(prof, backend="numpy").decide(
        c, Task(), MODES["performance"], provider=prov)
    d_green = PartitionPolicy(prof, backend="numpy").decide(
        c, Task(), GREEN, provider=prov)
    assert d_green.remote_frac <= d_perf.remote_frac


# ---------------------------------------------------------------------------
# feature-cache partition block
# ---------------------------------------------------------------------------


def test_partition_block_caches_on_data_rev():
    rng = np.random.default_rng(7)
    c = random_cluster(rng, 12)
    prof = random_profile(rng)
    pol = PartitionPolicy(prof, backend="numpy", use_select_memo=False)
    prov = StaticProvider.from_cluster(c)
    t = random_task(rng)
    pol.decide(c, t, GREEN, provider=prov)
    cache = get_cache(c)
    blk1 = cache._part_blocks[pol._block_key]
    pol.decide(c, t, GREEN, provider=prov)
    assert cache._part_blocks[pol._block_key] is blk1   # no recompute
    # node mutation bumps data_rev -> block recomputed with fresh times
    c.nodes["n0"].avg_time_ms *= 2.0
    pol.decide(c, t, GREEN, provider=prov)
    blk2 = cache._part_blocks[pol._block_key]
    assert blk2 is not blk1 and blk2[0] > blk1[0]


def test_partition_block_matches_joint_time_energy():
    from repro.partition.policy import joint_time_energy
    rng = np.random.default_rng(11)
    c = random_cluster(rng, 6)
    prof = random_profile(rng)
    pol = PartitionPolicy(prof, backend="numpy")
    pol.decide(c, random_task(rng), GREEN,
               provider=StaticProvider.from_cluster(c))
    cache = get_cache(c)
    t_pn, e_pn = cache.partition_block(pol._block_key, pol._rf, pol._cs)
    rf, cs = prof.remote_frac(), prof.comm_seconds(pol.link_mbps)
    for p in range(prof.num_cuts):
        for j, name in enumerate(cache.names):
            st_ = c.nodes[name]
            t_ref, e_ref = joint_time_energy(
                st_.avg_time_ms / 1000.0, st_.power_w(c.host_power_w),
                rf[p], cs[p])
            assert t_pn[p, j] == t_ref and e_pn[p, j] == e_ref


# ---------------------------------------------------------------------------
# engine integration: effective latency, execute-path parity
# ---------------------------------------------------------------------------


def _engine_pair(prof):
    def mk(batch_execute):
        c = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
        c.profile(254.85)
        return CarbonEdgeEngine(c, mode="green",
                                policy=PartitionPolicy(prof,
                                                       backend="numpy"),
                                batch_execute=batch_execute)
    return mk(True), mk(False)


def test_engine_bills_offloaded_segment_only():
    prof = profile_costs([10.0, 10.0, 10.0, 10.0],
                         boundary_bytes=[1e4, 1e4, 1e4, 0.0])
    eng, _ = _engine_pair(prof)
    t = Task(cpu=0.05, mem_mb=16.0, base_latency_ms=400.0)
    eng.submit(t)
    res = eng.step(now_hour=0.0)[0]
    d = eng.policy.last_decisions[0]
    assert d is not None and d.remote_frac < 1.0
    eff = d.effective_latency_ms(t.base_latency_ms)
    assert eff < t.base_latency_ms
    # the cluster measured the *effective* base, not the full one
    expect = eng.cluster.measured_latency_ms(eff, True)
    assert res.latency_ms == pytest.approx(expect)


def test_engine_execute_paths_bit_identical_with_partition_policy():
    prof = profile_costs([10.0, 20.0, 15.0, 30.0],
                         boundary_bytes=[2e4, 5e4, 1e4, 0.0])
    eng_b, eng_s = _engine_pair(prof)
    rng = np.random.default_rng(3)
    tasks = [random_task(rng) for _ in range(16)]
    for t in tasks:
        eng_b.submit(t)
        eng_s.submit(t)
    rb = eng_b.step(now_hour=0.0)
    rs = eng_s.step(now_hour=0.0)
    assert len(rb) == len(rs) == len(tasks)
    for a, b in zip(rb, rs):
        assert (a.node, a.latency_ms, a.energy_kwh, a.carbon_g) == \
            (b.node, b.latency_ms, b.energy_kwh, b.carbon_g)


def test_execution_latency_hook_shape_guard():
    prof = profile_costs([10.0, 10.0], boundary_bytes=[1e4, 0.0])
    pol = PartitionPolicy(prof, backend="numpy")
    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)
    t = Task(cpu=0.05, mem_mb=16.0)
    pol.select_batch(c, [t], GREEN)
    assert pol.execution_latency_ms([t]) is not None
    assert pol.execution_latency_ms([t, t]) is None    # re-grouped batch


# ---------------------------------------------------------------------------
# split-conformal calibration
# ---------------------------------------------------------------------------


def test_split_conformal_quantile_small_cases():
    sc = SplitConformal([1.0, -2.0, 3.0])
    # n=3: k = ceil(4 * 0.5) = 2 -> 2nd smallest |residual|
    assert sc.quantile(0.5) == 2.0
    # k = ceil(4 * 0.9) = 4 > n -> cannot certify
    assert sc.quantile(0.9) == float("inf")
    with pytest.raises(ValueError):
        sc.quantile(1.0)
    with pytest.raises(ValueError):
        SplitConformal([])


def test_split_conformal_heldout_coverage():
    rng = np.random.default_rng(42)
    noise = lambda n: rng.standard_t(df=5, size=n) * 3.0   # noqa: E731
    cal = SplitConformal(noise(500))
    q = cal.quantile(0.9)
    assert np.isfinite(q)
    held = noise(4000)
    coverage = float(np.mean(np.abs(held) <= q))
    assert coverage >= 0.87          # nominal 0.9, 3% finite-sample slack


def test_calibrate_intensity_coverage_on_traces():
    traces = {n.name: synthetic_trace(n.region, n.carbon_intensity,
                                      noise=0.08, seed=i)
              for i, n in enumerate(PAPER_NODES)}
    actual = TraceProvider(traces)
    smooth = {n.name: synthetic_trace(n.region, n.carbon_intensity)
              for n in PAPER_NODES}
    forecast = ForecastProvider(TraceProvider(smooth), smoothing_hours=2.0)
    names = list(traces)
    cal_hours = np.arange(0.0, 24.0, 0.25)          # calibration window
    sc = calibrate_intensity(forecast, actual, names, cal_hours)
    test_hours = np.arange(0.125, 24.0, 0.25)       # held-out offsets
    prov = ConformalProvider(forecast, sc)
    lo, hi = prov.intensity_interval_batch(names, test_hours)
    truth = actual.intensity_batch(names, test_hours)
    coverage = float(np.mean((truth >= lo) & (truth <= hi)))
    assert coverage >= 0.87
    assert np.all(lo >= 0.0)                        # clipped at zero


def test_calibrate_latency_bounds_residuals():
    rng = np.random.default_rng(5)
    pred = rng.uniform(50, 500, 200)
    meas = pred * 1.065 + rng.normal(0, 5.0, 200)
    sc = calibrate_latency(pred, meas)
    lo, hi = sc.interval(100.0, coverage=0.9)
    assert lo < 100.0 < hi
    with pytest.raises(ValueError):
        calibrate_latency([1.0], [1.0, 2.0])


# ---------------------------------------------------------------------------
# provider interval dispatch
# ---------------------------------------------------------------------------


def test_measured_providers_answer_zero_width():
    c = EdgeCluster(nodes=PAPER_NODES)
    names = list(c.nodes)
    sp = StaticProvider.from_cluster(c)
    lo, hi = intensity_interval_batch(sp, names, 3.0)
    np.testing.assert_array_equal(lo, hi)
    traces = {n: synthetic_trace(n, 400.0) for n in names}
    lo, hi = intensity_interval_batch(TraceProvider(traces), names,
                                      np.array([0.0, 6.0]))
    np.testing.assert_array_equal(lo, hi)
    assert lo.shape == (2, 3)


def test_unknown_provider_degrades_to_point_interval():
    class Bare:
        def intensity(self, node, hour=0.0):
            return 123.0
    lo, hi = intensity_interval_batch(Bare(), ["a", "b"], 0.0)
    np.testing.assert_array_equal(lo, [123.0, 123.0])
    np.testing.assert_array_equal(lo, hi)


def test_forecast_provider_conformal_band():
    sp = StaticProvider({"a": 100.0, "b": 200.0})
    fp = ForecastProvider(sp, conformal=SplitConformal(
        np.linspace(-30, 30, 99)))
    q = fp.conformal.quantile(0.9)
    lo, hi = fp.intensity_interval_batch(["a", "b"], 0.0)
    np.testing.assert_allclose(hi - lo, 2 * q)
    assert np.all(lo >= 0.0)


# ---------------------------------------------------------------------------
# risk-bounded deferral: temporal planner
# ---------------------------------------------------------------------------


def _risk_fixture(q, seed=0):
    traces = {n.name: synthetic_trace(n.region, n.carbon_intensity,
                                      solar_dip=0.5, seed=seed)
              for n in PAPER_NODES}
    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)
    base = TraceProvider(traces)
    prov = ConformalProvider(base, SplitConformal([q]))  # q certifies at 0.5
    return c, prov


def test_risk_plan_zero_width_defers_into_dip():
    # zero-width interval: risk planning should agree with the point
    # planner's "defer only on strict improvement" into the solar dip
    c, prov = _risk_fixture(0.0)
    t = DeferrableTask(cpu=0.05, mem_mb=16.0, deadline_hours=20.0,
                       duration_hours=0.5)
    wake = plan_wake_risk(prov, c, t, 20.0, coverage=0.5)
    assert wake > 20.0
    point = plan_wake_batch(prov, c, [t], 20.0)[0]
    assert wake == point


def test_risk_plan_wide_interval_never_defers():
    # an interval wider than the whole diurnal swing: no future slot's
    # upper bound can undercut now's lower bound -> execute immediately
    c, prov = _risk_fixture(1e4)
    t = DeferrableTask(cpu=0.05, mem_mb=16.0, deadline_hours=20.0,
                       duration_hours=0.5)
    assert plan_wake_risk(prov, c, t, 20.0, coverage=0.5) == 20.0


@pytest.mark.parametrize("seed", range(5))
def test_risk_plan_acceptance_invariant(seed):
    """A deferral's interval upper bound must strictly beat the best
    slot-0 lower bound — 'never defer when the lower bound loses to
    executing now', verified against raw provider reads."""
    rng = np.random.default_rng(seed)
    c, prov = _risk_fixture(float(rng.uniform(0.0, 200.0)), seed=seed)
    tasks = [DeferrableTask(cpu=0.05, mem_mb=16.0,
                            deadline_hours=float(rng.uniform(0.0, 22.0)),
                            duration_hours=0.5) for _ in range(12)]
    now = float(rng.uniform(0.0, 24.0))
    slot = 0.5
    wakes = plan_wake_risk_batch(prov, c, tasks, now, slot_hours=slot,
                                 coverage=0.5)
    names = list(c.nodes)
    for t, w in zip(tasks, wakes):
        if w == now:
            continue
        lo0, _ = intensity_interval_batch(prov, names, now, coverage=0.5)
        _, hi_w = intensity_interval_batch(prov, names, float(w),
                                           coverage=0.5)
        assert float(np.min(hi_w)) < float(np.min(lo0))
        assert w <= now + t.deadline_hours + 1e-9


# ---------------------------------------------------------------------------
# risk-bounded deferral: tenancy admission gate
# ---------------------------------------------------------------------------


def _broke_tenant_policy(coverage):
    # period budget would cover the task, but it's spent: budget DEFER
    reg = TenantRegistry([TenantSpec("a", allowance_g=1.0,
                                     period_hours=2.0)])
    reg.spent_g[0] = 1.0
    return TenantPolicy(registry=reg, defer_risk_coverage=coverage), reg


def test_tenancy_gate_keeps_defer_on_zero_width():
    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)
    pol, _ = _broke_tenant_policy(0.5)
    plan = pol.plan(c, [TenantTask(cpu=0.05, mem_mb=16.0, tenant="a")],
                    provider=StaticProvider.from_cluster(c), now_hour=0.0)
    assert plan.actions.tolist() == [DEFER]   # static: wake == now forever


def test_tenancy_gate_rejects_certainly_worse_wake():
    # intensity certainly rises by the wake hour (narrow interval around a
    # steeply climbing trace): deferral burns deadline for worse carbon
    c = EdgeCluster(nodes=PAPER_NODES)
    c.profile(250.0)

    class Climb:
        def intensity(self, node, hour=0.0):
            return 100.0 + 400.0 * hour

        def intensity_interval_batch(self, names, hours, coverage=0.9):
            h = np.asarray(hours, dtype=float)
            v = 100.0 + 400.0 * h
            grid = (np.broadcast_to(v[..., None],
                                    h.shape + (len(names),)).astype(float)
                    if h.ndim else np.full(len(names), float(v)))
            return grid - 5.0, grid + 5.0

    pol, reg = _broke_tenant_policy(0.9)
    plan = pol.plan(c, [TenantTask(cpu=0.05, mem_mb=16.0, tenant="a")],
                    provider=Climb(), now_hour=0.0)
    # wake = 2.0 -> lo_wake = 895 > hi_now = 105: downgraded
    assert plan.actions.tolist() == [REJECT]
    assert reg.rejected[0] == 1 and reg.deferred[0] == 0
    # gate off: plain budget DEFER
    pol2, _ = _broke_tenant_policy(None)
    plan2 = pol2.plan(c, [TenantTask(cpu=0.05, mem_mb=16.0, tenant="a")],
                      provider=Climb(), now_hour=0.0)
    assert plan2.actions.tolist() == [DEFER]


def test_tenancy_gate_validates_coverage():
    with pytest.raises(ValueError):
        TenantPolicy(defer_risk_coverage=1.5)
