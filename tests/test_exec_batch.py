"""Batched execution & billing parity (DESIGN.md §6).

``CarbonEdgeEngine(batch_execute=True)`` — the default — must be
bit-identical to the per-task execute+bill loop (``batch_execute=False``)
across: cluster node ledgers, the TaskResult log, monitor region accounts,
returned results, requeue state, and mid-batch failures (infeasible node,
provider KeyError, unknown node from a custom policy). The scalar loop is
the oracle, the same pattern as ``featurize`` vs ``featurize_cached``.

Also covers the batched primitives directly (``EdgeCluster.execute_batch``,
``CarbonMonitor.record_energy_batch``/``billing_intensity_batch``,
``energy.ledger_add`` sequential-fold bit-exactness, array-valued energy
helpers), the profile-level selection memo's invalidation contract, and a
sim-driver byte-identity check (``metrics.to_text``) across both paths.
A hypothesis fuzz (optional dep) drives randomized traffic with injected
failures through both engines.
"""
import numpy as np
import pytest

from repro.core import energy
from repro.core.api import CarbonEdgeEngine, NoFeasibleNodeError
from repro.core.carbon import CarbonMonitor
from repro.core.cluster import EdgeCluster, NodeSpec, PAPER_NODES
from repro.core.policy import VectorizedPolicy
from repro.core.providers import StaticProvider, TraceProvider
from repro.core.scheduler import MODES, Task
from repro.core.temporal import synthetic_trace


def fresh_cluster():
    c = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
    c.profile(250.0)
    return c


def mixed_tasks(n=40, seed=0):
    rng = np.random.default_rng(seed)
    return [Task(cpu=float(rng.uniform(0.0, 0.3)),
                 mem_mb=float(rng.uniform(0.0, 128.0)),
                 base_latency_ms=float(rng.uniform(50.0, 400.0)))
            for _ in range(n)]


def full_state(eng):
    """Every observable the parity contract covers, in comparable form."""
    cl = eng.cluster
    return {
        "nodes": [(n, s.completed, s.total_time_ms, s.energy_kwh,
                   s.carbon_g, s.running, s.load, s.mem_used_mb)
                  for n, s in cl.nodes.items()],
        "log": list(cl.log),
        "totals": cl.totals(),
        "regions": {r: (a.energy_kwh, a.carbon_g, a.tasks, a.pinned)
                    for r, a in eng.monitor.regions.items()},
        "queue": list(eng.queue),
    }


def engine_pair(provider=None, policy=None, mode="green", **kw):
    def mk(batch_execute):
        return CarbonEdgeEngine(fresh_cluster(), mode=mode,
                                provider=provider, policy=policy,
                                batch_execute=batch_execute, **kw)
    return mk(False), mk(True)


class RoundRobinPolicy:
    """Provider-blind stub: selection never touches the provider, so
    execute-path resolution is the first place a bad provider can fail."""

    name = "round-robin"

    def __init__(self, names):
        self.names = list(names)

    def select_batch(self, cluster, tasks, weights, provider=None,
                     now_hour=0.0):
        return [self.names[i % len(self.names)] for i in range(len(tasks))]

    def select(self, cluster, task, weights, provider=None, now_hour=0.0):
        return self.names[0]


class LateFailProvider:
    """Covers every node at registration (hour 0) but loses ``fail_node``
    for later hours — triggers the execute-path KeyError mid-batch."""

    def __init__(self, fail_node="node-green", after_hour=0.5):
        self.table = {n.name: n.carbon_intensity for n in PAPER_NODES}
        self.fail_node = fail_node
        self.after_hour = after_hour

    def intensity(self, node, hour=0.0):
        if node == self.fail_node and hour > self.after_hour:
            raise KeyError(f"no carbon intensity registered for {node!r}")
        return self.table[node]


# ---------------------------------------------------------------------------
# engine.step parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["green", "performance", "balanced"])
def test_step_parity_happy_path(mode):
    a, b = engine_pair(mode=mode)
    tasks = mixed_tasks()
    ra = a.submit_many(tasks).step(now_hour=3.0)
    rb = b.submit_many(tasks).step(now_hour=3.0)
    assert ra == rb
    assert full_state(a) == full_state(b)


def test_step_parity_multi_step_batches():
    a, b = engine_pair(batch_size=7)
    tasks = mixed_tasks(25, seed=1)
    a.submit_many(tasks)
    b.submit_many(tasks)
    while a.queue:
        assert a.step(2.0) == b.step(2.0)
        assert full_state(a) == full_state(b)
    assert not b.queue


def test_step_parity_trace_provider_run_until():
    def mk(batch_execute):
        c = fresh_cluster()
        prov = TraceProvider({n: synthetic_trace(n, st.spec.carbon_intensity,
                                                 seed=i)
                              for i, (n, st) in enumerate(c.nodes.items())})
        return CarbonEdgeEngine(c, provider=prov, batch_size=6,
                                batch_execute=batch_execute)
    a, b = mk(False), mk(True)
    tasks = [Task(cpu=0.05, mem_mb=8.0, base_latency_ms=900.0)
             for _ in range(30)]
    ra = a.submit_many(tasks).run_until(5.0)
    rb = b.submit_many(tasks).run_until(5.0)
    assert ra == rb
    assert full_state(a) == full_state(b)


def test_step_parity_infeasible_mid_batch():
    tasks = mixed_tasks(10, seed=2) + [Task(cpu=99.0, base_latency_ms=5.0)] \
        + mixed_tasks(5, seed=3)
    a, b = engine_pair()
    excs = []
    for eng in (a, b):
        with pytest.raises(NoFeasibleNodeError) as ei:
            eng.submit_many(tasks).step()
        excs.append(ei.value)
    assert excs[0].executed == excs[1].executed
    assert len(excs[0].executed) == 10
    assert full_state(a) == full_state(b)
    # the infeasible task and the tail are back at the queue head
    assert a.queue == tasks[10:]


def test_step_parity_provider_keyerror_mid_batch():
    tasks = [Task(cpu=0.01, mem_mb=1.0, base_latency_ms=100.0 + 7 * i)
             for i in range(9)]
    def mk(batch_execute):
        c = fresh_cluster()
        return CarbonEdgeEngine(c, policy=RoundRobinPolicy(c.nodes),
                                provider=LateFailProvider(),
                                batch_execute=batch_execute)
    a, b = mk(False), mk(True)
    excs = []
    for eng in (a, b):
        with pytest.raises(KeyError) as ei:
            eng.submit_many(tasks).step(now_hour=2.0)
        excs.append(ei.value)
    assert str(excs[0]) == str(excs[1])
    assert full_state(a) == full_state(b)
    # round-robin: node-green is task index 2, so exactly 2 executed
    assert len(a.cluster.log) == 2 and len(a.queue) == 7


def test_step_parity_unknown_node_from_custom_policy():
    tasks = mixed_tasks(6, seed=4)
    def mk(batch_execute):
        c = fresh_cluster()
        names = list(c.nodes)[:2] + ["ghost-node"]
        return CarbonEdgeEngine(c, policy=RoundRobinPolicy(names),
                                batch_execute=batch_execute)
    a, b = mk(False), mk(True)
    for eng in (a, b):
        with pytest.raises(KeyError):
            eng.submit_many(tasks).step()
    assert full_state(a) == full_state(b)
    assert len(a.cluster.log) == 2          # ghost-node is task index 2


def test_step_batched_requeues_everything_on_first_task_failure():
    a, b = engine_pair()
    bad = [Task(cpu=99.0, base_latency_ms=5.0)] + mixed_tasks(4, seed=5)
    for eng in (a, b):
        with pytest.raises(NoFeasibleNodeError) as ei:
            eng.submit_many(bad).step()
        assert ei.value.executed == []
    assert full_state(a) == full_state(b)
    assert a.queue == bad and b.queue == bad


# ---------------------------------------------------------------------------
# batched primitives vs their scalar oracles
# ---------------------------------------------------------------------------


def test_execute_batch_matches_sequential_execute():
    ca, cb = fresh_cluster(), fresh_cluster()
    rng = np.random.default_rng(7)
    names = list(ca.nodes)
    chosen = [names[i] for i in rng.integers(0, len(names), 32)]
    lats = rng.uniform(10.0, 500.0, 32)
    ints = rng.uniform(100.0, 900.0, 32)
    res_a = [ca.execute(n, float(lo), intensity=float(io))
             for n, lo, io in zip(chosen, lats, ints)]
    res_b = cb.execute_batch(chosen, lats, intensities=ints)
    assert res_a == res_b
    for n in names:
        sa, sb = ca.nodes[n], cb.nodes[n]
        assert (sa.completed, sa.total_time_ms, sa.energy_kwh, sa.carbon_g) \
            == (sb.completed, sb.total_time_ms, sb.energy_kwh, sb.carbon_g)
    assert ca.log == cb.log


def test_execute_batch_default_intensity_and_non_distributed():
    ca, cb = fresh_cluster(), fresh_cluster()
    chosen = ["node-high", "node-green", "node-high"]
    res_a = [ca.execute(n, 100.0, distributed=False) for n in chosen]
    res_b = cb.execute_batch(chosen, 100.0, distributed=False)
    assert res_a == res_b


def test_execute_batch_atomic_on_unknown_node():
    c = fresh_cluster()
    with pytest.raises(KeyError):
        c.execute_batch(["node-high", "ghost"], 100.0)
    assert not c.log
    assert all(st.completed == 0 and st.energy_kwh == 0.0
               for st in c.nodes.values())


def test_execute_batch_empty():
    assert fresh_cluster().execute_batch([], 100.0) == []


def monitor_pair(provider=None):
    def mk():
        m = CarbonMonitor(provider=provider)
        m.register_region("r-a", 600.0)             # pinned
        if provider is None:
            m.register_region("r-b", 300.0)
            m.register_region("r-c", 450.0)
        else:
            m.register_region("r-b")                # provider-driven
            m.register_region("r-c")
        return m
    return mk(), mk()


def test_record_energy_batch_matches_scalar():
    prov = StaticProvider({"r-b": 333.0, "r-c": 444.0}, default=500.0)
    ma, mb = monitor_pair(provider=prov)
    rng = np.random.default_rng(11)
    regions = [("r-a", "r-b", "r-c")[i] for i in rng.integers(0, 3, 24)]
    es = rng.uniform(1e-6, 1e-3, 24)
    ca = np.array([ma.record_energy(r, float(e), hour=4.0)
                   for r, e in zip(regions, es)])
    cb = mb.record_energy_batch(regions, es, hour=4.0)
    np.testing.assert_array_equal(ca, cb)
    for r in ("r-a", "r-b", "r-c"):
        aa, ab = ma.regions[r], mb.regions[r]
        assert (aa.energy_kwh, aa.carbon_g, aa.tasks) \
            == (ab.energy_kwh, ab.carbon_g, ab.tasks)


def test_record_energy_batch_unregistered_region_is_atomic():
    ma, _ = monitor_pair()
    with pytest.raises(KeyError):
        ma.record_energy_batch(["r-a", "nowhere"], 1e-4)
    assert ma.regions["r-a"].tasks == 0


def test_billing_intensity_batch_matches_scalar_probe():
    prov = StaticProvider({"r-b": 333.0, "r-c": 444.0}, default=500.0)
    m, _ = monitor_pair(provider=prov)
    regions = ["r-c", "r-a", "r-b"]
    batch = m.billing_intensity_batch(regions, hour=2.0)
    scalar = [m.billing_intensity(r, hour=2.0) for r in regions]
    np.testing.assert_array_equal(batch, scalar)
    assert batch[1] == 600.0                        # pinned wins


def test_ledger_add_is_sequential_fold():
    rng = np.random.default_rng(3)
    for _ in range(100):
        start = float(rng.uniform(0.0, 10.0))
        vals = rng.uniform(0.0, 1e-3, int(rng.integers(0, 40)))
        acc = start
        for v in vals:
            acc = acc + float(v)
        assert energy.ledger_add(start, vals) == acc


def test_energy_helpers_are_array_valued():
    lat = np.array([10.0, 250.0, 999.0])
    e = energy.task_energy_kwh(142.0, lat)
    np.testing.assert_array_equal(
        e, [energy.task_energy_kwh(142.0, float(x)) for x in lat])
    c = energy.carbon_g(e, np.array([600.0, 500.0, 400.0]), 1.1)
    np.testing.assert_array_equal(
        c, [energy.carbon_g(float(ei), ii, 1.1)
            for ei, ii in zip(e, (600.0, 500.0, 400.0))])
    terms = energy.RooflineTerms(np.array([1.0, 5.0]), np.array([2.0, 1.0]),
                                 np.array([3.0, 0.5]))
    np.testing.assert_array_equal(terms.step_time_s, [3.0, 5.0])
    np.testing.assert_array_equal(
        energy.step_energy_kwh(terms, 4),
        [energy.step_energy_kwh(energy.RooflineTerms(1.0, 2.0, 3.0), 4),
         energy.step_energy_kwh(energy.RooflineTerms(5.0, 1.0, 0.5), 4)])


# ---------------------------------------------------------------------------
# selection memo invalidation contract
# ---------------------------------------------------------------------------


def test_selection_memo_invalidates_on_feature_change():
    c = fresh_cluster()
    pol = VectorizedPolicy(backend="numpy")
    w = MODES["green"]
    t = Task(cpu=0.05, mem_mb=16.0, base_latency_ms=250.0)
    first = pol.select_batch(c, [t], w)
    # ledger-style churn does not move features: memo stays, answer stays
    c.nodes[first[0]].running += 1
    c.nodes[first[0]].running -= 1
    assert pol.select_batch(c, [t], w) == first
    # a real feature change must re-score: overload the chosen node
    c.nodes[first[0]].load = 0.99
    fresh = VectorizedPolicy(backend="numpy", use_select_memo=False)
    assert pol.select_batch(c, [t], w) == fresh.select_batch(c, [t], w)
    assert pol.select_batch(c, [t], w)[0] != first[0]


def test_selection_memo_epoch_tracks_provider_and_hour():
    c = fresh_cluster()
    pol = VectorizedPolicy(backend="numpy")
    memo_off = VectorizedPolicy(backend="numpy", use_select_memo=False)
    w = MODES["green"]
    t = Task(cpu=0.05, mem_mb=16.0, base_latency_ms=250.0)
    traces = {n: synthetic_trace(n, st.spec.carbon_intensity, seed=i,
                                 solar_dip=0.1 + 0.25 * i)
              for i, (n, st) in enumerate(c.nodes.items())}
    prov = TraceProvider(traces)
    for hour in (0.0, 6.5, 12.0, 6.5):
        assert pol.select_batch(c, [t], w, provider=prov, now_hour=hour) \
            == memo_off.select_batch(c, [t], w, provider=prov, now_hour=hour)
    # switching provider objects drops the memo
    static = StaticProvider.from_cluster(c)
    assert pol.select_batch(c, [t], w, provider=static) \
        == memo_off.select_batch(c, [t], w, provider=static)


def test_selection_memo_matches_fresh_across_profiles():
    c = fresh_cluster()
    pol = VectorizedPolicy(backend="numpy")
    fresh = VectorizedPolicy(backend="numpy", use_select_memo=False)
    w = MODES["green"]
    tasks = mixed_tasks(30, seed=9)
    assert pol.select_batch(c, tasks, w) == fresh.select_batch(c, tasks, w)
    # repeat: served from the memo, still identical
    assert pol.select_batch(c, tasks, w) == fresh.select_batch(c, tasks, w)


# ---------------------------------------------------------------------------
# sim driver byte-identity across execution paths
# ---------------------------------------------------------------------------


def test_sim_to_text_identical_across_exec_paths():
    from repro.sim import AsyncEngineDriver, PoissonArrivals

    def run(batch_execute):
        c = fresh_cluster()
        prov = TraceProvider({n: synthetic_trace(n, st.spec.carbon_intensity,
                                                 seed=i)
                              for i, (n, st) in enumerate(c.nodes.items())})
        eng = CarbonEdgeEngine(c, provider=prov,
                               batch_execute=batch_execute)
        drv = AsyncEngineDriver(
            eng, PoissonArrivals(120.0, seed=5),
            lambda uid, hour: Task(cpu=0.05, mem_mb=16.0,
                                   base_latency_ms=250.0),
            horizon_hours=0.5, max_batch=8, slo_latency_s=2.0,
            tick_hours=0.1)
        return drv.run().to_text()

    assert run(False) == run(True)


# ---------------------------------------------------------------------------
# hypothesis fuzz: randomized traffic + failure injection through both paths
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # optional extra: pip install -e .[test]
    HAVE_HYPOTHESIS = False


def _run_parity_example(specs, tasks, fail_node, limit):
    def mk(batch_execute):
        c = EdgeCluster(nodes=specs, host_power_w=120.0)
        c.profile(200.0)
        table = {s.name: s.carbon_intensity for s in specs}
        if fail_node is not None:
            # provider-blind selection + a provider that loses fail_node
            # after hour 0.5: exercises the execute-path KeyError cut
            prov = LateFailProvider(fail_node=fail_node)
            prov.table = table
            policy = RoundRobinPolicy(c.nodes)
        else:
            prov = StaticProvider(table)
            policy = None
        return CarbonEdgeEngine(c, policy=policy, provider=prov,
                                batch_execute=batch_execute)
    a, b = mk(False), mk(True)
    outcomes = []
    for eng in (a, b):
        eng.submit_many(tasks)
        try:
            res = eng.step(now_hour=1.0, limit=limit)
            outcomes.append(("ok", res))
        except NoFeasibleNodeError as e:
            outcomes.append(("infeasible", e.executed))
        except KeyError as e:
            outcomes.append(("keyerror", str(e)))
    assert outcomes[0] == outcomes[1]
    assert full_state(a) == full_state(b)


def test_parity_seeded_examples():
    """Deterministic slice of the fuzz domain — runs without hypothesis,
    so the parity contract is exercised even without the [test] extra."""
    rng = np.random.default_rng(21)
    for trial in range(25):
        n_nodes = int(rng.integers(2, 6))
        specs = [NodeSpec(f"n{i}", cpu=float(rng.uniform(0.2, 2.0)),
                          mem_mb=int(rng.integers(64, 1024)),
                          carbon_intensity=float(rng.uniform(50.0, 1000.0)))
                 for i in range(n_nodes)]
        n_tasks = int(rng.integers(1, 20))
        tasks = [Task(cpu=float(rng.uniform(0.0, 3.0)),
                      mem_mb=float(rng.integers(0, 1200)),
                      base_latency_ms=float(rng.uniform(1.0, 500.0)))
                 for _ in range(n_tasks)]
        fail_node = (None if trial % 3 == 0
                     else f"n{int(rng.integers(0, n_nodes))}")
        limit = None if trial % 2 else int(rng.integers(1, n_tasks + 1))
        _run_parity_example(specs, tasks, fail_node, limit)


if HAVE_HYPOTHESIS:
    @st.composite
    def traffic(draw):
        n_nodes = draw(st.integers(2, 5))
        specs = [NodeSpec(f"n{i}",
                          cpu=draw(st.floats(0.2, 2.0)),
                          mem_mb=draw(st.integers(64, 1024)),
                          carbon_intensity=draw(st.floats(50.0, 1000.0)))
                 for i in range(n_nodes)]
        n_tasks = draw(st.integers(1, 20))
        tasks = [Task(cpu=draw(st.floats(0.0, 3.0)),
                      mem_mb=float(draw(st.integers(0, 1200))),
                      base_latency_ms=draw(st.floats(1.0, 500.0)))
                 for _ in range(n_tasks)]
        fail_node = draw(st.sampled_from([None] + [s.name for s in specs]))
        limit = draw(st.one_of(st.none(), st.integers(1, n_tasks)))
        return specs, tasks, fail_node, limit

    @given(traffic())
    @settings(max_examples=60, deadline=None)
    def test_hypothesis_batched_scalar_parity(tr):
        _run_parity_example(*tr)
else:
    @pytest.mark.skip(reason="hypothesis not installed — pip install .[test]")
    def test_hypothesis_batched_scalar_parity():
        pass
