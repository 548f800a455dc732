"""Observability (repro.obs, DESIGN.md §9).

The load-bearing contract: obs disabled leaves every existing output
byte-identical (sim ``to_text`` across both execute paths), obs enabled
never perturbs a decision, and a fixed-seed run exports a byte-identical
JSONL trace.
"""
import json
import logging

import numpy as np
import pytest

from repro.core.api import CarbonEdgeEngine
from repro.core.cluster import EdgeCluster, PAPER_NODES
from repro.core.policy import VectorizedPolicy
from repro.core.providers import (ForecastProvider, StaticProvider,
                                  TraceProvider)
from repro.core.scheduler import MODES, Task
from repro.core.temporal import DeferrableTask, synthetic_trace
from repro.obs import (MODE_LABELS, TRACE_PREFIX, VERDICT_LABELS,
                       DecisionTrace, MetricsRegistry, Observability,
                       StepProfiler, console_logger, span)
from repro.obs import profiler as profiler_mod
from repro.partition import PartitionPolicy, profile_costs
from repro.sim import AsyncEngineDriver, PoissonArrivals
from repro.tenancy import (MODE_ORDER, TenantPolicy, TenantRegistry,
                           TenantSpec, TenantTask)

TASK = Task(cpu=0.05, mem_mb=16.0, base_latency_ms=250.0)


def fresh_cluster():
    c = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
    c.profile(250.0)
    return c


def submit_n(eng, n, seed=7):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        eng.submit(Task(cpu=float(rng.uniform(0.01, 0.2)),
                        mem_mb=float(rng.uniform(8, 64)),
                        base_latency_ms=float(rng.uniform(100, 800))))


# ---------------------------------------------------------------------------
# DecisionTrace
# ---------------------------------------------------------------------------


def test_trace_record_and_row_roundtrip():
    t = DecisionTrace(capacity=8)
    ids = t.intern_names(["b", "a", "b"])
    t.record_batch(step=1, hour=2.5, verdict=np.zeros(3, np.int8),
                   node=ids, score=[0.9, 0.8, 0.7], carbon_g=0.25)
    assert len(t) == 3 and t.count == 3
    r = t.row(0)
    assert r["step"] == 1 and r["task"] == 0 and r["hour"] == 2.5
    assert r["verdict"] == "done" and r["node"] == "b"
    assert r["score"] == 0.9 and r["carbon_g"] == 0.25
    # absent columns render as None, not stale fills
    assert r["cut"] is None and r["tenant"] is None and r["intensity"] is None


def test_trace_ring_wraparound_keeps_newest_oldest_first():
    t = DecisionTrace(capacity=5)
    for s in range(4):                       # 4 steps x 2 rows = 8 > 5
        t.record_batch(step=s, hour=0.0, verdict=np.zeros(2, np.int8),
                       score=[s + 0.1, s + 0.2])
    assert t.count == 8 and len(t) == 5
    got = [(r["step"], r["task"]) for r in t.rows()]
    assert got == [(1, 1), (2, 0), (2, 1), (3, 0), (3, 1)]
    scores = [r["score"] for r in t.rows()]
    assert scores == sorted(scores)          # oldest-first ordering


def test_trace_oversize_batch_clips_to_tail():
    t = DecisionTrace(capacity=4)
    t.record_batch(step=0, hour=0.0, verdict=np.zeros(10, np.int8),
                   score=np.arange(10.0))
    assert t.count == 10 and len(t) == 4
    assert [r["task"] for r in t.rows()] == [6, 7, 8, 9]
    assert [r["score"] for r in t.rows()] == [6.0, 7.0, 8.0, 9.0]


def test_trace_jsonl_sorted_keys_and_null_for_nan():
    t = DecisionTrace(capacity=4)
    t.record_batch(step=0, hour=0.0, verdict=np.zeros(1, np.int8))
    text = t.to_jsonl()
    assert text.endswith("\n") and "NaN" not in text
    row = json.loads(text.splitlines()[0])
    assert list(row) == sorted(row)
    assert row["score"] is None and row["node"] is None


def test_trace_explain_names_node_and_margin():
    t = DecisionTrace(capacity=4)
    ids = t.intern_names(["node-green"])
    t.record_batch(step=3, hour=0.0, verdict=np.zeros(1, np.int8),
                   node=ids, cut=2, mode=2, score=0.9, runner_up=0.7,
                   intensity=380.0, carbon_g=0.01)
    line = t.explain(3, 0)
    assert "'node-green'" in line and "cut 2" in line
    assert "green mode" in line and "margin 0.2" in line
    assert t.explain(99, 0) is None


def test_trace_verdict_counts_and_conformal_coverage():
    t = DecisionTrace(capacity=8)
    t.record_batch(step=0, hour=0.0, verdict=np.array([0, 1, 2, 0], np.int8),
                   intensity=[400.0, 400.0, 400.0, 500.0],
                   interval_lo=[390.0, np.nan, 390.0, 490.0],
                   interval_hi=[410.0, np.nan, 410.0, 495.0])
    assert t.verdict_counts() == {"done": 2, "reject": 1, "defer": 1}
    cov = t.conformal_coverage()
    # 3 non-degenerate intervals, the 500-in-[490,495] row misses
    assert cov["rows"] == 3 and cov["coverage"] == pytest.approx(2 / 3)


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_and_grow():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "tasks", labels=("node",))
    for i in range(20):                      # force several _grow doublings
        c.inc(1.0, (f"n{i:02d}",))
    c.inc(2.5, ("n00",))
    assert c.get(("n00",)) == 3.5 and len(c) == 20
    g = reg.gauge("depth", "queue depth")
    g.set(7.0)
    assert g.get() == 7.0


def test_registry_inc_at_matches_scalar_loop_on_duplicates():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "", labels=("k",))
    rows = c.rows([("a",), ("b",)])
    idx = np.array([rows[0], rows[1], rows[0], rows[0]])
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    c.inc_at(idx, vals)
    assert c.get(("a",)) == 8.0 and c.get(("b",)) == 2.0


def test_registry_histogram_buckets_cumulative_render():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s", "latency", edges=[0.1, 1.0, 10.0])
    h.observe([0.05, 0.5, 0.5, 5.0, 50.0])
    text = reg.to_text()
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="1"} 3' in text
    assert 'lat_s_bucket{le="10"} 4' in text
    assert 'lat_s_bucket{le="+Inf"} 5' in text
    assert "lat_s_count 5" in text
    assert "# TYPE lat_s histogram" in text


def test_registry_rejects_kind_and_label_mismatch():
    reg = MetricsRegistry()
    reg.counter("a_total", "", labels=("x",))
    with pytest.raises(ValueError, match="re-registered"):
        reg.gauge("a_total", "", labels=("x",))
    with pytest.raises(ValueError, match="re-registered"):
        reg.counter("a_total", "", labels=("y",))
    with pytest.raises(ValueError, match="expected labels"):
        reg.get("a_total").inc(1.0, ())


def test_registry_exposition_is_deterministic():
    def build():
        reg = MetricsRegistry()
        c = reg.counter("n_total", "help text", labels=("node",))
        for name in ("zeta", "alpha", "mid"):
            c.inc(1.5, (name,))
        return reg.to_text()

    assert build() == build()
    lines = build().splitlines()
    assert lines[0] == "# HELP n_total help text"
    # series sorted by label tuple regardless of intern order
    assert [l for l in lines if l.startswith("n_total{")] == [
        'n_total{node="alpha"} 1.5', 'n_total{node="mid"} 1.5',
        'n_total{node="zeta"} 1.5']


# ---------------------------------------------------------------------------
# StepProfiler
# ---------------------------------------------------------------------------


def test_profiler_add_span_and_percentiles():
    p = StepProfiler()
    for dt in (1e-5, 1e-4, 1e-4, 1e-3):
        p.add("score", dt)
    with p.span("score"):
        pass
    assert p.count("score") == 5
    assert p.total_s("score") >= 1e-5 + 2e-4 + 1e-3
    assert p.percentile_s("score", 50) <= p.percentile_s("score", 95)
    s = p.summary()["phases"]["score"]
    assert s["count"] == 5 and s["min_s"] <= 1e-5 and s["max_s"] >= 1e-3
    p.reset()
    assert p.phases() == []


def test_profiler_bins_handle_out_of_range_durations():
    p = StepProfiler()
    p.add("x", 1e-12)                        # below the first edge
    p.add("x", 1e6)                          # beyond the last edge
    s = p.summary()["phases"]["x"]
    assert s["count"] == 2 and sum(s["hist"]) == 2
    assert p.percentile_s("x", 99) == pytest.approx(1e6)


def test_span_detached_reads_no_clock(monkeypatch):
    """Off means an inactive profiler annotation and nothing else: no
    ``perf_counter`` and nothing folded anywhere."""
    def no_clock():
        raise AssertionError("perf_counter read by a detached span")

    monkeypatch.setattr(profiler_mod, "perf_counter", no_clock)
    p = StepProfiler()
    for _ in range(3):
        with span(None, "score"):
            pass
    assert p.phases() == []


def test_span_attached_folds_one_per_completed_call():
    p = StepProfiler()
    for _ in range(3):
        with span(p, "select.pad"):
            pass
    with pytest.raises(ValueError):
        with span(p, "select.pad"):
            raise ValueError("a phase that raises is not folded")
    assert p.phases() == ["select.pad"]
    assert p.count("select.pad") == 3


def test_span_lands_in_profiler_host_plane(tmp_path):
    """Under a ``jax.profiler`` capture the span is a host event named
    ``carbonedge.<phase>``, on the clock of the device's events."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with span(None, "unit.outer"):
            with span(None, "unit.inner"):
                jnp.ones((8, 8)).sum().block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {ev.name: (ev.start_ns, ev.end_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith(TRACE_PREFIX)}
    outer, inner = events["carbonedge.unit.outer"], events["carbonedge.unit.inner"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]


def test_pallas_select_splits_score_into_pad_put_launch_fetch():
    """On the Pallas column path each call's ``score`` holds one
    ``select.columns``, and it one ``select.pad``, ``select.put``,
    ``select.launch`` and ``select.fetch``, which add up to no more than
    it: all the call's rows go in one launch."""
    rng = np.random.default_rng(3)
    pol = VectorizedPolicy(backend="pallas", use_select_memo=False)
    pol.profiler = p = StepProfiler()
    tasks = [Task(cpu=float(c), mem_mb=float(m))
             for c, m in zip(rng.uniform(0.01, 0.2, 10),
                             rng.uniform(8, 64, 10))]
    for _ in range(3):
        pol.select_batch(fresh_cluster(), tasks, MODES["green"])
    parts = ("select.pad", "select.put", "select.launch", "select.fetch")
    assert [p.count(ph) for ph in parts] == [3] * 4
    assert p.count("score") == p.count("select.columns") == 3
    assert (sum(p.total_s(ph) for ph in parts) <= p.total_s("select.columns")
            <= p.total_s("score"))


# ---------------------------------------------------------------------------
# encoding consistency (kept duplicated to avoid import cycles)
# ---------------------------------------------------------------------------


def test_mode_labels_match_tenancy_mode_order():
    assert MODE_LABELS == MODE_ORDER
    assert set(MODE_LABELS) == set(MODES)


def test_verdict_labels_are_the_trace_contract():
    from repro.obs import VERDICT_DEFER, VERDICT_DONE, VERDICT_REJECT
    assert VERDICT_LABELS[VERDICT_DONE] == "done"
    assert VERDICT_LABELS[VERDICT_REJECT] == "reject"
    assert VERDICT_LABELS[VERDICT_DEFER] == "defer"


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


def untenanted_engine(obs=None, batch_execute=True):
    c = fresh_cluster()
    return CarbonEdgeEngine(c, mode="green", batch_execute=batch_execute,
                            obs=obs)


@pytest.mark.parametrize("batch_execute", [True, False])
def test_engine_obs_never_perturbs_decisions(batch_execute):
    base = untenanted_engine(batch_execute=batch_execute)
    submit_n(base, 40)
    ra = base.step(now_hour=3.0)

    obs = Observability.all()
    eng = untenanted_engine(obs=obs, batch_execute=batch_execute)
    submit_n(eng, 40)
    rb = eng.step(now_hour=3.0)

    assert [r.node for r in ra] == [r.node for r in rb]
    assert [r.carbon_g for r in ra] == [r.carbon_g for r in rb]
    # trace mirrors the executed batch exactly
    rows = list(obs.trace.rows())
    assert len(rows) == len(rb)
    for row, res in zip(rows, rb):
        assert row["node"] == res.node and row["verdict"] == "done"
        assert row["carbon_g"] == pytest.approx(res.carbon_g, rel=1e-12)


def test_engine_trace_scores_winner_beats_runner_up():
    obs = Observability.all()
    eng = untenanted_engine(obs=obs)
    submit_n(eng, 30)
    eng.step(now_hour=0.0)
    rows = list(obs.trace.rows())
    assert all(r["score"] is not None for r in rows)
    assert all(r["score"] >= r["runner_up"] for r in rows)
    assert all(r["intensity"] is not None and r["intensity_billed"] is not None
               for r in rows)


def test_engine_capture_off_leaves_policy_untouched():
    pol = VectorizedPolicy()
    eng = CarbonEdgeEngine(fresh_cluster(), mode="green", policy=pol)
    submit_n(eng, 10)
    eng.step(now_hour=0.0)
    assert pol.capture_scores is False and pol.last_scores is None
    assert pol.profiler is None


def test_engine_report_surfaces_outcomes_and_depths():
    obs = Observability.all()
    eng = untenanted_engine(obs=obs)
    submit_n(eng, 25)
    eng.step(now_hour=0.0, limit=10)
    eng.step(now_hour=0.0, limit=10)
    rep = eng.report()
    assert rep["steps"] == 2
    assert rep["outcomes"] == {"done": 20, "reject": 0, "defer": 0}
    assert rep["deferred_depth"] == 0
    deep = eng.report(deep=True)["deep"]
    assert deep["trace"]["recorded"] == 20
    assert deep["deferral"]["parked"] == 0
    prof = deep["profiler"]["phases"]
    for phase in ("select", "execute", "bill", "observe"):
        assert prof[phase]["count"] == 2, phase
    assert "engine_tasks_total" in deep["metrics"]


def test_engine_report_outcomes_without_obs():
    eng = untenanted_engine()
    submit_n(eng, 8)
    eng.step(now_hour=0.0)
    rep = eng.report()
    assert rep["steps"] == 1 and rep["outcomes"]["done"] == 8
    assert "deep" not in rep


def test_engine_conformal_interval_recorded_and_covered():
    c = fresh_cluster()

    class Margin:
        def quantile(self, coverage):
            return 25.0

    prov = ForecastProvider(StaticProvider.from_cluster(c), conformal=Margin())
    obs = Observability(trace=True)
    eng = CarbonEdgeEngine(c, mode="green", provider=prov, obs=obs)
    submit_n(eng, 12)
    eng.step(now_hour=0.0)
    rows = list(obs.trace.rows())
    assert all(r["interval_hi"] - r["interval_lo"] == pytest.approx(50.0)
               for r in rows)
    cov = obs.trace.conformal_coverage()
    assert cov["rows"] == 12 and cov["coverage"] == 1.0


def test_engine_partition_trace_records_cuts():
    prof = profile_costs([10.0, 10.0, 10.0, 10.0],
                         boundary_bytes=[1e4, 1e4, 1e4, 0.0])
    obs = Observability.all()
    eng = CarbonEdgeEngine(fresh_cluster(), mode="green",
                           policy=PartitionPolicy(prof, backend="numpy"),
                           obs=obs)
    submit_n(eng, 20)
    eng.step(now_hour=0.0)
    rows = list(obs.trace.rows())
    assert all(r["cut"] is not None for r in rows)
    hist = obs.trace.cut_histogram()
    assert sum(hist.values()) == 20
    deep = eng.report(deep=True)["deep"]
    assert deep["partition"]["cut_histogram"] == hist
    assert deep["partition"]["last_batch_cuts"] == hist


def test_engine_partition_obs_parity():
    prof = profile_costs([10.0, 10.0, 10.0, 10.0],
                         boundary_bytes=[1e4, 1e4, 1e4, 0.0])

    def run(obs):
        eng = CarbonEdgeEngine(fresh_cluster(), mode="green",
                               policy=PartitionPolicy(prof, backend="numpy"),
                               obs=obs)
        submit_n(eng, 20)
        res = eng.step(now_hour=0.0)
        return ([r.node for r in res],
                [d.cut_index for d in eng.policy.last_decisions])

    assert run(None) == run(Observability.all())


def tenant_specs():
    return [TenantSpec("acme", allowance_g=1e-5, period_hours=1.0,
                       defer_over_reject=False),
            TenantSpec("zen", allowance_g=1e6, period_hours=1.0)]


def test_engine_tenancy_trace_verdicts_match_outcomes():
    obs = Observability.all()
    reg = TenantRegistry(tenant_specs())
    eng = CarbonEdgeEngine(fresh_cluster(), mode="green",
                           policy=TenantPolicy(registry=reg), obs=obs)
    for i in range(8):
        eng.submit(TenantTask(cpu=0.05, mem_mb=16.0, base_latency_ms=250.0,
                              tenant=("acme" if i % 2 == 0 else "zen")))
    eng.step(now_hour=0.0)
    rows = list(obs.trace.rows())
    assert len(rows) == 8
    outcome_kinds = [k for k, _ in eng.last_outcomes]
    assert [r["verdict"] for r in rows] == outcome_kinds
    # tenants resolve by name; admitted rows carry node + score
    assert {r["tenant"] for r in rows} == {"acme", "zen"}
    done = [r for r in rows if r["verdict"] == "done"]
    assert done and all(r["node"] is not None and r["score"] is not None
                        for r in done)
    rejected = [r for r in rows if r["verdict"] == "reject"]
    assert rejected and all(r["node"] is None for r in rejected)
    assert all(r["expected_g"] is not None for r in rows)
    # outcome totals line up with the verdict counters
    rep = eng.report()
    assert rep["outcomes"]["done"] == len(done)
    assert rep["outcomes"]["reject"] == len(rejected)
    fam = obs.metrics.get("engine_outcomes_total")
    assert fam.get(("done",)) == len(done)
    assert fam.get(("reject",)) == len(rejected)


@pytest.mark.parametrize("batch_execute", [True, False])
def test_engine_tenancy_obs_parity(batch_execute):
    def run(obs):
        reg = TenantRegistry(tenant_specs())
        eng = CarbonEdgeEngine(fresh_cluster(), mode="green",
                               policy=TenantPolicy(registry=reg),
                               batch_execute=batch_execute, obs=obs)
        for i in range(10):
            eng.submit(TenantTask(cpu=0.05, mem_mb=16.0,
                                  base_latency_ms=250.0,
                                  tenant=("acme" if i % 2 else "zen")))
        res = eng.step(now_hour=0.0)
        return [k for k, _ in eng.last_outcomes], [r.node for r in res]

    assert run(None) == run(Observability.all())


# ---------------------------------------------------------------------------
# policy score capture
# ---------------------------------------------------------------------------


def test_policy_capture_matches_full_featurize_argmax():
    from repro.core.policy import featurize

    c = fresh_cluster()
    pol = VectorizedPolicy(backend="numpy")
    pol.capture_scores = True
    rng = np.random.default_rng(3)
    tasks = [Task(cpu=float(rng.uniform(0.01, 0.2)),
                  mem_mb=float(rng.uniform(8, 64)),
                  base_latency_ms=float(rng.uniform(100, 800)))
             for _ in range(16)]
    prov = StaticProvider.from_cluster(c)
    choices = pol.select_batch(c, tasks, MODES["green"], provider=prov)
    ls = pol.last_scores
    assert len(ls["score"]) == 16
    for t, ch, s, r in zip(tasks, choices, ls["score"], ls["runner_up"]):
        F, names = featurize(c, [t], provider=prov)
        totals = pol.score_batch(F, MODES["green"])[0]
        best = int(np.argmax(totals))
        assert ch == names[best]
        assert s == pytest.approx(totals[best], rel=1e-12)
        rest = np.delete(totals, best)
        rest = rest[np.isfinite(rest)]
        if rest.size:
            assert r == pytest.approx(rest.max(), rel=1e-12)
    # memo-hit path returns identical captures
    again = pol.select_batch(c, tasks, MODES["green"], provider=prov)
    assert again == choices
    np.testing.assert_array_equal(pol.last_scores["score"], ls["score"])


# ---------------------------------------------------------------------------
# sim integration: the byte-identity contract
# ---------------------------------------------------------------------------


def duck_traces():
    return {
        "node-high": synthetic_trace("coal-heavy", 620.0, solar_dip=0.1),
        "node-medium": synthetic_trace("cn-average", 530.0, solar_dip=0.3),
        "node-green": synthetic_trace("hydro-rich", 380.0, solar_dip=0.5),
    }


def sim_run(obs=None, batch_execute=True, plain=False):
    """One fixed-seed sim; ``plain=True`` builds pre-obs-style objects
    (no obs kwarg anywhere) — the pre-PR golden path."""
    c = fresh_cluster()
    provider = TraceProvider(duck_traces(),
                             fallback=StaticProvider.from_cluster(c))
    ekw = {} if plain else {"obs": obs}
    eng = CarbonEdgeEngine(c, mode="green", provider=provider,
                           batch_execute=batch_execute, **ekw)
    fore = ForecastProvider(provider)

    def factory(uid, hour):
        if uid % 3 == 0:
            return DeferrableTask(cpu=0.05, mem_mb=16.0,
                                  base_latency_ms=250.0, deadline_hours=4.0)
        return TASK

    dkw = {} if plain else {"obs": obs}
    d = AsyncEngineDriver(eng, PoissonArrivals(rate_per_hour=240.0, seed=11),
                          factory, horizon_hours=1.0, max_batch=16,
                          forecast=fore, tick_hours=0.25,
                          slo_latency_s=2.0, **dkw)
    return d.run(), (None if plain else obs)


@pytest.mark.parametrize("batch_execute", [True, False])
def test_sim_to_text_byte_identical_across_obs_states(batch_execute):
    golden = sim_run(plain=True, batch_execute=batch_execute)[0].to_text()
    off = sim_run(obs=None, batch_execute=batch_execute)[0].to_text()
    disabled = sim_run(obs=Observability(),
                       batch_execute=batch_execute)[0].to_text()
    on = sim_run(obs=Observability.all(),
                 batch_execute=batch_execute)[0].to_text()
    assert off == golden
    assert disabled == golden
    assert on == golden


def test_sim_trace_jsonl_deterministic_across_runs():
    _, a = sim_run(obs=Observability.all())
    _, b = sim_run(obs=Observability.all())
    ja, jb = a.trace.to_jsonl(), b.trace.to_jsonl()
    assert ja and ja == jb


def test_sim_obs_counters_and_phases():
    m, obs = sim_run(obs=Observability.all())
    phases = set(obs.profiler.phases())
    assert {"sim_step", "sim_record", "sim_plan",
            "select", "execute", "bill", "observe"} <= phases
    ev = obs.metrics.get("sim_events_total")
    n_tasks = len(m.records)
    assert ev.get(("ARRIVAL",)) >= n_tasks
    # every profiled executor step came from a BATCH_READY event
    assert 0 < obs.profiler.count("sim_step") <= ev.get(("BATCH_READY",))
    # the exported summary gauge agrees with the collector
    assert obs.metrics.get("sim_summary").get(("tasks",)) == n_tasks
    done = obs.metrics.get("sim_tasks_total")
    total = sum(done.get((n,)) for n in ("node-high", "node-medium",
                                         "node-green"))
    assert total == n_tasks
    # trace saw exactly the completed tasks (untenanted: all done)
    assert obs.trace.verdict_counts()["done"] == n_tasks


# ---------------------------------------------------------------------------
# console logger
# ---------------------------------------------------------------------------


def test_console_logger_idempotent_and_bare_format():
    root = logging.getLogger("repro")
    before = [h for h in root.handlers
              if getattr(h, "_repro_console", False)]
    a = console_logger("repro.launch.serve")
    b = console_logger("repro.launch.train")
    after = [h for h in root.handlers
             if getattr(h, "_repro_console", False)]
    assert len(after) == max(1, len(before))       # attached exactly once
    assert a is not b and after[0].formatter._fmt == "%(message)s"


def test_console_logger_emits_bare_message(capsys):
    log = console_logger("obs_test_logger")        # non-repro: own handler
    log.info("plain %d output", 42)
    assert capsys.readouterr().out == "plain 42 output\n"


def test_launchers_use_module_loggers():
    import repro.launch.serve as serve
    import repro.launch.train as train
    assert isinstance(serve.log, logging.Logger)
    assert isinstance(train.log, logging.Logger)
