"""Internet-scale event calendar vs the scalar heap oracle
(repro.sim.events, DESIGN.md §11)."""
import numpy as np
import pytest

from repro.core.cluster import EdgeCluster, PAPER_NODES
from repro.core.providers import TraceProvider, load_intensity_csv
from repro.core.scheduler import Task
from repro.sim import (AsyncEngineDriver, ClientPopulation,
                       ClosedLoopClientPool, EventCalendar, EventHeap,
                       EventKind, SimExhausted, TraceReplayArrivals)
from repro.sim.events import KIND_CODE

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # the container image ships without hypothesis
    HAVE_HYPOTHESIS = False

TASK = Task(cpu=0.05, mem_mb=16.0, base_latency_ms=250.0)

# Kinds whose interleaving the calendar must keep in heap order: int
# payloads (CLIENT_READY/RETRY) share the payload column with the
# object-store kinds (DEFER_WAKE/NODE_DOWN/...).
FUZZ_KINDS = (EventKind.DEFER_WAKE, EventKind.RETRY, EventKind.NODE_DOWN,
              EventKind.CLIENT_READY, EventKind.ARRIVAL)


# ---------------------------------------------------------------------------
# Empty-pop regression
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make", [EventHeap, EventCalendar])
def test_empty_pop_raises_sim_exhausted(make):
    q = make()
    with pytest.raises(SimExhausted):
        q.pop()
    # SimExhausted subclasses IndexError (what heapq used to leak), so
    # pre-existing `except IndexError` callers keep working
    with pytest.raises(IndexError):
        q.pop()
    q.push(1.0, EventKind.ARRIVAL)
    q.pop()
    with pytest.raises(SimExhausted):
        q.pop()


# ---------------------------------------------------------------------------
# Calendar-vs-heap ordering parity
# ---------------------------------------------------------------------------


def _payload_for(kind: EventKind, i: int):
    # CLIENT_READY/RETRY payloads ride the int column; others go through
    # the per-kind object store
    if kind in (EventKind.CLIENT_READY, EventKind.RETRY):
        return i
    return ("obj", i) if i % 3 else None


def _apply_ops(ops):
    """Apply the same op sequence to heap and calendar; compare every
    popped event and the final drain."""
    heap, cal = EventHeap(), EventCalendar(target_bucket_events=4)
    i = 0
    for op in ops:
        if op == "pop":
            if heap:
                a, b = heap.pop(), cal.pop()
                assert (a.time_hours, a.seq, a.kind, a.payload) == \
                    (b.time_hours, b.seq, b.kind, b.payload)
            continue
        if op[0] == "push":
            _, t, kind = op
            heap.push(t, kind, _payload_for(kind, i))
            cal.push(t, kind, _payload_for(kind, i))
            i += 1
        else:                                   # ("batch", times, kind)
            _, times, kind = op
            ts = np.asarray(times, dtype=float)
            if kind in (EventKind.CLIENT_READY, EventKind.RETRY):
                ids = np.arange(i, i + ts.size, dtype=np.int64)
                cal.push_batch(ts, kind, ids)
                for j, t in enumerate(ts.tolist()):
                    heap.push(t, kind, i + j)
            else:
                cal.push_batch(ts, kind)
                for t in ts.tolist():
                    heap.push(t, kind, None)
            i += ts.size
    assert len(heap) == len(cal)
    while heap:
        a, b = heap.pop(), cal.pop()
        assert (a.time_hours, a.seq, a.kind, a.payload) == \
            (b.time_hours, b.seq, b.kind, b.payload)
    assert not cal


def test_calendar_matches_heap_under_collision_bursts():
    """Seeded fuzz: bursts of identical timestamps interleaved with
    scalar pushes, batch pushes and pops must preserve (time, seq)
    order exactly — including pushes landing behind the cursor after
    the first pop activates the calendar."""
    rng = np.random.default_rng(20260808)
    grid = np.array([0.0, 0.25, 0.25, 0.5, 0.5, 0.5, 1.0, 2.5])
    for _ in range(25):
        ops = []
        for _ in range(rng.integers(10, 60)):
            r = rng.random()
            if r < 0.45:
                ops.append(("push", float(rng.choice(grid)),
                            FUZZ_KINDS[rng.integers(len(FUZZ_KINDS))]))
            elif r < 0.7:
                n = int(rng.integers(1, 12))
                ops.append(("batch", rng.choice(grid, n),
                            FUZZ_KINDS[rng.integers(len(FUZZ_KINDS))]))
            else:
                ops.append("pop")
        _apply_ops(ops)


def test_pop_run_matches_scalar_pops():
    """pop_run must return exactly the prefix a scalar pop loop with the
    same qualification rule would, in the same order."""
    rng = np.random.default_rng(7)
    codes = (KIND_CODE[EventKind.CLIENT_READY], KIND_CODE[EventKind.RETRY])
    for _ in range(10):
        heap, cal = EventHeap(), EventCalendar(target_bucket_events=8)
        n = int(rng.integers(30, 120))
        ts = np.round(rng.uniform(0.0, 1.0, n), 2)     # forced collisions
        for j, t in enumerate(ts.tolist()):
            kind = FUZZ_KINDS[int(rng.integers(len(FUZZ_KINDS)))]
            p = j if KIND_CODE[kind] in codes else None
            heap.push(t, kind, p)
            cal.push(t, kind, p)
        while cal:
            max_n = int(rng.integers(1, 16))
            max_t = float(rng.choice([0.3, 0.7, np.inf]))
            rt, rp, rk = cal.pop_run(codes, max_n, max_time=max_t)
            # reference: scalar pops off the heap under the same rule
            want = []
            while heap and len(want) < max_n:
                nxt = heap.peek()
                if KIND_CODE[nxt.kind] not in codes or \
                        not nxt.time_hours <= max_t:
                    break
                want.append(heap.pop())
            assert rt.size == len(want)
            assert rt.tolist() == [e.time_hours for e in want]
            assert rk.tolist() == [KIND_CODE[e.kind] for e in want]
            assert rp.tolist() == [e.payload for e in want]
            if rt.size == 0:                # next event doesn't qualify
                a, b = heap.pop(), cal.pop()
                assert (a.time_hours, a.seq, a.kind) == \
                    (b.time_hours, b.seq, b.kind)


if HAVE_HYPOTHESIS:
    _kind = st.sampled_from(FUZZ_KINDS)
    _time = st.sampled_from([0.0, 0.125, 0.25, 0.25, 0.5, 1.0])
    _op = st.one_of(
        st.just("pop"),
        st.tuples(st.just("push"), _time, _kind),
        st.tuples(st.just("batch"),
                  st.lists(_time, min_size=1, max_size=10), _kind),
    )

    @settings(max_examples=60, deadline=None)
    @given(st.lists(_op, max_size=60))
    def test_hypothesis_calendar_heap_parity(ops):
        _apply_ops(ops)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_hypothesis_calendar_heap_parity():
        pass


# ---------------------------------------------------------------------------
# Driver byte identity (null executor isolates the event machinery)
# ---------------------------------------------------------------------------


class _NullResult:
    __slots__ = ()
    latency_ms = 0.05
    energy_kwh = 1e-6
    carbon_g = 0.5
    node = "n0"


class _NullExecutor:
    """Constant-cost executor exposing the same surface the driver uses
    on a real engine (submit/submit_many/step + last_exec columns)."""

    def __init__(self, max_batch=256):
        self._queued = 0
        self._res = _NullResult()
        self._uniq = np.array([_NullResult.node])
        self._inv = np.zeros(max_batch, dtype=np.int64)
        self._lat = np.full(max_batch, _NullResult.latency_ms)
        self._ekwh = np.full(max_batch, _NullResult.energy_kwh)
        self._cg = np.full(max_batch, _NullResult.carbon_g)
        self.last_exec = None

    def submit(self, task):
        self._queued += 1

    def submit_many(self, tasks):
        self._queued += len(tasks)

    def step(self, now_hour=0.0, limit=None):
        k = self._queued if limit is None else min(self._queued, limit)
        self._queued -= k
        self.last_exec = (self._uniq, self._inv[:k], self._lat[:k],
                          self._ekwh[:k], self._cg[:k])
        return [self._res] * k


def _closed_loop_text(event_queue, *, n_clients=300, max_batch=64,
                      horizon=0.1, slo=1e-5):
    pool = ClosedLoopClientPool([
        ClientPopulation("bulk", (n_clients * 2) // 3,
                         mean_think_hours=0.01),
        ClientPopulation("strict", n_clients - (n_clients * 2) // 3,
                         mean_think_hours=0.015, slo_latency_s=slo,
                         max_attempts=3, priority=1),
    ], seed=11)
    drv = AsyncEngineDriver(
        _NullExecutor(max_batch), None, lambda uid, hour, tenant: uid,
        horizon_hours=horizon, max_batch=max_batch,
        batch_window_hours=5e-4, clients=pool, event_queue=event_queue)
    return drv.run().to_text()


def test_driver_byte_identity_closed_loop():
    assert _closed_loop_text("calendar") == _closed_loop_text("heap")


def test_driver_byte_identity_saturated_regime():
    """max_batch=2 keeps the pending set full, forcing the calendar loop
    through its scalar-dispatch fallback and the per-bucket heap overlay
    — the paths a wide-open batch never touches."""
    kw = dict(n_clients=120, max_batch=2, horizon=0.05)
    assert _closed_loop_text("calendar", **kw) == _closed_loop_text(
        "heap", **kw)


def test_saturated_flush_event_count_linear():
    """Sustained saturation (pending never drains below max_batch) must
    keep the BATCH_READY population bounded: one armed flush at a time,
    re-armed per drain — not one per enqueue, which made total event
    count quadratic (each drain dragged every same-time flush event
    along, ~45 flush pops per client event at 10^6 clients)."""
    for event_queue in ("heap", "calendar"):
        pool = ClosedLoopClientPool([
            ClientPopulation("bulk", 400, mean_think_hours=0.01),
        ], seed=7)
        drv = AsyncEngineDriver(
            _NullExecutor(4), None, lambda uid, hour, tenant: uid,
            horizon_hours=0.02, max_batch=4, batch_window_hours=5e-4,
            clients=pool, event_queue=event_queue)
        m = drv.run()
        # per task: one CLIENT_READY/RETRY pop + O(1) amortized flush
        # pops (drain + busy bounce + superseded stale); initial events
        # past the horizon still pop once each
        bound = 6 * m.n_records + 2 * 400 + 50
        assert drv.events_processed <= bound, (
            event_queue, drv.events_processed, m.n_records)


def test_driver_horizon_boundary_parity():
    """Arrivals exactly at and beyond start+horizon: the batched
    searchsorted split must drop the same suffix the scalar `now >=
    horizon` check does, on both queues."""
    ts = np.array([0.005, 0.01, 0.02, 0.02, 0.05, 0.0500000001, 0.06])

    def one(event_queue):
        drv = AsyncEngineDriver(
            _NullExecutor(), TraceReplayArrivals(ts),
            lambda uid, hour: uid, horizon_hours=0.05, max_batch=16,
            batch_window_hours=5e-4, event_queue=event_queue)
        m = drv.run()
        return m.to_text(), m.n_records

    (text_c, n_c), (text_h, n_h) = one("calendar"), one("heap")
    assert text_c == text_h
    assert n_c == n_h
    assert n_c <= 5        # the past-horizon tail must not be served


def test_pool_initial_events_batch_matches_scalar():
    def pool():
        # both paths consume the RNG stream, so each gets a fresh pool
        return ClosedLoopClientPool([
            ClientPopulation("a", 40, mean_think_hours=0.01),
            ClientPopulation("b", 25, mean_think_hours=0.02, priority=1),
        ], seed=3)

    ats, cids = pool().initial_events_arrays(2.0)
    scalar = pool().initial_events(2.0)
    assert ats.size == len(scalar) == 65
    assert list(zip(ats.tolist(), cids.tolist())) == scalar
    # priority=1 clients win same-instant ties; all stagger past start
    assert (ats >= 2.0).all()


# ---------------------------------------------------------------------------
# Regional CSV ingestion + multi-region trace replay determinism
# ---------------------------------------------------------------------------

CSV_ISO = (
    "datetime,zone_name,carbon_intensity_avg\n"
    "2026-08-07T00:00:00Z,DE,320.5\n"
    "2026-08-07T00:00:00Z,FR,58.0\n"
    "2026-08-07T01:00:00Z,DE,310.0\n"
    "2026-08-07T01:00:00Z,FR,61.5\n"
    "2026-08-07T02:00:00Z,DE,300.25\n"
    "2026-08-07T02:00:00Z,FR,60.0\n"
)


def test_load_intensity_csv_iso_multizone():
    zones = load_intensity_csv(CSV_ISO)
    assert sorted(zones) == ["DE", "FR"]
    de = zones["DE"]
    # midnight-started day rebases onto hours 0..2, one-hour steps
    assert de.start_hour == 0.0 and de.step_hours == 1.0
    assert de.at(0.0) == 320.5
    assert de.at(1.5) == pytest.approx((310.0 + 300.25) / 2)
    assert zones["FR"].at(2.0) == 60.0


def test_load_intensity_csv_numeric_hours_zoneless():
    text = "hour,intensity\n0.0,100\n0.5,110\n\n1.0,120\n"
    zones = load_intensity_csv(text)
    assert list(zones) == [""]
    tr = zones[""]
    assert tr.step_hours == 0.5
    assert tr.at(0.25) == pytest.approx(105.0)


def test_load_intensity_csv_errors():
    with pytest.raises(KeyError, match="carbon-intensity"):
        load_intensity_csv("hour,zone\n0,DE\n1,DE\n")
    with pytest.raises(ValueError, match="uniformly spaced"):
        load_intensity_csv("hour,intensity\n0,100\n1,110\n3,120\n")
    with pytest.raises(ValueError, match="no intensity rows"):
        load_intensity_csv("hour,intensity\n")


def test_from_csv_unknown_zone_lists_available():
    with pytest.raises(KeyError, match="zones.*DE.*FR"):
        TraceProvider.from_csv(CSV_ISO, node_zones={"n0": "XX"})


def test_from_csv_node_mapping_and_batch_parity():
    tp = TraceProvider.from_csv(CSV_ISO, node_zones={"n0": "DE",
                                                     "n1": "FR"})
    assert tp.intensity("n0", 1.0) == 310.0
    hours = np.array([0.5, 1.0, 2.0])
    batch = tp.intensity_batch(["n0", "n1"], hours)   # (hours, names) grid
    want = [[tp.intensity(n, h) for n in ("n0", "n1")]
            for h in hours.tolist()]
    assert batch.tolist() == want


def test_multi_region_trace_replay_deterministic():
    """A sim over an ingested multi-region CSV renders byte-identically
    across a repeat run, both event queues and both execute paths."""
    from repro.core.api import CarbonEdgeEngine

    node_zones = {n.name: ("DE", "FR")[i % 2]
                  for i, n in enumerate(PAPER_NODES)}
    ts = np.round(np.linspace(0.02, 2.9, 60), 4)

    def one(event_queue, batch_execute):
        provider = TraceProvider.from_csv(CSV_ISO, node_zones=node_zones)
        cluster = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
        cluster.profile(250.0)
        engine = CarbonEdgeEngine(cluster, mode="green",
                                  provider=provider,
                                  batch_execute=batch_execute)
        drv = AsyncEngineDriver(
            engine, TraceReplayArrivals(ts), lambda uid, hour: TASK,
            horizon_hours=3.0, max_batch=8, batch_window_hours=0.01,
            tick_hours=0.5, event_queue=event_queue)
        return drv.run().to_text()

    ref = one("calendar", True)
    assert one("calendar", True) == ref
    assert one("heap", True) == ref
    assert one("calendar", False) == ref
