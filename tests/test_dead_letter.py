"""Requeue-loop guard & fleet-surgery robustness (DESIGN.md §10).

Satellite regressions for two pre-resilience engine traps:

1. A permanently infeasible (or unknown-node) task at the queue head used
   to bounce between ``step()``'s requeue handler and the next drain
   forever — every ``run()`` call an infinite raise/requeue loop. Now the
   ``max_requeues``-th consecutive failure of the same head task consumes
   it as a ``("dead", reason)`` outcome and the drain proceeds. Verified
   on both execute paths, for both failure shapes (NoFeasibleNodeError,
   provider/unknown-node KeyError), on the tenancy path, and through
   ``run()``/``run_until``.

2. ``Cluster.remove_node`` while tasks are queued/deferred against the
   removed node: stale placements must re-place (resilience) or
   dead-letter (bare engine) instead of KeyError-looping — including a
   ``pop_ripe`` wake that resubmits a parked task after its target died.
"""
import numpy as np
import pytest

from repro.core.api import CarbonEdgeEngine, NoFeasibleNodeError
from repro.core.cluster import EdgeCluster, NodeSpec, PAPER_NODES
from repro.core.providers import StaticProvider
from repro.core.scheduler import Task
from repro.resilience import Resilience


def fresh_cluster():
    c = EdgeCluster(nodes=PAPER_NODES, host_power_w=142.0)
    c.profile(250.0)
    return c


class PinnedPolicy:
    """Always place on one fixed node name — stale placements on demand."""

    name = "pinned"

    def __init__(self, node):
        self.node = node

    def select_batch(self, cluster, tasks, weights, provider=None,
                     now_hour=0.0):
        return [self.node] * len(tasks)


# ---------------------------------------------------------------------------
# 1. requeue-loop guard
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch_execute", [True, False])
def test_infeasible_head_dead_letters_after_max_requeues(batch_execute):
    eng = CarbonEdgeEngine(fresh_cluster(), batch_execute=batch_execute,
                           max_requeues=3)
    bad = Task(cpu=99.0, base_latency_ms=5.0)
    good = Task(cpu=0.05, mem_mb=8.0)
    eng.submit_many([bad, good])
    for _ in range(2):                      # first max_requeues-1 raise
        with pytest.raises(NoFeasibleNodeError):
            eng.step()
        assert eng.queue[0] is bad          # requeued at the head
    out = eng.step()                        # cap reached: consumed
    assert out == []
    assert eng.last_outcomes[0][0] == "dead"
    assert len(eng.dead_letters) == 1 and eng.dead_letters[0][0] is bad
    assert eng.queue == [good]
    assert len(eng.step()) == 1             # drain proceeds normally
    rep = eng.report()
    assert rep["outcomes"]["dead"] == 1 and rep["outcomes"]["done"] == 1
    assert rep["resilience"]["dead_letters"] == 1


@pytest.mark.parametrize("batch_execute", [True, False])
def test_unknown_node_head_dead_letters(batch_execute):
    c = fresh_cluster()
    eng = CarbonEdgeEngine(c, policy=PinnedPolicy("ghost"),
                           batch_execute=batch_execute, max_requeues=2)
    eng.submit_many([Task(cpu=0.05, mem_mb=8.0) for _ in range(3)])
    with pytest.raises(KeyError):
        eng.step()
    assert eng.step() == []                 # head dead-lettered
    assert eng.last_outcomes[0][0] == "dead"
    assert "KeyError" in eng.last_outcomes[0][1]
    assert len(eng.queue) == 2


def test_run_terminates_instead_of_looping_forever():
    """The old engine would raise/requeue the same head forever; with the
    cap, repeated run() calls make monotone progress to completion."""
    eng = CarbonEdgeEngine(fresh_cluster(), max_requeues=2)
    tasks = [Task(cpu=99.0), Task(cpu=0.05, mem_mb=8.0), Task(cpu=99.0)]
    eng.submit_many(tasks)
    raises = 0
    for _ in range(20):
        if not eng.queue:
            break
        try:
            eng.run()
        except NoFeasibleNodeError:
            raises += 1
    assert not eng.queue
    assert raises == 2                      # one pre-cap raise per bad task
    assert len(eng.dead_letters) == 2
    assert eng.report()["outcomes"]["done"] == 1


def test_streak_resets_for_new_head():
    """The counter tracks one task identity: a different failing task
    restarts the streak rather than inheriting the predecessor's."""
    eng = CarbonEdgeEngine(fresh_cluster(), max_requeues=3)
    bad1, bad2 = Task(cpu=99.0), Task(cpu=98.0)
    eng.submit_many([bad1])
    for _ in range(2):
        with pytest.raises(NoFeasibleNodeError):
            eng.step()
    eng.queue = [bad2] + eng.queue          # surgery: new head mid-streak
    with pytest.raises(NoFeasibleNodeError):
        eng.step()                          # bad2 streak = 1, not 3
    assert not eng.dead_letters


def test_max_requeues_validation():
    with pytest.raises(ValueError):
        CarbonEdgeEngine(fresh_cluster(), max_requeues=0)


def test_tenancy_head_dead_letters_and_uncounts():
    from repro.tenancy import TenantPolicy, TenantRegistry, TenantSpec
    from repro.tenancy.spec import TenantTask
    reg = TenantRegistry([TenantSpec("a")])
    eng = CarbonEdgeEngine(fresh_cluster(),
                           policy=TenantPolicy(registry=reg),
                           max_requeues=2)
    bad = TenantTask(cpu=99.0, tenant="a")
    good = TenantTask(cpu=0.05, mem_mb=8.0, tenant="a")
    eng.submit_many([bad, good])
    with pytest.raises(NoFeasibleNodeError):
        eng.step()
    assert eng.step() == []
    kinds = [o[0] for o in eng.last_outcomes]
    assert kinds[0] == "dead"
    # the survivor parks as an immediate retry (outcome-aligned), the
    # dead/retried tasks' admissions were reversed
    assert kinds[1] == "retry"
    assert int(reg.admitted[0]) == 0
    eng.submit_many(eng.pop_ripe(0.0))
    assert len(eng.step()) == 1
    assert int(reg.admitted[0]) == 1


# ---------------------------------------------------------------------------
# 2. remove_node with queued / deferred work
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch_execute", [True, False])
def test_remove_node_mid_stream_dead_letters_stale_placements(batch_execute):
    c = fresh_cluster()
    eng = CarbonEdgeEngine(c, policy=PinnedPolicy("node-green"),
                           batch_execute=batch_execute, max_requeues=2)
    eng.submit_many([Task(cpu=0.05, mem_mb=8.0) for _ in range(2)])
    assert len(eng.step()) == 2             # sanity: placements work
    c.remove_node("node-green")
    eng.submit_many([Task(cpu=0.05, mem_mb=8.0) for _ in range(2)])
    with pytest.raises(KeyError):
        eng.step()
    assert eng.step() == []                 # no KeyError loop: dead-letter
    assert eng.last_outcomes[0][0] == "dead"
    assert len(eng.queue) == 1


def test_remove_node_with_resilience_fails_over():
    """With resilience attached the stale placement is a contact failure:
    the batch re-places onto surviving nodes, nothing raises."""
    c = fresh_cluster()
    res = Resilience()
    eng = CarbonEdgeEngine(c, resilience=res)
    eng.submit_many([Task(cpu=0.05, mem_mb=8.0) for _ in range(2)])
    pref = eng.step()[0].node
    c.remove_node(pref)
    res.node_down(pref, detected=False)     # injector's view of the crash
    eng.submit_many([Task(cpu=0.05, mem_mb=8.0) for _ in range(3)])
    out = eng.step(0.1)
    assert len(out) == 3
    assert all(r.node != pref and r.node in c.nodes for r in out)


def test_pop_ripe_wake_onto_removed_node():
    """A parked task whose wake arrives after its only viable node was
    removed: resubmission must re-place (resilience) rather than crash."""
    c = fresh_cluster()
    res = Resilience()
    eng = CarbonEdgeEngine(c, resilience=res)
    t = Task(cpu=0.05, mem_mb=8.0)
    eng.deferred.append((0.5, t))           # parked before the surgery
    c.remove_node("node-green")
    res.node_down("node-green", detected=False)
    ripe = eng.pop_ripe(0.6)
    assert ripe == [t]
    eng.submit_many(ripe)
    out = eng.step(0.6)
    assert len(out) == 1 and out[0].node in c.nodes


def test_remove_node_keeps_mask_consistent():
    """Removing a node that was masked down must not leave a stale mask
    column misaligned with the rebuilt topology."""
    c = fresh_cluster()
    res = Resilience()
    eng = CarbonEdgeEngine(c, resilience=res)
    res.node_down("node-medium")
    c.remove_node("node-high")
    cache = c.feature_cache()
    assert cache.n == 2
    assert cache.avail is not None and len(cache.avail) == 2
    assert not cache.avail[cache.index["node-medium"]]
    res.node_up("node-medium")
    assert cache.avail is None
