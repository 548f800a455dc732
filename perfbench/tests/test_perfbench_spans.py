"""The program's spans on the device trace's clock (``perfbench/spans.py``)
and the readers of the ``select.*`` spans.

``data/spans.xplane.pb`` was recorded on one TPU v5e chip: three
``select_best_fused`` launches at the (8, 1024) bucket through
``VectorizedPolicy._select_pallas_fused`` and its ``carbonedge.select.*``
spans, inside ``bench.window``, with the padding slowed by a sleep of 4, 8
and 12 ms. ``data/small.xplane.pb`` holds ``bench.*`` spans only."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from perfbench import harness, spans, trace  # noqa: E402

DATA = ROOT / "perfbench" / "tests" / "data"
SMALL = DATA / "small.xplane.pb"
SPANS = DATA / "spans.xplane.pb"
SELECT = ("select.pad", "select.put", "select.launch", "select.fetch")


def test_label_prefers_innermost_program_span():
    ivs = [(0, 100, "bench.window"), (10, 40, "carbonedge.score"),
           (12, 30, "carbonedge.select.pad"), (50, 90, "bench.batch"),
           (60, 70, "carbonedge.serve.sync")]
    assert trace._label(ivs, 20) == "carbonedge.select.pad"
    assert trace._label(ivs, 35) == "carbonedge.score"
    assert trace._label(ivs, 65) == "carbonedge.serve.sync"
    assert trace._label(ivs, 80) == "bench.batch"
    assert trace._label(ivs, 45) == "bench.window"


def test_host_spans_clip_to_window_and_skip_bench():
    ivs = [(0, 1000, "bench.window"), (-500, 500, "carbonedge.score"),
           (100, 300, "carbonedge.select.pad"),
           (600, 700, "carbonedge.select.pad"),
           (900, 1500, "carbonedge.serve.sync"),
           (2000, 2100, "carbonedge.serve.sync")]
    got = spans.host_spans(ivs, (0, 1000))
    assert got == {
        "carbonedge.score": {"count": 1, "seconds": pytest.approx(500e-9)},
        "carbonedge.select.pad": {"count": 2,
                                  "seconds": pytest.approx(300e-9)},
        "carbonedge.serve.sync": {"count": 1,
                                  "seconds": pytest.approx(100e-9)}}


def test_window_from_bench_spans_and_modules_only():
    ivs = [(10, 90, "bench.window"), (0, 200, "carbonedge.score")]
    assert spans.window(ivs, [[(20, 95, "jit_x")]]) == (10, 95)


def test_labelled_gaps_on_hand_made_intervals():
    ivs = [(0, 100, "bench.window"), (20, 60, "carbonedge.select.pad")]
    mods = [[(10, 20, "jit_a"), (60, 70, "jit_a"), (65, 80, "jit_b")]]
    assert spans.labelled_gaps(ivs, mods, (0, 100)) == [
        (10e-9, "bench.window"), (40e-9, "carbonedge.select.pad"),
        (20e-9, "bench.window")]


def test_trace_reduce_on_small_trace_unchanged():
    """The values ``trace.reduce`` gives on the fixture, pinned."""
    red = trace.reduce(str(SMALL))
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.030848175, rel=1e-12)
    assert red["busy_s"] == pytest.approx(7.9948e-05, rel=1e-12)
    (name,) = red["modules"]
    assert name == "jit_select_best_fused(7528991152297237257)"
    mod = red["modules"][name]
    assert (mod["count"], mod["kernels"]) == (3, ["select_best_fused"])
    assert mod["total_s"] == pytest.approx(7.9948e-05, rel=1e-12)
    assert red["kernels"] == {
        "select_best_fused": pytest.approx(6.7448e-05, rel=1e-12)}
    assert red["gaps_s"] == pytest.approx(
        [0.005303714, 0.010074877, 0.015389636], rel=1e-12)
    assert [n for n, _ in red["breakdown"]["device_ops"]] == [
        "select_best_fused.1 = (s32[8,1,1]", "copy.1 = f32[8,1024,8]",
        "reduce = s32[8]", "reduce.1 = f32[8]", "copy-start = (f32[8]",
        "copy-done = f32[8]"]
    assert red["breakdown"]["idle_gaps"] == [
        ["bench.host", pytest.approx(g, rel=1e-12)]
        for g in (0.015389636, 0.010074877, 0.005303714)]


def test_spans_reduce_agrees_with_trace_reduce_without_program_spans():
    red, sp = trace.reduce(str(SMALL)), spans.reduce(str(SMALL))
    assert sp["window_s"] == red["window_s"]
    assert sp["idle_gaps"] == red["breakdown"]["idle_gaps"]
    assert sp["host_spans"] == {}


def test_recorded_gaps_carry_program_spans():
    """On the chip the slowed padding is what the device waits for: the
    three longest gaps lie inside ``carbonedge.select.pad``, and each
    ``select.*`` span is counted once per launch."""
    sp = spans.reduce(str(SPANS))
    top = sp["idle_gaps"][:3]
    assert [label for label, _ in top] == ["carbonedge.select.pad"] * 3
    for (_, g), sleep in zip(top, (0.012, 0.008, 0.004)):
        assert sleep <= g < sleep + 0.005
    for phase in SELECT:
        assert sp["host_spans"]["carbonedge." + phase]["count"] == 3
    pad = sp["host_spans"]["carbonedge.select.pad"]["seconds"]
    assert 0.024 <= pad < sp["window_s"]
    # the harness's own reduction still sees only its bench.* span there
    red = trace.reduce(str(SPANS))
    assert red["window_s"] == sp["window_s"]
    assert {label for label, _ in red["breakdown"]["idle_gaps"]} == {
        "bench.window"}


def _sched_rec(**spans_s):
    return {"path": "scheduler", "steps": [(0.0, 1.0, 4), (1.0, 2.5, 4)],
            "spans": {p: {"count": 10, "total_s": s}
                      for p, s in spans_s.items()}}


@pytest.mark.parametrize("metric,phase", [
    ("select_pad_ms.distinct", "select.pad"),
    ("select_put_ms.distinct", "select.put"),
    ("select_fetch_ms.distinct", "select.fetch")])
def test_select_span_readers(metric, phase):
    read = harness.metric_reader(metric)
    rec = _sched_rec(score=1.0)
    rec["spans"][phase] = {"count": 20, "total_s": 0.6}
    assert read(rec) == pytest.approx(300.0)       # 0.6 s over 2 steps
    assert read(_sched_rec(score=1.0)) is None      # a tree without it
    assert read({"path": "scheduler", "steps": [(0.0, 1.0, 4)]}) is None
    assert read({"path": "serving", "batches": []}) is None
