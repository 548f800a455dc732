"""The reduction from a profiler trace to busy time, per-kernel time and
labelled idle gaps, on a small trace recorded on one TPU v5e chip
(``data/small.xplane.pb``: three ``select_best_fused`` launches at the
(8, 1024) shape, each followed by a ``bench.host`` sleep of 4, 8 and
12 ms, all inside ``bench.window``), and on hand-made intervals."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from perfbench import trace  # noqa: E402

SMALL = ROOT / "perfbench" / "tests" / "data" / "small.xplane.pb"


def test_union_and_merge():
    ivs = [(0, 10), (5, 12), (20, 25), (21, 22)]
    assert trace.union_length(ivs) == 17
    assert trace.merged(ivs) == [(0, 12), (20, 25)]


def test_self_time_of_nested_ops():
    ops = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c"),
           (120, 130, "a")]
    st = trace._self_times(ops)
    assert st == {"while": 30, "a": 30, "b": 40, "c": 10}


def test_instruction_names():
    assert trace.inst_name("%decode_attention.5 = bf16[8,16,1,128]{3,2,1,0} "
                           "custom-call(s32[1] %x)") == "decode_attention"
    assert trace.inst_name("%copy_dynamic-update-slice_fusion.4 = bf16[2]"
                           " fusion(...)") == "copy_dynamic-update-slice_fusion"
    assert trace._is_kernel("%select_best_fused.1 = (s32[128,1,1]) "
                            "custom-call(f32[128,16384,8] %f)")
    assert not trace._is_kernel("%copy.1 = f32[128] copy(f32[128] %x)")


def test_label_is_innermost_span():
    spans = [(0, 100, "bench.window"), (10, 20, "bench.step"),
             (40, 60, "bench.host")]
    assert trace._label(spans, 50) == "bench.host"
    assert trace._label(spans, 30) == "bench.window"
    assert trace._label(spans, 200) == "outside bench spans"


def test_small_recorded_trace():
    red = trace.reduce(str(SMALL))
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    (mod,) = trace.modules_with_kernel(red, "select_best_fused")
    assert mod["count"] == 3
    assert 0 < red["kernels"]["select_best_fused"] <= mod["total_s"]
    # the sleeps are the longest idle gaps: at least 12, 8 and 4 ms, each
    # with the host's few ms of dispatch around it
    top = red["breakdown"]["idle_gaps"][:3]
    assert [label for label, _ in top] == ["bench.host"] * 3
    for (_, g), sleep in zip(top, (0.012, 0.008, 0.004)):
        assert sleep <= g < sleep + 0.005
    # busy plus idle covers the window
    assert red["busy_s"] + sum(red["gaps_s"]) == pytest.approx(
        red["window_s"], rel=1e-9)
    names = [n for n, _ in red["breakdown"]["device_ops"]]
    assert any(n.startswith("select_best_fused") for n in names)
