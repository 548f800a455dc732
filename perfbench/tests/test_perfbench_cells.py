"""Each path driven end to end on the CPU at a size a test can hold: a
sound run is correct; the control (the reference one precision below the
configuration's, in the program's place) and each fault a cell can have,
planted in the timed path, come out not correct. The device check is the
only part of a run skipped. One chip, so no exchange between chips can be
left out."""
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from perfbench import harness  # noqa: E402
from perfbench.control import readings  # noqa: E402

DATA = ROOT / "perfbench" / "tests" / "data"
CELLS = {
    "metro-distinct": ("metro-distinct", "tiny-fleet.json", "tiny-distinct.json", 0.5),
    "qwen3-chat": ("qwen3-chat", "tiny-qwen3.json", "tiny-chat.json", 0.3),
    "qwen3-longprompt": ("qwen3-longprompt", "tiny-qwen3.json", "tiny-longprompt.json", 0.3),
}

def _load(name):
    return json.loads((DATA / name).read_text())


def run(cell, seed=11):
    workload, cfg, mix, secs = CELLS[cell]
    return harness.run_cell(workload, seed, secs, False,
                            time.perf_counter(), require_tpu=False,
                            config_override=_load(cfg),
                            traffic_override=_load(mix))


@pytest.mark.parametrize("cell", list(CELLS))
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2


# the number the control has to fail in each cell
CONTROL_FAILS = {"metro-distinct": "placement_gap", "qwen3-chat": "token_gap",
                 "qwen3-longprompt": "token_gap"}


@pytest.mark.parametrize("cell", list(CELLS))
def test_control_is_not_correct(cell):
    # a window of 0 s holds exactly one batch or step, so the requests
    # compared do not depend on how fast the host ran
    workload, cfg, mix, _ = CELLS[cell]
    config = _load(cfg)
    (line,) = readings(workload, [5], 0.0, require_tpu=False, config=config,
                       mix=_load(mix))
    lim = config["limits"]
    assert all(v <= lim[k] for k, v in line["program"].items()
               if k in lim), line
    key = CONTROL_FAILS[cell]
    assert line["control"][key] > lim[key], line


# -- faults planted in the timed path ----------------------------------------

SCHEDULER = ["metro-distinct"]
SERVING = ["qwen3-chat", "qwen3-longprompt"]


@pytest.mark.parametrize("cell", SCHEDULER)
def test_scheduler_state_unchanged(monkeypatch, cell):
    from repro.core.carbon import CarbonMonitor
    from repro.core.cluster import EdgeCluster, TaskResult

    monkeypatch.setattr(EdgeCluster, "execute_batch",
                        lambda self, names, base, **kw:
                        [TaskResult(n, 0.0, 0.0, 0.0) for n in names])
    monkeypatch.setattr(CarbonMonitor, "record_energy_batch",
                        lambda self, regions, e, **kw: None)
    out = run(cell)
    assert not out["correct"]
    assert out["checks"]["billing_rel_err"]["value"] > 0.5


@pytest.mark.parametrize("cell", SCHEDULER)
def test_scheduler_half_batch_left_out(monkeypatch, cell):
    from repro.core.api import CarbonEdgeEngine

    step = CarbonEdgeEngine.step

    def half(self, *a, **k):
        b = self.batch_size or len(self.queue)
        batch = self.queue[:b]
        self.queue = batch[:len(batch) // 2] + self.queue[b:]
        return step(self, *a, **k)

    monkeypatch.setattr(CarbonEdgeEngine, "step", half)
    out = run(cell)
    assert not out["correct"] and out["failed"] > 0


@pytest.mark.parametrize("cell", SCHEDULER)
def test_scheduler_answer_altered(monkeypatch, cell):
    from repro.core.policy import VectorizedPolicy

    select = VectorizedPolicy._select_from_features

    def altered(self, F, names, weights):
        out = select(self, F, names, weights)
        return [names[(names.index(n) + 1) % len(names)] for n in out]

    monkeypatch.setattr(VectorizedPolicy, "_select_from_features", altered)
    out = run(cell)
    assert not out["correct"]
    assert out["checks"]["placement_gap"]["value"] > 1e-3


@pytest.mark.parametrize("cell", SERVING)
def test_serving_state_unchanged(monkeypatch, cell):
    from repro.runtime import steps

    decode_fn = steps.decode_fn

    def stale(cfg):
        f = decode_fn(cfg)
        return lambda params, cache, tok, pos: (f(params, cache, tok, pos)[0],
                                                cache)

    monkeypatch.setattr(steps, "decode_fn", stale)
    out = run(cell)
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > 0.02


@pytest.mark.parametrize("cell", SERVING)
def test_serving_half_batch_left_out(monkeypatch, cell):
    from repro.runtime.serving import ServingEngine

    run_batch = ServingEngine.run_batch

    def half(self, *a, **k):
        n = min(self.batch_size, len(self.queue))
        self.queue = self.queue[:n // 2] + self.queue[n:]
        return run_batch(self, *a, **k)

    monkeypatch.setattr(ServingEngine, "run_batch", half)
    out = run(cell)
    assert not out["correct"] and out["failed"] > 0


@pytest.mark.parametrize("cell", SERVING)
def test_serving_token_altered(monkeypatch, cell):
    import jax.numpy as jnp

    from repro.runtime import steps

    def altered(logits):
        return ((jnp.argmax(logits, -1) + 1) % logits.shape[-1]).astype(
            jnp.int32)

    monkeypatch.setattr(steps, "greedy_sample", altered)
    out = run(cell)
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > 0.02
