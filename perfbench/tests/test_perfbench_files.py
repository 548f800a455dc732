"""Everything ``BENCHMARK.json`` names is found by name, and ``run.py``
refuses to time anything but a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from perfbench import harness, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + WORKLOADS + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            target = next(x for x in BENCH["end_to_end"]
                          if x["name"] == m["moves"])
            assert w in target.get("workloads", WORKLOADS)
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
    for w in WORKLOADS:
        assert harness.cell_metrics(BENCH, w, True), w
        assert len(harness.cell_metrics(BENCH, w, False)) >= 2, w


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_files_load_by_name(name):
    wl, cfg = harness.cell_entries(BENCH, name)
    config = harness.load_json(cfg["file"])
    assert config["name"] == cfg["name"]
    assert (ROOT / "perfbench" / "paths" / f"{config['path']}.py").exists()
    mix = traffic.load(wl["traffic"])
    assert mix["name"] == wl["traffic"]
    for m in harness.cell_metrics(BENCH, name, False) + \
            harness.cell_metrics(BENCH, name, True):
        # a reader that finds nothing to read returns nothing
        assert harness.metric_reader(m["name"])({"path": None}) is None


def test_setup_reader():
    assert harness.metric_reader("setup_s")({"setup_s": 12.5}) == 12.5


def test_percentile_counts_misses():
    assert harness.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
    assert harness.percentile([1.0] * 19 + [float("inf")], 95) == \
        float("inf")
    assert harness.percentile(list(range(101)), 95) == 95.0


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "metro-distinct",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no CPU fallback" in p.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
