"""Scheduler cells: ``CarbonEdgeEngine.step`` over a seeded edge fleet.

The window drives the engine as users do: ``submit_many`` then ``step``,
which runs ``VectorizedPolicy.select_batch`` (profile dedup, selection
memo, ``featurize_cached``, padding to power-of-two buckets, the Pallas
``select_best_fused`` kernel on the chip) and then ``execute_batch`` and
``CarbonMonitor`` billing. The traffic is a closed loop
(``"kind": "closed_batches"``): full batches stepped back to back.

``check`` compares a sample of the window's placements, drawn from the
seed, and every billed ledger with ``reference/eq3.py`` in float64 on the
same fleet.

The window keeps only the name of the node each task went to (a string,
which the garbage collector does not track), and set-up ends with
``gc.freeze()``: the fleet's objects live for the whole run, and a full
collection that scanned them would stall the engine, an artefact of the
harness.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import time
from typing import Dict

import numpy as np

from perfbench import trace as trace_mod
from perfbench import traffic
from perfbench.harness import stage
from perfbench.reference import eq3

CHECK_SAMPLE = 2048


def make_fleet(fleet: Dict, seed: int) -> Dict[str, np.ndarray]:
    """The fleet's per-node values, drawn from the seed (a copy of
    ``benchmarks/fleet_scale.make_fleet``'s generator, vectorised): cpu
    quota, memory and load uniform over the configuration's ranges; each
    node in one of the configuration's grid regions, as many nodes in each
    for every seed, and billed at that region's static intensity;
    profiled time ``profile_latency_ms / cpu``."""
    rng = traffic.rng_for(seed, "fleet")
    n = fleet["nodes"]
    cpu = rng.uniform(*fleet["cpu"], n)
    mem = rng.integers(*fleet["mem_mb"], n).astype(np.float64)
    regions = np.array([r["intensity_g_per_kwh"] for r in fleet["regions"]])
    inten = regions[rng.permutation(np.arange(n) % len(regions))]
    load = rng.uniform(*fleet["load"], n)
    return {"cpu": cpu, "mem_mb": mem, "intensity": inten, "load": load,
            "avg_time_ms": fleet["profile_latency_ms"] / cpu,
            "running": np.zeros(n), "mem_used_mb": np.zeros(n),
            "power_w": fleet["host_power_w"] * cpu}


class Cell:
    def __init__(self, config: Dict, mix: Dict, seed: int, trace: bool,
                 seconds: float):
        from repro.core.api import CarbonEdgeEngine
        from repro.core.cluster import EdgeCluster, NodeSpec
        from repro.core.scheduler import Weights
        from repro.obs import Observability, StepProfiler

        self.config, self.mix, self.seed, self.trace = config, mix, seed, trace
        t = time.perf_counter()
        fl = config["fleet"]
        self.fleet = make_fleet(fl, seed)
        f = self.fleet
        self.names = [f"n{i}" for i in range(fl["nodes"])]
        nodes = [NodeSpec(nm, cpu=float(c), mem_mb=int(m),
                          carbon_intensity=float(i))
                 for nm, c, m, i in zip(self.names, f["cpu"], f["mem_mb"],
                                        f["intensity"])]
        cluster = EdgeCluster(nodes=nodes, host_power_w=fl["host_power_w"],
                              distribution_overhead=fl["distribution_overhead"],
                              pue=fl["pue"])
        cluster.profile(fl["profile_latency_ms"])
        for st, ld in zip(cluster.nodes.values(), f["load"]):
            st.load = float(ld)
        t = stage("fleet and cluster", t)
        self.weights = np.array([config["weights"][k] for k in
                                 ("w_r", "w_l", "w_p", "w_b", "w_c")])
        self.profiler = StepProfiler() if trace else None
        self.eng = CarbonEdgeEngine(
            cluster, weights=Weights(*self.weights),
            batch_size=mix["batch"],
            obs=Observability(profile=self.profiler) if trace else None)
        if self.eng.policy.latency_threshold_ms != config["latency_threshold_ms"]:
            raise ValueError("the engine's latency threshold differs from "
                             "the configuration's")
        t = stage("engine", t)
        self._warm()
        t = stage("warm-up", t)
        if trace:
            seconds = min(seconds, mix["trace_seconds"])
        self.seconds = seconds
        gc.collect()
        gc.freeze()
        stage("gc", t)

    # -- set-up -----------------------------------------------------------
    def _tasks(self, prof: np.ndarray):
        from repro.core.scheduler import Task

        base = self.mix["base_latency_ms"]
        return [Task(cpu=float(c), mem_mb=float(m), base_latency_ms=base)
                for c, m in prof]

    def _warm(self) -> None:
        """Compile the kernel shapes this traffic reaches, with tasks the
        window never sees. Every row misses the memo, and the policy scores
        misses in chunks of ``_CHUNK_ELEMS // nodes`` rows, each padded to
        its power-of-two bucket: one step of one chunk per bucket that a
        batch's full chunks and its last chunk reach (at 10^4 nodes and
        batches of 1024: chunks of 104 and a last one of 88, both at the
        (128, 16384) bucket), not a whole batch of chunks."""
        pol, batch = self.eng.policy, self.mix["batch"]
        chunk = min(batch, max(1, pol._CHUNK_ELEMS // len(self.names)))
        sizes = {pol._bucket(n): n for n in (chunk, batch % chunk or chunk)}
        rng = traffic.rng_for(self.seed, "warm")
        for n in sizes.values():
            self.eng.submit_many(self._tasks(
                traffic._profiles(self.mix, rng, n))).step()
        self._ledger0 = self._ledgers()

    def _ledgers(self) -> Dict[str, np.ndarray]:
        nodes = self.eng.cluster.nodes
        regions = self.eng.monitor.regions
        get = lambda objs, attr: np.array(  # noqa: E731
            [getattr(objs[n], attr) for n in self.names], dtype=np.float64)
        return {"cluster.tasks": get(nodes, "completed"),
                "cluster.time_ms": get(nodes, "total_time_ms"),
                "cluster.energy_kwh": get(nodes, "energy_kwh"),
                "cluster.carbon_g": get(nodes, "carbon_g"),
                "monitor.tasks": get(regions, "tasks"),
                "monitor.energy_kwh": get(regions, "energy_kwh"),
                "monitor.carbon_g": get(regions, "carbon_g")}

    # -- the window -------------------------------------------------------
    def _closed_loop(self, seconds: float) -> Dict:
        eng = self.eng
        profs, placed, steps = [], [], []
        clock = time.perf_counter
        t0 = clock()
        for prof in traffic.closed_batches(self.mix, self.seed):
            tasks = self._tasks(prof)
            s0 = clock() - t0
            try:
                res = eng.submit_many(tasks).step()
            except Exception as err:        # a task the engine could not place
                print(f"engine.step raised {err!r}", file=sys.stderr)
                res = []
            s1 = clock() - t0
            profs.append(prof)
            placed.extend([r.node for r in res]
                          + [None] * (len(tasks) - len(res)))
            steps.append((s0, s1, len(res)))
            if len(res) < len(tasks) or s1 >= seconds:
                break
        return {"profiles": np.concatenate(profs), "placed": placed,
                "steps": steps, "window_s": steps[-1][1]}

    def window(self, trace_dir=None) -> Dict:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if self.profiler is not None:
            self.profiler.reset()           # drop the warm-up's spans
        ctx = (trace_mod.capture(trace_dir) if trace_dir is not None
               else contextlib.nullcontext())
        with ctx:
            import jax
            with jax.profiler.TraceAnnotation("bench.window"):
                rec = self._closed_loop(self.seconds)
        gc.unfreeze()
        rec["attempted"] = len(rec["placed"])
        rec["failed"] = sum(r is None for r in rec["placed"])
        rec["tasks_done"] = rec["attempted"] - rec["failed"]
        rec["nodes"] = len(self.names)
        rec["path"] = "scheduler"
        if self.profiler is not None:
            rec["spans"] = {p: {"count": self.profiler.count(p),
                                "total_s": self.profiler.total_s(p)}
                            for p in self.profiler.phases()}
        if trace_dir is not None:
            rec["trace"] = trace_mod.reduce(trace_mod.find_xplane(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
        return rec

    # -- correctness ------------------------------------------------------
    def _kw(self) -> Dict:
        return dict(latency_threshold_ms=self.config["latency_threshold_ms"],
                    load_threshold=self.config["load_threshold"])

    def _placed(self, rec: Dict) -> np.ndarray:
        index = {nm: i for i, nm in enumerate(self.names)}
        return np.array([-1 if r is None else index[r]
                         for r in rec["placed"]])

    def _sample(self, rec: Dict, placed: np.ndarray):
        """The tasks compared, drawn from the seed, with their profiles."""
        rng = traffic.rng_for(self.seed, "check")
        n = len(placed)
        sample = np.sort(rng.choice(n, min(n, CHECK_SAMPLE), replace=False))
        prof = rec["profiles"][sample]
        return sample, prof[:, 0], prof[:, 1]

    def _gap(self, nodes, tc, tm) -> float:
        """Widest float64 Eq. 3 gap between each task's best feasible node
        and the node it was given (infinite for an infeasible or missing
        one while a feasible node exists)."""
        f, w, kw = self.fleet, self.weights, self._kw()
        best, best_val = eq3.place(f, tc, tm, w, **kw)
        got = eq3.score_of(f, tc, tm, nodes, w, **kw)
        gap = np.where(best >= 0, best_val - got,
                       np.where(nodes >= 0, np.inf, 0.0))
        return float(gap.max(initial=0.0))

    def _billing_ref(self, placed: np.ndarray, dtype=np.float64) -> Dict:
        fl = self.config["fleet"]
        return eq3.billing(placed[placed >= 0], len(self.names),
                           self.mix["base_latency_ms"], fl["host_power_w"],
                           fl["distribution_overhead"],
                           self.fleet["intensity"], fl["pue"], dtype=dtype)

    @staticmethod
    def _rel_err(got: Dict, want: Dict) -> float:
        worst = 0.0
        for key, g in got.items():
            w = want[key.split(".", 1)[-1]]
            err = np.abs(g - w) / np.maximum(np.abs(w), 1e-300)
            worst = max(worst, float(np.where((w == 0) & (g == 0), 0.0,
                                              err).max()))
        return worst

    def check(self, rec: Dict) -> Dict:
        f = self.fleet
        placed = self._placed(rec)
        # the reference scores the fleet as generated: execution must not
        # have moved a scored column
        index = {nm: i for i, nm in enumerate(self.names)}
        drift = sum(int(st.load != f["load"][index[nm]] or st.running != 0
                        or st.mem_used_mb != 0
                        or st.avg_time_ms != f["avg_time_ms"][index[nm]])
                    for nm, st in self.eng.cluster.nodes.items())
        sample, tc, tm = self._sample(rec, placed)
        now = self._ledgers()
        billed = {k: now[k] - self._ledger0[k] for k in now}
        lim = self.config["limits"]
        return {
            "placement_gap": {"value": self._gap(placed[sample], tc, tm),
                              "limit": lim["placement_gap"]},
            "billing_rel_err": {"value": self._rel_err(
                billed, self._billing_ref(placed)),
                "limit": lim["billing_rel_err"]},
            "fleet_state_drift": {"value": float(drift), "limit": 0.0},
        }

    def control(self, rec: Dict) -> Dict:
        """The control: the reference put in the program's place, each part
        one precision below the configuration's: scoring in bfloat16 (below
        float32), billing in float32 (below float64)."""
        import ml_dtypes

        placed = self._placed(rec)
        sample, tc, tm = self._sample(rec, placed)
        nodes, _ = eq3.place(self.fleet, tc, tm, self.weights,
                             dtype=ml_dtypes.bfloat16, **self._kw())
        return {"placement_gap": self._gap(nodes, tc, tm),
                "billing_rel_err": self._rel_err(
                    self._billing_ref(placed, np.float32),
                    self._billing_ref(placed))}
