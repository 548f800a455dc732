"""Serving cells: ``ServingEngine.run_batch`` behind a ``GreenRouter``.

Each batch goes through ``GreenRouter.route`` (Eq. 3 over the pods, the
fused select kernel at its (8, 8) bucket on the chip), the jitted prefill
(``flash_attention``), one jitted decode per further token
(``decode_attention``, a host sync per token) and ``CarbonMonitor``
billing per step. The traffic is a closed loop of static batches: the
next batch is submitted when the last one returns.

The harness wraps the engine's jitted prefill and decode callables to
stamp the host clock as the engine calls them: the decode for token t is
called just after the engine's own sync of token t, so no sync is added.

``check`` takes a sample of the window's requests, drawn from the seed,
with the longest among them, runs ``reference/qwen3.py`` in float32 over
each prompt and its served tokens, and compares how far each served token
lies below the reference's best logit; it also replays every routing
decision through ``reference/eq3.py`` in float64.
"""
from __future__ import annotations

import shutil
import sys
import time
from typing import Dict, List

import numpy as np

from perfbench import trace as trace_mod
from perfbench import traffic
from perfbench.reference import eq3, qwen3

REF_SEQ_MULTIPLE = 512


def model_config(config: Dict):
    """The program's ``ModelConfig`` for the published ``config.json``
    keys in the configuration file."""
    from repro.configs.base import ModelConfig

    m = config["model"]
    if m["model_type"] != "qwen3":
        raise ValueError(f"no mapping for model_type {m['model_type']!r}")
    return ModelConfig(
        name=config["name"], arch_type="dense",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        qk_norm=True, qkv_bias=m["attention_bias"],
        rope_theta=float(m["rope_theta"]),
        tie_embeddings=m["tie_word_embeddings"], norm_eps=m["rms_norm_eps"],
        act=m["hidden_act"], dtype=m["torch_dtype"],
        param_dtype=m["torch_dtype"])


# The axes each matrix contracts with its input (the fan-in).
FAN_IN_AXES = {"wq": ("embed",), "wk": ("embed",), "wv": ("embed",),
               "wo": ("heads", "head_dim"), "w_up": ("embed",),
               "w_gate": ("embed",), "w_down": ("ff",)}


def make_weights(cfg, seed: int, norm_scale: float):
    """Random weights in the served dtype, made on the device in one jitted
    call from the seed: leaf i is drawn from fold_in(key(seed), i), over
    the shapes of the program's parameter tree. Projections are
    N(0, 1/fan_in), so activations keep their scale through the stack and
    each token depends on its context (a stale cache changes the tokens);
    the embedding keeps the tree's own N(0, 0.02); RMSNorm gains (stored as
    offsets from 1) are N(0, ``norm_scale``)."""
    import jax
    import jax.numpy as jnp

    from repro.models import common, transformer

    spec = transformer.model_spec(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        spec, is_leaf=lambda x: isinstance(x, common.ParamSpec))
    shapes = []
    for path, p in flat:
        leaf = getattr(path[-1], "key", None)
        if leaf in FAN_IN_AXES:
            fan_in = int(np.prod([d for d, ax in zip(p.shape, p.axes)
                                  if ax in FAN_IN_AXES[leaf]]))
            scale = fan_in ** -0.5
        else:
            scale = p.scale if p.init == "normal" else norm_scale
        shapes.append((p.shape, scale))
    shapes = tuple(shapes)
    dtype = jnp.dtype(cfg.param_dtype)

    @jax.jit
    def make(words):
        key = jax.random.PRNGKey(0)
        for i in range(words.shape[0]):
            key = jax.random.fold_in(key, words[i])
        return [(jax.random.normal(jax.random.fold_in(key, i), shape,
                                   jnp.float32) * scale).astype(dtype)
                for i, (shape, scale) in enumerate(shapes)]

    words = np.array([seed >> s & 0xFFFFFFFF for s in (0, 32, 64)], np.uint32)
    return jax.tree_util.tree_unflatten(treedef, make(words))


def timed_router(pods, mode):
    """A ``GreenRouter`` that times each ``route`` call and keeps the pod
    state it decided on, for the route check."""
    from repro.core.router import GreenRouter, PodSpec

    class TimedRouter(GreenRouter):
        def route(self, task=None, now_hour=0.0):
            import jax

            snap = [(st.load, st.avg_time_ms, st.running, st.mem_used_mb)
                    for st in self.cluster.nodes.values()]
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.route"):
                choice = super().route(task, now_hour)
            self.records.append((time.perf_counter() - t0, snap, choice))
            return choice

    r = TimedRouter([PodSpec(**p) for p in pods], mode=mode)
    r.records = []
    return r


class Cell:
    def __init__(self, config: Dict, mix: Dict, seed: int, trace: bool,
                 seconds: float):
        from repro.core import costmodel, energy
        from repro.runtime.serving import ServingEngine

        self.config, self.mix, self.seed, self.trace = config, mix, seed, trace
        self.seconds = seconds
        sv = config["serving"]
        self.cfg = model_config(config)
        self.params = make_weights(self.cfg, seed, sv["norm_scale"])
        self.router = timed_router(sv["pods"], sv["mode"])
        # as the serving launcher does: seed each pod's history with the
        # roofline time of a decode step at the traffic's shortest prompt
        B = mix["batch"]
        terms = energy.roofline(
            2.0 * self.cfg.active_param_count() * B,
            costmodel.step_hbm_bytes(self.cfg,
                                     min(mix["prompt_len"]["values"]), B,
                                     "decode"),
            0.0, chips=sv["pods"][0]["chips"])
        self.router.seed_profile({p["name"]: terms for p in sv["pods"]})
        self.eng = ServingEngine(self.cfg, self.params, self.router,
                                 max_len=traffic.max_context(mix) + 8,
                                 batch_size=B)
        self._stamps = {"prefill": None, "decode": []}
        self._wrap()
        self._uid = 0
        rng = traffic.rng_for(seed, "warm")
        for L in sorted(set(mix["prompt_len"]["values"])):
            prompts = rng.integers(0, self.cfg.vocab_size, (B, L),
                                   dtype=np.int32)
            self._batch(L, prompts, [2] * B)
        self.router.records.clear()

    def _wrap(self) -> None:
        import jax

        eng, stamps = self.eng, self._stamps
        prefill, decode = eng._prefill, eng._decode

        def timed_prefill(*a, **k):
            stamps["prefill"] = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.prefill"):
                return prefill(*a, **k)

        def timed_decode(*a, **k):
            stamps["decode"].append(time.perf_counter())
            with jax.profiler.TraceAnnotation("bench.decode"):
                return decode(*a, **k)

        eng._prefill, eng._decode = timed_prefill, timed_decode

    def _batch(self, L: int, prompts: np.ndarray, outs: List[int]) -> Dict:
        import jax

        from repro.runtime.serving import Request

        self._stamps["decode"] = []
        uids = list(range(self._uid, self._uid + len(outs)))
        self._uid += len(outs)
        for uid, p, m in zip(uids, prompts, outs):
            self.eng.submit(Request(uid=uid, prompt=p, max_new_tokens=m))
        with jax.profiler.TraceAnnotation("bench.batch"):
            comps = self.eng.run_batch()
        t_end = time.perf_counter()
        by_uid = {c.uid: c for c in comps}
        comps = [by_uid.get(u) for u in uids]
        return {"L": L, "outs": outs, "prompts": prompts,
                "tokens": [None if c is None else c.tokens for c in comps],
                "service_s": [None if c is None else c.service_s
                              for c in comps],
                "t_prefill": self._stamps["prefill"],
                "t_decode": list(self._stamps["decode"]), "t_end": t_end}

    def window(self, trace_dir=None) -> Dict:
        import contextlib

        import jax

        mix = self.mix
        n_max = mix["trace_batches"] if trace_dir is not None else 10 ** 6
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        source = traffic.serve_batches(mix, self.seed, self.cfg.vocab_size)
        batches = []
        ctx = (trace_mod.capture(trace_dir) if trace_dir is not None
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ctx:
            with jax.profiler.TraceAnnotation("bench.window"):
                for L, prompts, slot in source:
                    batches.append(self._batch(L, prompts, slot))
                    if (len(batches) >= n_max
                            or time.perf_counter() - t0 >= self.seconds):
                        break
        window_s = batches[-1]["t_end"] - t0
        rec = {"path": "serving", "batches": batches, "window_s": window_s,
               "model": self.config["model"]}
        gaps, useful, failed, attempted = [], 0, 0, 0
        for b in batches:
            times = self._token_times(b)
            for toks, m in zip(b["tokens"], b["outs"]):
                attempted += 1
                if toks is None or len(toks) != m:
                    failed += 1
                    continue
                useful += m
                gaps.extend(np.diff(times[:m]).tolist())
        rec.update(token_gaps_s=gaps, tokens_useful=useful, failed=failed,
                   attempted=attempted,
                   route_s=[r[0] for r in self.router.records])
        if trace_dir is not None:
            rec["trace"] = trace_mod.reduce(trace_mod.find_xplane(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
        return rec

    @staticmethod
    def _token_times(b: Dict) -> np.ndarray:
        """Host time at which each token position reached the host: token t
        at the engine's decode call t, the batch's last token at the end of
        the longest request's service."""
        longest = max(b["outs"])
        t = list(b["t_decode"][:longest - 1])
        done = [s for s in b["service_s"] if s is not None]
        t.append(b["t_prefill"] + max(done) if done else np.nan)
        return np.asarray(t)

    # -- correctness ------------------------------------------------------
    def _picked(self, rec: Dict):
        """The requests compared: the window's longest and, drawn from the
        seed, ``check_requests - 1`` others; as (prompt, served tokens)."""
        reqs = [(b["prompts"][i], b["tokens"][i])
                for b in rec["batches"] for i in range(len(b["outs"]))
                if b["tokens"][i]]
        rng = traffic.rng_for(self.seed, "check")
        longest = max(range(len(reqs)), key=lambda i: len(reqs[i][1]))
        others = [i for i in range(len(reqs)) if i != longest]
        k = min(len(others), self.mix["check_requests"] - 1)
        pick = [longest] + sorted(rng.choice(others, k, replace=False).tolist())
        return [reqs[i] for i in pick]

    def _token_gap(self, rec: Dict, control: bool) -> float:
        import jax.numpy as jnp

        tokens, positions, served = self._ref_inputs(self._picked(rec))
        gaps, _ = qwen3.token_gaps(self.params, self.config["model"],
                                   jnp.asarray(tokens),
                                   jnp.asarray(positions),
                                   jnp.asarray(np.maximum(served, 0)),
                                   control=control)
        valid = served >= 0
        print(f"reference: {len(tokens)} requests, {int(valid.sum())} served "
              f"tokens compared", file=sys.stderr)
        return float(np.max(np.where(valid, gaps, -np.inf)))

    def check(self, rec: Dict) -> Dict:
        lim = self.config["limits"]
        return {"token_gap": {"value": self._token_gap(rec, False),
                              "limit": lim["token_gap"]},
                "route_gap": {"value": self._route_gap(),
                              "limit": lim["route_gap"]}}

    def control(self, rec: Dict) -> Dict:
        """The control: the reference with float8 matrix products, the
        precision below the configuration's bfloat16, in the program's
        place; and the route replayed in bfloat16."""
        import ml_dtypes

        return {"token_gap": self._token_gap(rec, True),
                "route_gap": self._route_gap(ml_dtypes.bfloat16)}

    def _ref_inputs(self, reqs):
        """Token rows padded to one fixed length, the positions whose
        logits chose each served token, and the served tokens (-1 pads)."""
        mix = self.mix
        S = traffic.ceil_to(traffic.max_context(mix), REF_SEQ_MULTIPLE)
        P = traffic.max_output(mix)
        tokens = np.zeros((len(reqs), S), np.int32)
        positions = np.zeros((len(reqs), P), np.int32)
        served = np.full((len(reqs), P), -1, np.int32)
        for r, (prompt, toks) in enumerate(reqs):
            L, n = len(prompt), len(toks)
            tokens[r, :L] = prompt
            tokens[r, L:L + n] = toks
            positions[r, :n] = L - 1 + np.arange(n)
            served[r, :n] = toks
        return tokens, positions, served

    def _route_gap(self, dtype=None) -> float:
        """Widest float64 Eq. 3 gap between the best pod and the routed pod
        over every routing decision of the window; with ``dtype``, between
        the best pod and the one Eq. 3 in that precision would pick."""
        sv = self.config["serving"]
        pods = sv["pods"]
        names = [p["name"] for p in pods]
        w = np.array([sv["weights"][k] for k in
                      ("w_r", "w_l", "w_p", "w_b", "w_c")])
        worst = 0.0
        for _, snap, choice in self.router.records:
            fleet = {"cpu": np.ones(len(pods)),
                     "mem_mb": np.full(len(pods), float(sv["pod_mem_mb"])),
                     "intensity": np.array([p["carbon_intensity"]
                                            for p in pods]),
                     "load": np.array([s[0] for s in snap]),
                     "avg_time_ms": np.array([s[1] for s in snap]),
                     "running": np.array([s[2] for s in snap], float),
                     "mem_used_mb": np.array([s[3] for s in snap]),
                     "power_w": np.array([p["chips"] * p["chip_power_w"]
                                          for p in pods])}
            kw = dict(latency_threshold_ms=sv["latency_threshold_ms"],
                      load_threshold=sv["load_threshold"])
            s = eq3.scores(fleet, np.zeros(1), np.zeros(1), w, **kw)[0]
            if dtype is not None:
                choice = names[int(np.argmax(eq3.scores(
                    fleet, np.zeros(1), np.zeros(1), w, dtype=dtype, **kw)[0]))]
            got = s[names.index(choice)] if choice in names else -np.inf
            worst = max(worst, float(s.max() - got))
        return worst
