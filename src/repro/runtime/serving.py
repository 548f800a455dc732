"""Serving engine: batched request execution with carbon-aware routing.

The engine owns jitted prefill/decode step functions per model and runs
request batches; the GreenRouter (core/router.py) decides which pod/node a
batch executes on, and the CarbonMonitor bills each step's energy. On this
CPU host the "pods" are simulated domains; the step functions are the same
ones the dry-run lowers for the production mesh.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import costmodel, energy
from repro.core.router import GreenRouter
from repro.kernels.decode_attention import BLOCK_K
from repro.models import attention, transformer
from repro.obs.profiler import span
from repro.runtime import steps


@dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    # None = not yet submitted; 0.0 is a valid (virtual) submission time
    submitted_s: Optional[float] = None


@dataclass
class Completion:
    uid: int
    tokens: List[int]
    pod: str
    wait_s: float                # queue time: submit -> batch start
    service_s: float             # batch start -> this request's last token
    carbon_g: float

    @property
    def latency_s(self) -> float:
        """End-to-end: queue wait plus service (wait used to be dropped and
        every request in a batch reported the identical batch dt)."""
        return self.wait_s + self.service_s


class ServingEngine:
    """Batched prefill+decode with greedy sampling and carbon accounting.

    ``obs`` (a ``repro.obs.Observability``) with a profiler gets the
    ``serve.*`` phases of :meth:`run_batch`: route, prefill, and per token
    decode dispatch, sampling, the host's wait for the token, and billing.
    They reach a profiler trace as ``carbonedge.serve.*`` either way.

    For a model with experts the cache carries, per expert layer, the rows
    routed to each expert held here; with ``obs`` metrics on, the engine
    reads it once per batch after the last token (``serve.moe_counts``)
    into the counter ``serve.moe_rows_held`` (all rows) and the gauge
    ``serve.moe_rows_max`` (the batch's busiest held expert in one
    layer).

    The decode jit donates the cache (argument 1): each step returns the
    cache in the buffers it was given, and on one device the attention
    caches are updated in place, one position a layer
    (``transformer.cache_in_place``). A caller of ``_decode`` that keeps the
    cache it passed in must copy it first. With ``obs`` metrics on, the
    gauge ``serve.decode_cache_in_place`` says which decode path the jits
    take: 1 in place, 0 the mesh path; and ``serve.decode_attention_stacked``
    says how decode's GQA attention layers attend: 1 through
    ``decode_attention`` over the stacked K/V cache by layer index, 0
    through the jnp or mesh path (or where the model has no such layer)."""

    def __init__(self, cfg: ModelConfig, params, router: GreenRouter,
                 max_len: int = 256, batch_size: int = 4, obs=None):
        self.cfg = cfg
        self.params = params
        self.router = router
        # The KV cache rounds up to the decode kernel's block so decode can
        # take the Pallas path; slots past the decode position are masked,
        # so the extra length never changes a token.
        self.max_len = -(-max_len // BLOCK_K) * BLOCK_K
        self.batch_size = batch_size
        self.obs = obs if obs is not None and obs.enabled else None
        self._prefill = jax.jit(steps.prefill_step(cfg, self.max_len))
        self._decode = jax.jit(steps.decode_fn(cfg), donate_argnums=(1,))
        if self.obs is not None and self.obs.metrics is not None:
            self.obs.metrics.gauge(
                "serve.decode_cache_in_place",
                "1 if decode updates the attention caches in place").set(
                    float(transformer.cache_in_place()))
            self.obs.metrics.gauge(
                "serve.decode_attention_stacked",
                "1 if decode attention reads the stacked K/V cache in the "
                "kernel").set(float(
                    any(ld.kind == "attn" for ld in cfg.layer_defs)
                    and attention.decode_kernel_reads_stack(cfg,
                                                            self.max_len)))
        self.queue: List[Request] = []
        self.completions: List[Completion] = []

    # -- request lifecycle ---------------------------------------------------
    def submit(self, req: Request, now_s: Optional[float] = None):
        """``now_s`` lets a simulator stamp virtual submission time; the
        default is the wall clock (live serving)."""
        if now_s is not None:
            req.submitted_s = now_s
        elif req.submitted_s is None:
            # keep a caller-stamped submission time (sim task factories
            # pre-stamp virtual seconds; 0.0 is a valid virtual instant)
            req.submitted_s = time.perf_counter()
        self.queue.append(req)

    def _step_terms(self, kind: str, seq: int, batch: int,
                    chips: int) -> energy.RooflineTerms:
        """Roofline terms for this batch on the routed pod (billing +
        history update — must use that pod's chip count)."""
        flops = 2.0 * self.cfg.active_param_count() * batch * (seq if kind == "prefill" else 1)
        hbm = costmodel.step_hbm_bytes(self.cfg, seq, batch, kind)
        return energy.roofline(flops, hbm, 0.0, chips=chips)

    def run_batch(self, now_hour: float = 0.0,
                  now_s: Optional[float] = None) -> List[Completion]:
        """Serve up to batch_size queued requests as one batch.

        ``now_hour`` flows into routing and billing so a time-varying
        intensity provider on the router (TraceProvider/ForecastProvider)
        is sampled at the request time, not at hour 0. ``now_s`` is the
        batch start on the same clock ``submitted_s`` was stamped with
        (wall by default, virtual under the simulator) — each request's
        queue wait is ``now_s - submitted_s``, and its service time runs
        until *its own* last decoded token, so a short request in a long
        batch no longer inherits the whole batch's dt.
        """
        if not self.queue:
            return []
        batch = self.queue[: self.batch_size]
        self.queue = self.queue[self.batch_size:]
        B = len(batch)
        S = max(len(r.prompt) for r in batch)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(batch):
            toks[i, S - len(r.prompt):] = r.prompt  # left-pad
        prof = self.obs.profiler if self.obs is not None else None
        with span(prof, "serve.route"):
            pod = self.router.route(now_hour=now_hour)
        chips = self.router.pods[pod].chips
        t0 = time.perf_counter()
        start_s = t0 if now_s is None else now_s
        with span(prof, "serve.prefill"):
            cache, logits = self._prefill(self.params,
                                          {"tokens": jnp.asarray(toks)})
        with span(prof, "serve.bill"):
            carbon = self.router.commit(
                pod, self._step_terms("prefill", S, B, chips), hour=now_hour)
        prefill_elapsed = time.perf_counter() - t0
        max_new = max(r.max_new_tokens for r in batch)
        out = np.zeros((B, max_new), np.int32)
        elapsed = np.zeros(max_new)     # service elapsed when token t exists
        with span(prof, "serve.sample"):
            tok = steps.greedy_sample(logits)[:, None]
        for t in range(max_new):
            with span(prof, "serve.sync"):
                out[:, t] = np.asarray(tok[:, 0])
            elapsed[t] = time.perf_counter() - t0
            if t == max_new - 1:
                # token 0 came from prefill, so max_new tokens need only
                # max_new - 1 decodes; running (and billing) a final
                # decode whose sample is discarded inflated carbon by one
                # step per batch
                break
            with span(prof, "serve.decode"):
                logits, cache = self._decode(self.params, cache, tok,
                                             jnp.int32(S + t))
            with span(prof, "serve.bill"):
                carbon += self.router.commit(
                    pod, self._step_terms("decode", S + t + 1, B, chips),
                    hour=now_hour)
            with span(prof, "serve.sample"):
                tok = steps.greedy_sample(logits)[:, None]
        if "moe_rows" in cache and self.obs is not None \
                and self.obs.metrics is not None:
            with span(prof, "serve.moe_counts"):
                rows = np.asarray(cache["moe_rows"])
                m = self.obs.metrics
                m.counter("serve.moe_rows_held",
                          "rows routed to experts held here").inc(
                              float(rows.sum()))
                m.gauge("serve.moe_rows_max",
                        "rows of the batch's busiest held expert").set(
                            float(rows.max()))
        comps = []
        for i, r in enumerate(batch):
            # a zero-token request's service ends at prefill
            service = (float(elapsed[r.max_new_tokens - 1])
                       if r.max_new_tokens > 0 else prefill_elapsed)
            c = Completion(r.uid, out[i, : r.max_new_tokens].tolist(), pod,
                           wait_s=max(0.0, start_s - r.submitted_s),
                           service_s=service,
                           carbon_g=carbon / B)
            comps.append(c)
            self.completions.append(c)
        return comps

    # -- sim integration -----------------------------------------------------
    def step(self, now_hour: float = 0.0,
             limit: Optional[int] = None) -> List[Completion]:
        """:class:`repro.sim.driver.BatchExecutor` interface: the sim
        driver's executor hook. ``limit`` caps this batch; virtual batch
        start is derived from ``now_hour`` so waits stay on sim time."""
        # hours -> virtual seconds inline: the runtime layer must not
        # depend on repro.sim (the sim drives the runtime, not vice versa)
        now_s = now_hour * 3600.0
        if limit is None:
            return self.run_batch(now_hour, now_s=now_s)
        if limit <= 0:
            return []           # match CarbonEdgeEngine.step(limit=0)
        old, self.batch_size = self.batch_size, limit
        try:
            return self.run_batch(now_hour, now_s=now_s)
        finally:
            self.batch_size = old

    def run_all(self, now_hour: float = 0.0) -> List[Completion]:
        done = []
        while self.queue:
            done.extend(self.run_batch(now_hour))
        return done

    def report(self) -> Dict:
        return {
            "completed": len(self.completions),
            "carbon_g_total": self.router.monitor.total_carbon_g(),
            "energy_kwh_total": self.router.monitor.total_energy_kwh(),
            "per_region": self.router.monitor.report(),
            "policy": self.router.policy.name,
        }
