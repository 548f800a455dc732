"""Arithmetic the metric readers in ``metrics/`` share. Each reader takes
the run's record and returns a number, or ``None`` when the record has
nothing for it to read (another path, or no trace)."""
from __future__ import annotations

from typing import Dict, List, Optional

from perfbench import work
from perfbench.trace import modules_with_kernel


def of_path(rec: Dict, path: str) -> bool:
    return rec.get("path") == path


def device_idle(rec: Dict) -> Optional[float]:
    """Share of the traced window in which no module ran on the device."""
    red = rec.get("trace")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def span_ms_per_step(rec: Dict, *phases: str) -> Optional[float]:
    """Summed program spans (``StepProfiler``) per engine step, in ms."""
    spans, steps = rec.get("spans"), rec.get("steps")
    if not spans or not steps or not all(p in spans for p in phases):
        return None
    return 1e3 * sum(spans[p]["total_s"] for p in phases) / len(steps)


def exec_bill_ms(rec: Dict) -> Optional[float]:
    """Step wall time minus its ``select`` span, per step, in ms: what the
    engine spends executing and billing a placed batch."""
    spans, steps = rec.get("spans"), rec.get("steps")
    if not spans or "select" not in spans or not steps:
        return None
    wall = sum(s1 - s0 for s0, s1, _ in steps)
    return 1e3 * (wall - spans["select"]["total_s"]) / len(steps)


def kernel_roofline(rec: Dict, kernel: str, flops: float,
                    nbytes: float) -> Optional[float]:
    """Least time for the work over the kernel's device time, in %."""
    red, pk = rec.get("trace"), rec.get("peaks")
    if not red or not pk:
        return None
    t = red["kernels"].get(kernel, 0.0)
    if t <= 0 or flops <= 0:
        return None
    return 100.0 * work.least_time_s(flops, nbytes, pk) / t


def mfu(rec: Dict, flops: float, seconds: float) -> Optional[float]:
    pk = rec.get("peaks")
    if not pk or seconds <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (seconds * pk["bf16_flops_per_s"])


def module_ms(rec: Dict, kernel: str) -> Optional[float]:
    """Device time per execution of the jitted step that runs ``kernel``."""
    red = rec.get("trace")
    if not red:
        return None
    mods = modules_with_kernel(red, kernel)
    n = sum(m["count"] for m in mods)
    return 1e3 * sum(m["total_s"] for m in mods) / n if n else None


def module_gaps_s(rec: Dict, kernel: str) -> List[float]:
    """Idle device time between consecutive executions of the step that
    runs ``kernel``."""
    red = rec.get("trace")
    if not red:
        return []
    ivs = sorted(iv for m in modules_with_kernel(red, kernel)
                 for iv in m["intervals"])
    return [b[0] - a[1] for a, b in zip(ivs, ivs[1:]) if b[0] > a[1]]


# -- serving work ------------------------------------------------------------


def prompt_lens(rec: Dict) -> List[int]:
    return [b["L"] for b in rec["batches"] for _ in b["outs"]]


def decode_contexts(rec: Dict) -> List[int]:
    """Positions attended by each useful decode row: decode t of a batch
    yields token t + 1 at position L + t, useful to the requests that
    still want it."""
    out = []
    for b in rec["batches"]:
        for t in range(max(b["outs"]) - 1):
            n = sum(1 for m in b["outs"] if t + 1 < m)
            out.extend([b["L"] + t + 1] * n)
    return out


def decode_phase_s(rec: Dict) -> float:
    """Host time from each batch's first decode call to its last token."""
    total = 0.0
    for b in rec["batches"]:
        if b["t_decode"]:
            done = [s for s in b["service_s"] if s is not None]
            total += b["t_prefill"] + max(done) - b["t_decode"][0]
    return total

