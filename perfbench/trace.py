"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Only the process that holds the chip can trace it, so a traced run wraps a
short steady stretch in :func:`capture` and then calls :func:`reduce`,
which reads the file with nothing but ``jax.profiler.ProfileData``:

- device busy time: the union of the ``XLA Modules`` executions on each
  ``/device:*`` plane, averaged over the devices used;
- per module (``jit_<fn>(<fingerprint>)``): executions, their intervals,
  and the names of the custom-call kernels run inside it;
- per kernel (a custom call such as ``select_best_fused``): summed device
  time;
- the idle gaps between module executions, each labelled with the
  innermost ``bench.*`` host span (``jax.profiler.TraceAnnotation``) open
  at the gap's middle;
- ``breakdown``: the ten operations with the most device self time and the
  ten longest labelled idle gaps.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
_INST = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")


@contextlib.contextmanager
def capture(trace_dir: str):
    """Profile the enclosed block into ``trace_dir`` with the Python tracer
    off (it adds hundreds of thousands of host events) and host TraceMe
    spans on, so the ``bench.*`` annotations land in the trace."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        yield


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def inst_name(op_event_name: str) -> str:
    """``%decode_attention.5 = bf16[...] custom-call(...)`` ->
    ``decode_attention``: the HLO instruction name without its numeric
    suffix."""
    m = _INST.match(op_event_name)
    return m.group(1) if m else op_event_name.split(" ", 1)[0].lstrip("%")


def _is_kernel(op_event_name: str) -> bool:
    """A Pallas/Mosaic kernel appears as a ``custom-call`` instruction."""
    return "custom-call(" in op_event_name


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(events) -> Dict[str, float]:
    """Device self time per op on one line: an op's duration minus that of
    the ops nested in it (a ``while`` holds the loop body's ops)."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [start, end, name, child total]

    def close():
        s, e, name, child = stack.pop()
        out[name] = out.get(name, 0.0) + (e - s) - child
        if stack:
            stack[-1][3] += e - s

    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][1] <= s:
            close()
        stack.append([s, e, name, 0.0])
    while stack:
        close()
    return out


def reduce(path: str, window: Tuple[float, float] = None) -> Dict:
    """Reduce one ``.xplane.pb``. Times are seconds. ``window``: optional
    (start, end) in the trace's own nanoseconds; default: from the first to
    the last event of the ``bench.*`` host spans and the device modules."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/device:") and "TPU" in plane.name:
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(ev.start_ns, ev.end_ns, ev.name)
                            for ev in line.events]
                elif line.name == "XLA Ops":
                    ops = [(ev.start_ns, ev.end_ns, ev.name)
                           for ev in line.events]
            if mods:
                devices.append((plane.name, mods, ops))
    if not devices:
        raise ValueError(f"{path}: no device plane with XLA module events")
    if window is None:
        # device timestamps can run a millisecond or two behind the host
        # spans, so the window holds both
        starts = [m[0] for _, ms, _ in devices for m in ms]
        ends = [m[1] for _, ms, _ in devices for m in ms]
        window = (min(starts + [s for s, _, _ in spans]),
                  max(ends + [e for _, e, _ in spans]))
    w0, w1 = window

    def clip(s, e):
        return max(s, w0), min(e, w1)

    busy, modules, kernels, selft = [], {}, {}, {}
    gaps: List[Tuple[float, str]] = []
    for _, mods, ops in devices:
        ivs = [clip(s, e) for s, e, _ in mods]
        ivs = [(s, e) for s, e in ivs if e > s]
        busy.append(union_length(ivs) / 1e9)
        # the kernels run inside each module execution
        ops_sorted = sorted(ops)
        j = 0
        for s, e, name in sorted(mods):
            rec = modules.setdefault(name, {"count": 0, "total_s": 0.0,
                                            "intervals": [],
                                            "kernels": set()})
            cs, ce = clip(s, e)
            if ce <= cs:
                continue
            rec["count"] += 1
            rec["total_s"] += (ce - cs) / 1e9
            rec["intervals"].append((cs / 1e9, ce / 1e9))
            while j < len(ops_sorted) and ops_sorted[j][0] < s:
                j += 1
            k = j
            while k < len(ops_sorted) and ops_sorted[k][0] < e:
                if _is_kernel(ops_sorted[k][2]):
                    rec["kernels"].add(inst_name(ops_sorted[k][2]))
                k += 1
        for s, e, name in ops:
            cs, ce = clip(s, e)
            if ce > cs and _is_kernel(name):
                kn = inst_name(name)
                kernels[kn] = kernels.get(kn, 0.0) + (ce - cs) / 1e9
        inside = [(max(s, w0), min(e, w1), name) for s, e, name in ops
                  if min(e, w1) > max(s, w0)]
        for name, t in _self_times(inside).items():
            selft[name] = selft.get(name, 0.0) + t / 1e9
        m = merged(ivs)
        edges = [w0] + [x for iv in m for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) / 1e9, _label(spans, (a + b) / 2)))
    n_dev = len(devices)
    for rec in modules.values():
        rec["kernels"] = sorted(rec["kernels"])
    top_ops = sorted(selft.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: -g[0])[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n_dev,
        "devices": n_dev,
        "modules": modules,
        "kernels": {k: v / n_dev for k, v in kernels.items()},
        "gaps_s": [g for g, _ in gaps],
        "breakdown": {
            "device_ops": [[_short(n), t / n_dev] for n, t in top_ops],
            "idle_gaps": [[label, g] for g, label in top_gaps],
        },
    }


def _short(op_event_name: str) -> str:
    """Instruction name plus its result type, e.g.
    ``decode_attention.5 = bf16[8,16,1,128]``."""
    head = op_event_name.lstrip("%").split("{", 1)[0]
    return head[:120]


def _label(spans, t) -> str:
    """Innermost ``bench.*`` span holding instant ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "outside bench spans"


def modules_with_kernel(red: Dict, kernel: str) -> List[Dict]:
    """Module records whose executions ran ``kernel``."""
    return [m for m in red["modules"].values() if kernel in m["kernels"]]
