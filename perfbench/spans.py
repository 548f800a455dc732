"""The program's own spans on the device trace's clock.

The program opens each of its phases as a profiler annotation named
``carbonedge.<phase>`` (``repro.obs.profiler.span``), so a capture holds
them beside the harness's ``bench.*`` spans and the device's modules.
:func:`reduce` reads them from an ``.xplane.pb``:

- ``idle_gaps``: the ten longest idle gaps of the device, each labelled
  with the innermost span of either prefix open at the gap's middle, so a
  gap inside ``carbonedge.select.pad`` reads so and not ``bench.window``;
- ``host_spans``: for each ``carbonedge.*`` name, the count of its spans
  and their seconds, clipped to the window.

The window and the gaps are the ones :func:`perfbench.trace.reduce` takes:
from the first to the last event of the ``bench.*`` spans and the device
modules, and the complement of the modules' union inside it.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from perfbench import trace

PREFIXES = (trace.SPAN_PREFIX, "carbonedge.")
PROGRAM_PREFIX = "carbonedge."

Interval = Tuple[float, float, str]


def _read(path: str):
    """(host spans of either prefix, module intervals per device plane)."""
    from jax.profiler import ProfileData

    spans: List[Interval] = []
    devices: List[List[Interval]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            spans.extend((ev.start_ns, ev.end_ns, ev.name)
                         for line in plane.lines for ev in line.events
                         if ev.name.startswith(PREFIXES))
        elif plane.name.startswith("/device:") and "TPU" in plane.name:
            mods = [(ev.start_ns, ev.end_ns, ev.name)
                    for line in plane.lines if line.name == "XLA Modules"
                    for ev in line.events]
            if mods:
                devices.append(mods)
    return spans, devices


def window(spans: List[Interval],
           devices: List[List[Interval]]) -> Tuple[float, float]:
    """First to last event of the ``bench.*`` spans and the modules."""
    bench = [(s, e) for s, e, name in spans
             if name.startswith(trace.SPAN_PREFIX)]
    ivs = bench + [(s, e) for mods in devices for s, e, _ in mods]
    return min(s for s, _ in ivs), max(e for _, e in ivs)


def host_spans(spans: List[Interval],
               win: Tuple[float, float]) -> Dict[str, Dict]:
    """Count and seconds of each ``carbonedge.*`` name inside ``win``
    (nanoseconds); a span that only overlaps it counts its overlap."""
    w0, w1 = win
    out: Dict[str, Dict] = {}
    for s, e, name in spans:
        lo, hi = max(s, w0), min(e, w1)
        if not name.startswith(PROGRAM_PREFIX) or hi <= lo:
            continue
        rec = out.setdefault(name, {"count": 0, "seconds": 0.0})
        rec["count"] += 1
        rec["seconds"] += (hi - lo) / 1e9
    return out


def labelled_gaps(spans: List[Interval], devices: List[List[Interval]],
                  win: Tuple[float, float]) -> List[Tuple[float, str]]:
    """Every idle gap of each device inside ``win``, in seconds, with the
    innermost span of either prefix at its middle."""
    w0, w1 = win
    gaps = []
    for mods in devices:
        ivs = [(max(s, w0), min(e, w1)) for s, e, _ in mods]
        m = trace.merged([(s, e) for s, e in ivs if e > s])
        edges = [w0] + [x for iv in m for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) / 1e9, trace._label(spans, (a + b) / 2)))
    return gaps


def reduce(path: str) -> Dict:
    spans, devices = _read(path)
    if not devices:
        raise ValueError(f"{path}: no device plane with XLA module events")
    win = window(spans, devices)
    gaps = sorted(labelled_gaps(spans, devices, win), key=lambda g: -g[0])
    return {"window_s": (win[1] - win[0]) / 1e9,
            "host_spans": host_spans(spans, win),
            "idle_gaps": [[label, g] for g, label in gaps[:10]]}
