"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

A trace is read into plain events, ``(name, start_ns, dur_ns)``, split into
device events (planes ``/device:TPU:<n>``, line "XLA Ops" where the plane
has one) and host spans (the benchmark's own ``TraceAnnotation`` names,
prefixed ``bench.``). Everything below works on those lists, so the tests
can feed it synthetic events.

  * busy: the union of the device-op intervals of each chip, averaged over
    chips; idle share = 1 - busy / window.
  * kernel time: the summed device durations of the ops whose own name
    (the HLO instruction, ``%_plan_dds_call.94 = ...``: a Pallas call is
    named after its jitted wrapper) contains the kernel's name; operands
    named in the rest of an op's text do not count.
  * idle gaps: each gap between busy intervals is charged to the innermost
    host span that covers its midpoint (``host idle`` where none does).
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (label, start_ns, dur_ns)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def load(log_dir: str):
    """-> (device events per chip {plane name: [Event]}, host spans
    [Event]) from the newest ``.xplane.pb`` under ``log_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            devices[plane.name] = [(e.name, e.start_ns, e.duration_ns)
                                   for ln in ops for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.duration_ns))
    return devices, spans


Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals clipped to [lo, hi]."""
    tot = 0.0
    for s, e in union((max(s, lo), min(s + d, hi)) for _, s, d in events
                      if s + d > lo and s < hi):
        tot += e - s
    return tot


CONTAINERS = ("while", "conditional", "call")


def op_name(label: str) -> str:
    """'%_plan_dds_call.94 = bf16[...] custom-call(...)' -> '_plan_dds_call':
    instances of one op (one per layer, say) add up under one name."""
    name = label.split(" ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def kernel_ns(events: Sequence[Event], needle: str) -> float:
    """Summed durations of the ops whose own name contains ``needle``."""
    return float(sum(d for lab, _, d in events if needle in op_name(lab)))


def top_ops(events: Sequence[Event], n: int = 10) -> List[List]:
    """The ``n`` ops that took most device time: [[name, seconds]].
    Control-flow containers (a scan's ``while``) are left out: their time
    is that of the ops inside them."""
    acc: Dict[str, float] = collections.defaultdict(float)
    for lab, _, d in events:
        name = op_name(lab)
        if name not in CONTAINERS:
            acc[name] += d
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(events: Sequence[Event], spans: Sequence[Event], lo: float,
              hi: float, n: int = 10) -> List[List]:
    """Idle device time inside [lo, hi], summed by the innermost host span
    covering each gap's midpoint: [[span name, seconds]], largest first."""
    busy = union((max(s, lo), min(s + d, hi)) for _, s, d in events
                 if s + d > lo and s < hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    acc: Dict[str, float] = collections.defaultdict(float)
    # the gaps' midpoints rise: sweep the spans sorted by start, keeping
    # those begun and not yet ended
    pending = sorted(spans, key=lambda sp: sp[1])
    nxt, open_ = 0, []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        while nxt < len(pending) and pending[nxt][1] <= mid:
            open_.append(pending[nxt])
            nxt += 1
        open_ = [sp for sp in open_ if sp[1] + sp[2] >= mid]
        cover = [(d, name) for name, _, d in open_]
        acc[min(cover)[1] if cover else "host idle"] += b - a
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def window_of(spans: Sequence[Event], name: str) -> Tuple[float, float]:
    """(start, end) ns of the one host span called ``name``."""
    hits = [(s, s + d) for lab, s, d in spans if lab == name]
    if len(hits) != 1:
        raise ValueError(f"expected one {name!r} span, found {len(hits)}")
    return hits[0]


def reduce(devices: Dict[str, List[Event]], spans: Sequence[Event],
           kernels: Dict[str, str], window_span: str) -> Dict:
    """The traced window's device numbers: ``busy_s`` (averaged over
    chips), ``window_s``, ``kernel_s`` per kernel key (summed over chips,
    so divide by the chip count for a per-chip share), the breakdown."""
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    lo, hi = window_of(spans, window_span)
    inside = {p: [ev for ev in evs if ev[1] + ev[2] > lo and ev[1] < hi]
              for p, evs in devices.items()}
    busy = [busy_ns(evs, lo, hi) for evs in inside.values()]
    first = inside[sorted(inside)[0]]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "chips": len(inside),
        "kernel_s": {k: sum(kernel_ns(evs, needle)
                            for evs in inside.values()) * 1e-9
                     for k, needle in kernels.items()},
        "breakdown": {"device_ops": top_ops(first),
                      "idle_gaps": idle_gaps(first, spans, lo, hi)},
    }
