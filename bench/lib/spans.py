"""Arithmetic of the per-layer metrics that read the program's own spans.

While a profiler session records, the program keeps one record per span in
memory (``repro.runtime.trace.recorded()``: name, start and end in ns,
arguments). ``run.py`` profiles the traced window alone, so the record holds
that window's spans. A program without that module records nothing:
``program_spans`` is then empty and every metric here None, left out of the
line.
"""
from __future__ import annotations

import statistics

from lib import stats as S

FORWARD = "repro.servable.forward"      # Servable.forward's dispatch
ADMIT = "repro.engine.admit"            # one admission or prefill pass
WINDOW = "repro.engine.window"          # one decode window, to the host


def program_spans(name):
    """The program's ended spans called ``name``, in the order opened."""
    try:
        from repro.runtime import trace
    except ImportError:
        return []
    return [sp for sp in trace.recorded()
            if sp.name == name and sp.t1_ns is not None]


def _dur_ns(sp):
    return sp.t1_ns - sp.t0_ns


def median_ms(spans):
    """Median span duration, ms."""
    if not spans:
        return None
    return 1e-6 * statistics.median(_dur_ns(sp) for sp in spans)


def window_share_pct(spans, window_s):
    """Summed span durations over the window, %."""
    if not spans or not window_s or window_s <= 0:
        return None
    return 100.0 * sum(_dur_ns(sp) for sp in spans) * 1e-9 / window_s


def queue_wait_p95_ms(admits):
    """p95 of ``wait_us`` over the admission spans that carry it: one per
    request, the pass that first made it active."""
    waits = [sp.args["wait_us"] for sp in admits if "wait_us" in sp.args]
    if not waits:
        return None
    return 1e-3 * S.percentile(waits, 95)


def slot_use_pct(windows):
    """Tokens the decode windows emitted over their k x slots row-steps."""
    steps = sum(sp.args["k"] * sp.args["slots"] for sp in windows)
    if steps <= 0:
        return None
    return 100.0 * sum(sp.args["tokens"] for sp in windows) / steps
