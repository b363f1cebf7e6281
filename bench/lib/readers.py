"""Arithmetic shared by the per-layer metric readers (``bench/metrics``).

Each reader gets ``ctx = {"record", "trace", "peaks"}``: the window's host
record with its required-work log (``families/*.work``), the reduced trace
(``lib/trace.reduce``) and the chip's published peaks. A reader that finds
nothing to read returns None and the metric is left out of the line; a
share of a roofline or of a peak is never reported as 0 for want of data.
"""
from __future__ import annotations

from lib import stats as S
from lib import work as W


def _least(ctx, key):
    pk = ctx["peaks"]
    return sum(n * W.least_s(w, pk["flops_bf16"], pk["hbm_bw"])
               for n, w in ctx["record"]["work"].get(key, ()))


def roofline_pct(ctx, kernel):
    """Least time for the kernel's required work over its device time."""
    tr = ctx["trace"]
    if tr is None:
        return None
    t = tr["kernel_s"].get(kernel, 0.0) / tr["chips"]
    least = _least(ctx, kernel)
    if t <= 0 or least <= 0:
        return None
    return 100.0 * least / t


def step_roofline_pct(ctx):
    """Least time of every step's required work over the window."""
    rec = ctx["record"]
    least = _least(ctx, "steps")
    return 100.0 * least / rec["window_s"] if least > 0 else None


def flops_pct(ctx):
    rec = ctx["record"]
    f = rec["work"].get("flops", 0.0)
    if f <= 0:
        return None
    return 100.0 * f / (rec["window_s"] * ctx["peaks"]["flops_bf16"])


def idle_pct(ctx):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def p95_ms(values):
    return 1e3 * S.percentile(values, 95) if values else None


def share_pct(part_s, window_s):
    return 100.0 * part_s / window_s if window_s > 0 else None


def host_share_pct(rec):
    """The window's time outside the engine's device calls (prefill and
    decode windows until their outputs reach the host)."""
    busy = share_pct(rec["prefill_s"] + rec["decode_s"], rec["window_s"])
    return None if busy is None else 100.0 - busy
