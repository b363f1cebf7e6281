"""Trace reduction on synthetic device events and host spans."""
import collections
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import trace as TR  # noqa: E402

MS = 1e6  # ns


def _dev():
    # two overlapping kernels, a gap, a third op; times in ns
    return [("%_plan_dds_call.1 = bf16[16,4096] custom-call()", 0, 4 * MS),
            ("%_paged_flash_call.3 = bf16[16,32,1,128] custom-call()",
             2 * MS, 4 * MS),
            ("%while.2 = (s32[]) while()", 0, 15 * MS),
            ("%dot.7 = f32[8] dot()", 10 * MS, 5 * MS)]


def test_union_merges_overlaps():
    assert TR.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_and_idle_share():
    ev = _dev()
    assert TR.busy_ns(ev, 0, 20 * MS) == pytest.approx(15 * MS)
    # clipped to the window
    assert TR.busy_ns(ev, 3 * MS, 12 * MS) == pytest.approx(9 * MS)
    assert TR.busy_ns(ev[:2] + ev[3:], 0, 20 * MS) == pytest.approx(11 * MS)


def test_kernel_sums_by_label():
    ev = _dev()
    assert TR.kernel_ns(ev, "plan_dds") == 4 * MS
    assert TR.kernel_ns(ev, "flash_call") == 4 * MS
    assert TR.kernel_ns(ev, "absent") == 0.0
    # an op that only names the kernel's output among its operands
    user = [("%fusion.3 = f32[8] fusion(bf16[8] %_plan_dds_call.1)", 0, 9)]
    assert TR.kernel_ns(user, "plan_dds") == 0.0


def test_idle_gaps_charged_to_innermost_span():
    spans = [("bench.window", 0, 20 * MS), ("bench.step", 0, 16 * MS),
             ("bench.submit", 16 * MS, 2 * MS)]
    ev = [e for e in _dev() if not e[0].startswith("%while")]
    gaps = dict(TR.idle_gaps(ev, spans, 0, 20 * MS))
    # 6..10 ms inside bench.step; 15..20 ms: midpoint 17.5 in bench.submit
    assert gaps["bench.step"] == pytest.approx(4e-3)
    assert gaps["bench.submit"] == pytest.approx(5e-3)


def test_idle_gaps_match_a_scan_of_every_span():
    g = random.Random(0)
    spans, t = [("bench.window", 0, 10_000)], 0
    while t < 9_000:
        d = g.randint(5, 60)
        spans.append(("bench.request", t, d))
        if g.random() < 0.5:
            spans.append(("bench.keep", t + d - 4, 3))
        t += d + g.randint(0, 5)
    ev = [("%op", s, g.randint(1, 30)) for s in range(0, 10_000, 37)]
    busy = TR.union((s, s + d) for _, s, d in ev)
    edges = [0] + [x for iv in busy for x in iv] + [10_000]
    want = collections.defaultdict(float)
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = 0.5 * (a + b)
            cover = [(d, n) for n, s, d in spans if s <= mid <= s + d]
            want[min(cover)[1] if cover else "host idle"] += (b - a) * 1e-9
    got = dict(TR.idle_gaps(ev, spans, 0, 10_000))
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k])


def test_top_ops_add_instances_and_skip_containers():
    ev = _dev() + [("%_plan_dds_call.9 = bf16[16,4096] custom-call()",
                    20 * MS, 1 * MS)]
    ops = dict(TR.top_ops(ev))
    assert ops["_plan_dds_call"] == pytest.approx(5e-3)
    assert "while" not in ops and ops["dot"] == pytest.approx(5e-3)


def test_reduce_over_two_chips():
    devs = {"/device:TPU:0": _dev(),
            "/device:TPU:1": [("%_plan_dds_call.1", 0, 10 * MS)]}
    spans = [("bench.window", 0, 20 * MS)]
    r = TR.reduce(devs, spans, {"sparse_matmul": "plan_dds",
                                "decode_attn": "flash_call"},
                  "bench.window")
    assert r["window_s"] == pytest.approx(0.02)
    assert r["busy_s"] == pytest.approx((15 + 10) / 2 * 1e-3)
    assert r["chips"] == 2
    assert r["kernel_s"]["sparse_matmul"] == pytest.approx(14e-3)
    assert r["kernel_s"]["decode_attn"] == pytest.approx(4e-3)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["dot"] == pytest.approx(5e-3)
    assert len(r["breakdown"]["device_ops"]) <= 10


def test_reduce_refuses_a_trace_without_a_device():
    with pytest.raises(ValueError):
        TR.reduce({}, [("bench.window", 0, 1)], {}, "bench.window")


def test_load_reads_a_recorded_trace(tmp_path):
    """A real (CPU) profiler trace parses; it has host spans but no TPU
    plane, which ``reduce`` then refuses."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    devices, spans = TR.load(str(tmp_path))
    assert devices == {}
    assert [s[0] for s in spans] == ["bench.window"]
