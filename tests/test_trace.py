"""Program spans (``repro.runtime.trace``): nothing recorded and only a
counter kept without a profiler session; under one, the engine's admissions,
windows and syncs and the servable's dispatch recorded with their arguments,
their timing the very numbers ``EngineStats`` adds up, and the same names on
the host plane of the session's ``.xplane.pb``."""
import collections
import glob
import os
import threading
import time
import types

import jax
import numpy as np
import pytest

from repro.configs.base import LayerKind, ModelConfig
from repro.models import init_model
from repro.runtime import trace
from repro.serving import SchedSpec, ServingSpec, prepare_servable

ENGINE_SPANS = ("repro.engine.admit", "repro.engine.window",
                "repro.engine.sync")


@pytest.fixture(scope="module")
def servable():
    cfg = ModelConfig(
        arch="trace-smoke", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256,
        pattern=(LayerKind("attn", "dense"),), dtype="float32")
    params = init_model(jax.random.PRNGKey(1), cfg)
    return prepare_servable(params, cfg, ServingSpec(
        tile=(16, 16), sparsity=0.5, prune="oneshot",
        targets=("attn/wq", "attn/wk", "attn/wv", "attn/wo")))


@pytest.fixture(autouse=True)
def fresh_record():
    trace.clear()
    yield
    trace.clear()


def _prompts(n):
    rng = np.random.RandomState(7)
    return [rng.randint(0, 256, (int(rng.randint(4, 30)),)).tolist()
            for _ in range(n)]


def _serve(eng, prompts, max_new=6):
    hs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run()
    assert all(h.done for h in hs)
    return hs


def _counters(st):
    return (st.tokens_generated, st.prefill_s, st.decode_s, st.sync_s,
            st.prefilled_tokens)


def test_no_session_records_nothing_and_fills_stats(servable):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    eng = servable.engine(max_slots=3, cache_len=64, sync_every=4)
    hs = _serve(eng, _prompts(5))
    st = eng.stats
    assert trace.recorded() == []
    assert st.tokens_generated == sum(len(h.tokens) for h in hs) == 30
    assert st.prefills == 5
    assert st.prefill_s > 0 and st.decode_s > 0 and st.sync_s > 0
    # the shared no-op handle: no object per span
    assert trace.span("repro.a") is trace.span("repro.b", x=1)


@pytest.mark.parametrize("layout,sched", [
    ("dense", None),
    ("paged", None),
    ("dense", SchedSpec(max_chunk=8, token_budget=16)),
], ids=["dense", "paged", "chunked"])
def test_engine_spans_under_a_session(servable, tmp_path, layout, sched):
    eng = servable.engine(max_slots=3, cache_len=64, sync_every=4,
                          kv_layout=layout, sched=sched)
    _serve(eng, _prompts(2))            # compiles outside the session
    c0 = _counters(eng.stats)
    with jax.profiler.trace(str(tmp_path)):
        hs = _serve(eng, _prompts(6))
    c1 = _counters(eng.stats)
    spans = trace.recorded()
    by = collections.defaultdict(list)
    for sp in spans:
        by[sp.name].append(sp)
    assert set(by) == set(ENGINE_SPANS)
    assert all(sp.t1_ns is not None and sp.t1_ns >= sp.t0_ns
               for sp in spans)

    # each request's first pass carries its queue wait, later passes none
    ids = [h.req_id for h in hs]
    assert {sp.args["req"] for sp in by["repro.engine.admit"]} == set(ids)
    first = {}
    for sp in by["repro.engine.admit"]:
        first.setdefault(sp.args["req"], sp)
    assert all(sp.args["wait_us"] >= 0 for sp in first.values())
    later = [sp for sp in by["repro.engine.admit"]
             if first[sp.args["req"]] is not sp]
    assert all("wait_us" not in sp.args for sp in later)
    if sched is not None:
        assert later, "multi-chunk prompts take more than one pass"
    assert sum(sp.args["tokens"] for sp in by["repro.engine.admit"]) == \
        c1[4] - c0[4]

    # every window runs k steps over all slots; the tokens it emitted
    # plus each request's first token (sampled at admission) are the
    # engine's own count
    for sp in by["repro.engine.window"]:
        assert sp.args["slots"] == 3 and 1 <= sp.args["k"] <= 4
        assert 0 < sp.args["tokens"] <= sp.args["k"] * sp.args["slots"]
    assert sum(sp.args["tokens"] for sp in by["repro.engine.window"]) + \
        len(hs) == c1[0] - c0[0]

    # one timing site: the spans' durations are the counters' increments
    def total_s(name):
        return sum(sp.t1_ns - sp.t0_ns for sp in by[name]) * 1e-9
    assert total_s("repro.engine.admit") == pytest.approx(c1[1] - c0[1],
                                                          rel=1e-9)
    assert total_s("repro.engine.window") == pytest.approx(c1[2] - c0[2],
                                                           rel=1e-9)
    assert total_s("repro.engine.sync") == pytest.approx(c1[3] - c0[3],
                                                         rel=1e-9)

    # the same spans on the host plane of the session's trace
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    host = collections.Counter(
        e.name for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("repro."))
    assert host == collections.Counter({k: len(v) for k, v in by.items()})


def test_window_span_counts_live_and_walked_kv_pages(servable, tmp_path):
    """A paged engine's windows carry how many KV pages its decoding slots
    hold up to their positions, against the pages their tables span: two
    slots at positions 5 and 20 (pages of 16, tables of 64 / 16 = 4
    pages) decode a window of 4 steps, then one of 1 at 9 and 24."""
    eng = servable.engine(max_slots=2, cache_len=64, sync_every=4,
                          kv_layout="paged")
    assert (eng.kv_page_size, eng._table_width) == (16, 4)
    _serve(eng, _prompts(3))            # compiles outside the session
    st0 = (eng.stats.kv_pages_live, eng.stats.kv_pages_walked)
    rng = np.random.RandomState(3)
    with jax.profiler.trace(str(tmp_path)):
        _serve(eng, [rng.randint(0, 256, (n,)).tolist() for n in (5, 20)])
    wins = [sp.args for sp in trace.recorded()
            if sp.name == "repro.engine.window"]
    assert all(a["kv_pages_live"] <= a["kv_pages_walked"] for a in wins)
    # positions 5, 20 -> pages 1 + 2; then 9, 24 -> 1 + 2
    assert [(a["k"], a["kv_pages_live"], a["kv_pages_walked"])
            for a in wins] == [(4, 4 * (1 + 2), 4 * 2 * 4),
                               (1, 1 * (1 + 2), 1 * 2 * 4)]
    assert (eng.stats.kv_pages_live - st0[0],
            eng.stats.kv_pages_walked - st0[1]) == (15, 40)


def test_dense_window_span_has_no_kv_pages(servable, tmp_path):
    eng = servable.engine(max_slots=2, cache_len=64, sync_every=4)
    _serve(eng, _prompts(2))
    with jax.profiler.trace(str(tmp_path)):
        _serve(eng, _prompts(2))
    wins = [sp.args for sp in trace.recorded()
            if sp.name == "repro.engine.window"]
    assert wins and not any("kv_pages_live" in a for a in wins)
    assert eng.stats.kv_pages_live == eng.stats.kv_pages_walked == 0


def test_servable_forward_span_is_dispatch_only(servable, tmp_path):
    toks = np.arange(16, dtype=np.int32)[None]
    jax.block_until_ready(servable.forward(toks))
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            t0 = time.perf_counter_ns()
            out = servable.forward(toks)
            t1 = time.perf_counter_ns()
            jax.block_until_ready(out)
    spans = trace.recorded()
    assert [sp.name for sp in spans] == ["repro.servable.forward"] * 3
    assert all(sp.args == {} for sp in spans)
    # the span closes when the call returns, before the wait for the logits
    assert t0 <= spans[-1].t0_ns <= spans[-1].t1_ns <= t1


def _other_thread_span():
    with trace.span("repro.other"):
        pass


def test_set_args_and_threads(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("repro.outer", a=1) as outer:
            with trace.span("repro.inner") as inner:
                inner.set(tokens=3)
            outer.set(b=2)
            t = threading.Thread(target=_other_thread_span)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
    spans = trace.recorded()
    assert [sp.name for sp in spans] == ["repro.outer", "repro.inner",
                                         "repro.other"]
    assert spans[0].args == {"a": 1, "b": 2}
    assert spans[1].args == {"tokens": 3}
    assert spans[0].t0_ns <= spans[1].t0_ns <= spans[1].t1_ns <= \
        spans[0].t1_ns


def test_timed_counts_with_and_without_a_session(tmp_path):
    st = types.SimpleNamespace(busy_s=0.0)
    with trace.timed(st, "busy_s", "repro.busy", k=1) as sp:
        sp.set(tokens=1)
    off = st.busy_s
    assert off > 0 and trace.recorded() == []
    with jax.profiler.trace(str(tmp_path)):
        with trace.timed(st, "busy_s", "repro.busy", k=2):
            pass
    sp, = trace.recorded()
    assert (sp.name, sp.args) == ("repro.busy", {"k": 2})
    assert st.busy_s - off == pytest.approx((sp.t1_ns - sp.t0_ns) * 1e-9,
                                            rel=1e-9)


def test_cap_stops_recording(tmp_path, monkeypatch):
    monkeypatch.setattr(trace, "CAP", 5)
    st = types.SimpleNamespace(x_s=0.0)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(8):
            with trace.span("repro.s"):
                pass
        with trace.timed(st, "x_s", "repro.t"):
            pass
    assert [sp.name for sp in trace.recorded()] == ["repro.s"] * 5
    assert st.x_s > 0                   # a dropped timed span still counts
    trace.clear()
    assert trace.recorded() == []


def _admit_passes(req_id):
    return [sp for sp in trace.recorded() if sp.name == "repro.engine.admit"
            and sp.args["req"] == req_id]


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_queue_wait_skips_a_preempted_requests_resume(servable, tmp_path,
                                                      layout):
    kw = {"kv_pool_pages": 16} if layout == "paged" else {}
    eng = servable.engine(max_slots=1, cache_len=64, sync_every=2,
                          kv_layout=layout, **kw)
    _serve(eng, _prompts(1))            # compiles outside the session
    p0, p1 = _prompts(2)
    with jax.profiler.trace(str(tmp_path)):
        victim = eng.submit(p0, max_new_tokens=10, priority=0)
        eng.step()
        vip = eng.submit(p1, max_new_tokens=10, priority=5)
        eng.run()
    assert victim.done and vip.done and victim.n_preempted == 1
    # the admission carries the wait, the resume (queued again behind vip's
    # whole decode) none
    first, resume = _admit_passes(victim.req_id)
    assert first.args["wait_us"] >= 0 and "wait_us" not in resume.args
    w, = _admit_passes(vip.req_id)
    assert w.args["wait_us"] >= 0


def test_queue_wait_is_read_at_the_admission_that_succeeds(servable,
                                                           tmp_path):
    # a 2-page pool of 16 tokens a page: x holds one, y needs both and parks
    # until x ends
    eng = servable.engine(max_slots=2, cache_len=64, sync_every=2,
                          kv_layout="paged", kv_pool_pages=2)
    _serve(eng, [[1, 2, 3]], max_new=2)
    with jax.profiler.trace(str(tmp_path)):
        x = eng.submit([1, 2, 3, 4], max_new_tokens=4)
        y = eng.submit(list(range(5, 21)), max_new_tokens=16)
        eng.run()
    assert x.done and y.done
    passes = _admit_passes(y.req_id)
    assert len(passes) >= 2             # parked at least once
    assert [("wait_us" in sp.args) for sp in passes] == \
        [False] * (len(passes) - 1) + [True]
    assert passes[-1].args["tokens"] == 16
    # the wait runs to the attempt that succeeded, not to the parked one
    parked_ns = passes[-1].t0_ns - passes[0].t0_ns
    assert passes[-1].args["wait_us"] + 1000 >= parked_ns / 1000
