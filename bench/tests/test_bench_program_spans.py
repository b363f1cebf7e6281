"""The readers of the program's own spans (``bench/lib/spans.py``), on
synthetic records with known values, on records the program made under a
profiler session, and on a program that keeps no spans (None, never a
raise)."""
import os
import sys

import jax
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as R  # noqa: E402
from lib import spans as SP  # noqa: E402
from repro.runtime import trace  # noqa: E402

MS = 1_000_000  # ns
READERS = ("dispatch_ms.bert", "dispatch_share.bert", "queue_wait_p95_ms.lm",
           "decode_slot_use.lm")


def _sp(name, t0_ms, dur_ms, **args):
    return trace.Span(name, int(t0_ms * MS), int((t0_ms + dur_ms) * MS), args)


def _records():
    fwd = [_sp(SP.FORWARD, 10 * i, d, n=i)
           for i, d in enumerate((1.0, 3.0, 2.0, 4.0))]
    # request 1 waits 40 ms, then a later prefill pass (no wait); request 2
    # waits 0; requests 3..21 wait 1..19 ms
    admits = [_sp(SP.ADMIT, 0, 1, req=1, wait_us=40_000, tokens=8),
              _sp(SP.ADMIT, 5, 1, req=1, tokens=8),
              _sp(SP.ADMIT, 7, 1, req=2, wait_us=0, tokens=4)] + \
        [_sp(SP.ADMIT, 10 + r, 1, req=r, wait_us=1000 * (r - 2), tokens=1)
         for r in range(3, 22)]
    windows = [_sp(SP.WINDOW, 0, 5, k=8, slots=4, tokens=32),
               _sp(SP.WINDOW, 5, 5, k=8, slots=4, tokens=24),
               _sp(SP.WINDOW, 10, 1, k=1, slots=4, tokens=2)]
    return fwd + admits + windows


@pytest.fixture
def synthetic(monkeypatch):
    recs = _records()
    monkeypatch.setattr(SP, "program_spans",
                        lambda name: [r for r in recs if r.name == name])


def _read(name, window_s=0.1):
    return R.load_reader(name).read({"record": {"window_s": window_s},
                                     "trace": {"window_s": window_s}})


def test_readers_on_synthetic_records(synthetic):
    assert _read("dispatch_ms.bert") == pytest.approx(2.5)
    # 10 ms of dispatch in a 100 ms window
    assert _read("dispatch_share.bert") == pytest.approx(10.0)
    # first waits per request: 40, 0, 1..19 ms (21 values); the p95 sits at
    # position 0.95 x 20 = 19 of the sorted 0..19, 40: 19 ms
    assert _read("queue_wait_p95_ms.lm") == pytest.approx(19.0)
    # (32 + 24 + 2) / (32 + 32 + 4)
    assert _read("decode_slot_use.lm") == pytest.approx(100 * 58 / 68)


def test_queue_wait_takes_each_requests_first_admission():
    # a parked attempt and a later chunk carry no wait: only the pass that
    # made the request active counts
    recs = [_sp(SP.ADMIT, 0, 1, req=7, tokens=0),
            _sp(SP.ADMIT, 1, 1, req=7, wait_us=5000, tokens=8),
            _sp(SP.ADMIT, 2, 1, req=7, tokens=8)]
    assert SP.queue_wait_p95_ms(recs) == pytest.approx(5.0)


@pytest.mark.parametrize("name", READERS)
def test_no_spans_reads_none(monkeypatch, name):
    monkeypatch.setattr(SP, "program_spans", lambda name: [])
    assert _read(name) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_spans_reads_none(monkeypatch, name):
    """The parent of the change that added the spans has no
    ``repro.runtime.trace``: its traced runs leave the metrics out."""
    monkeypatch.setitem(sys.modules, "repro.runtime.trace", None)
    assert SP.program_spans(SP.FORWARD) == []
    assert _read(name) is None


def test_reads_what_the_program_recorded(tmp_path):
    trace.clear()
    st = type("St", (), {"decode_s": 0.0})()
    try:
        with jax.profiler.trace(str(tmp_path)):
            for n in range(3):
                with trace.span(SP.FORWARD, n=n):
                    pass
            with trace.timed(st, "decode_s", SP.WINDOW, k=4, slots=2,
                             tokens=0) as sp:
                sp.set(tokens=6)
        assert len(SP.program_spans(SP.FORWARD)) == 3
        assert _read("dispatch_ms.bert") > 0
        assert _read("decode_slot_use.lm") == pytest.approx(75.0)
        assert _read("queue_wait_p95_ms.lm") is None
    finally:
        trace.clear()
