"""Spans of the serving program, on the profiler's trace and in memory.

``span(name, **args)`` marks one piece of the program's host work. While a
profiler session records (``jax.profiler.trace`` / ``start_trace``) the span
is a ``jax.profiler.TraceAnnotation``, so it lands on the host plane of the
session's ``.xplane.pb`` beside the device ops, and a record
``Span(name, t0_ns, t1_ns, args)`` is kept in this process's memory
(``recorded()``). With no session recording, ``span`` returns a shared
no-op handle after one ``is_enabled`` check: no clock read, no object of
its own.

``timed(obj, field, name, **args)`` is the variant for the sites that feed a
wall-clock counter (``EngineStats.prefill_s`` and the like): it always adds
its duration in seconds to ``obj.field`` and is a span besides while a
session records, the record and the counter reading the same two
``perf_counter_ns`` values.

A handle's ``set(**args)`` adds values known only at exit (tokens emitted,
say) to the record and to the profiler's event. Every name starts with
``repro.``.

The record outlives the session: up to ``CAP`` spans stay held in memory
after any profiler session (a benchmark's, or a TensorBoard capture of a
server) until ``clear()``; spans past the cap reach the profiler alone.
It is there because the benchmark's readers cannot yet read ``repro.``
events from the trace file itself.

Spans of the program and what reads them (``bench/metrics``):

  * ``repro.servable.forward``: ``Servable.forward``'s dispatch, not the
    wait for its result -- ``dispatch_ms.bert``, ``dispatch_share.bert``;
  * ``repro.engine.admit`` (``req``, ``tokens``, and ``wait_us`` on a
    request's first admission) -- ``queue_wait_p95_ms.lm``;
  * ``repro.engine.window`` (``k``, ``slots``, ``tokens``) --
    ``decode_slot_use.lm``;
  * ``repro.engine.sync``: times ``EngineStats.sync_s``.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

__all__ = ["CAP", "Span", "clear", "recorded", "span", "timed"]

CAP = 200_000           # records kept before recording stops

_enabled = jax.profiler.TraceAnnotation.is_enabled
_records: List[list] = []       # [name, t0_ns, t1_ns, args]


class Span(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: Optional[int]        # None while the span is open
    args: Dict[str, Any]


def recorded() -> List[Span]:
    """The spans kept since the last ``clear()``, in the order opened."""
    return [Span(r[0], r[1], r[2], dict(r[3])) for r in list(_records)]


def clear() -> None:
    _records.clear()


class _Off:
    """The handle of a span while no profiler session records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_OFF = _Off()


class _Live:
    """A span that records; with ``obj``, one that adds its time to
    ``obj.<field>`` and records only while a session does."""

    __slots__ = ("name", "args", "obj", "field", "ann", "rec", "t0")

    def __init__(self, name, args, obj=None, field=None):
        self.name, self.args, self.obj, self.field = name, args, obj, field
        self.ann = self.rec = None

    def __enter__(self):
        if self.obj is None or _enabled():
            self.ann = jax.profiler.TraceAnnotation(self.name, **self.args)
            self.ann.__enter__()
            if len(_records) < CAP:
                self.rec = [self.name, 0, None, self.args]
                _records.append(self.rec)
        self.t0 = time.perf_counter_ns()
        if self.rec is not None:
            self.rec[1] = self.t0
        return self

    def set(self, **args) -> None:
        self.args.update(args)
        if self.ann is not None:
            self.ann.set_metadata(**args)

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self.obj is not None:
            setattr(self.obj, self.field,
                    getattr(self.obj, self.field) + (t1 - self.t0) * 1e-9)
        if self.rec is not None:
            self.rec[2] = t1
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


def span(name: str, **args):
    """A span called ``name`` (``repro.`` first) with ``args``; a no-op
    handle unless a profiler session records."""
    if not _enabled():
        return _OFF
    return _Live(name, args)


def timed(obj, field: str, name: str, **args):
    """Add the duration of the ``with`` block, in seconds, to
    ``obj.<field>``; a span besides while a profiler session records."""
    return _Live(name, args, obj, field)
