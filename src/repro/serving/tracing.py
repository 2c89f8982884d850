"""Spans and counters of the serving path, kept while a profiler records.

A span is one stage of the serving path: its name, its start and end on
``time.perf_counter``, the span it ran inside, the request it served
(``rid``; a span given none takes its parent's) and the counters recorded
at the same boundary (bytes, slots, compiles).  Spans are kept only while
a JAX profiler session records (``jax.profiler.trace``); each is then also
a ``jax.profiler.TraceAnnotation`` named ``kvserve.<name>``, with its
``rid`` and counters as the event's metadata, so it sits in the trace
beside the device's operations.  Finished spans go to :data:`SPANS`, a
bounded list that outlives the runtime; readers take them after the run.

With no profiler session :func:`span` costs one ``is_enabled`` check.  A
stage whose wall seconds the cluster clock charges opens a :func:`timed`
span and reads ``.seconds`` from it, so the clock and the span share one
pair of ``perf_counter`` reads.

While a span is open, each XLA compile adds 1 to the ``compiles`` counter
of the innermost one: a step that recompiled shows which stage did.

    with tracing.timed("compress") as sp:
        comps = [pipe.compress(kv) for kv in kvs]
        if sp:
            sp.add(kv_bytes=..., wire_bytes=...)
    t_wall = sp.seconds
"""
from __future__ import annotations

import collections
import time
from typing import Any, Deque, Dict, List, Optional

import jax

MAX_SPANS = 1 << 16
# Finished spans, oldest first; the oldest go once MAX_SPANS are kept.
SPANS: Deque["Span"] = collections.deque(maxlen=MAX_SPANS)
_open: List["Span"] = []
_recording = jax.profiler.TraceAnnotation.is_enabled


class _Off:
    """A stage with no profiler session: keeps nothing, reads no clock."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        pass

    def __bool__(self) -> bool:
        return False

    def add(self, **counters: Any) -> None:
        pass


_OFF = _Off()


class Clock(_Off):
    """A timed stage with no profiler session: only its clock pair."""

    __slots__ = ("t0", "t1")

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Span(Clock):
    """One recorded stage."""

    __slots__ = ("name", "rid", "parent", "counters", "_note")

    def __init__(self, name: str, rid: Optional[int] = None):
        self.name = name
        self.rid = rid
        self.parent: Optional[Span] = None
        self.counters: Dict[str, Any] = {}

    def __bool__(self) -> bool:
        return True

    def add(self, **counters: Any) -> None:
        self.counters.update(counters)

    def __enter__(self):
        self.parent = _open[-1] if _open else None
        if self.rid is None and self.parent is not None:
            self.rid = self.parent.rid
        _open.append(self)
        self._note = jax.profiler.TraceAnnotation(f"kvserve.{self.name}")
        self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        _open.pop()
        if self.rid is not None:
            self._note.set_metadata(rid=self.rid)
        if self.counters:
            self._note.set_metadata(**self.counters)
        self._note.__exit__(*exc)
        SPANS.append(self)


def span(name: str, rid: Optional[int] = None):
    """A stage that no clock charges: recorded while a profiler records,
    else a no-op."""
    return Span(name, rid) if _recording() else _OFF


def timed(name: str, rid: Optional[int] = None) -> Clock:
    """A stage whose wall seconds the caller charges: always timed
    (``.seconds``), recorded while a profiler records."""
    return Span(name, rid) if _recording() else Clock()


def _count_compile(event: str, duration: float, **kwargs: Any) -> None:
    if _open and event.endswith("backend_compile_duration"):
        c = _open[-1].counters
        c["compiles"] = c.get("compiles", 0) + 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)
