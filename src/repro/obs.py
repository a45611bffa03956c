"""In-memory spans and counters of the program's own layers.

``span(name, **attrs)`` times a block on the host clock
(``time.perf_counter_ns``) and records it with its own id and the id of
the span open around it when it started (0 for a root), so the records
form one tree per call into the program.  ``count(name, n)`` adds to a
counter of the innermost open span, ``tag(**attrs)`` sets its attributes.
Each span also opens a ``jax.profiler.TraceAnnotation`` of its name: in a
profiled run it sits in the profiler's host plane, on the device planes'
clock, so an idle gap of the device can be put down to the span around it.
A listener on JAX's backend-compile event counts ``compiles`` on the
innermost open span of the process's recorder.

Recording is always on and bounded: finished spans go into a ring of
``capacity`` records, and the recorder counts the spans it dropped (and
the largest id among them), so a reader can tell when it lacks part of
what it asks for.  Nothing here waits for the device: a span around an
asynchronous dispatch measures the host's enqueue, and a span that
includes a sync the code already makes ends after it.

Names carry their layer as a prefix: ``serve.`` (serving loop), ``elastic.``
(control), ``plan.`` (planner and plan check), ``migrate.`` (executor and
device state).  ``src/repro/runtime/README.md`` lists them.  Durations are
for readers after the fact; no plan may depend on one.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax import monitoring
from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CAPACITY = 65536
_now = time.perf_counter_ns


class Span:
    """One timed block; a context manager while open, a record after."""

    __slots__ = ("name", "id", "parent", "t0", "t1", "attrs", "counts",
                 "_rec", "_stack", "_ann")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict):
        self.name = name
        self.attrs = attrs
        self.counts: Dict[str, float] = {}
        self._rec = rec

    @property
    def dur_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def __enter__(self) -> "Span":
        rec = self._rec
        self._stack = stack = rec._stack()
        self.parent = stack[-1].id if stack else 0
        self.id = next(rec._ids)
        stack.append(self)
        self._ann = ann = TraceAnnotation(self.name)
        ann.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = _now()
        self._ann.__exit__(*exc)
        self._stack.pop()
        self._rec.add(self)
        self._rec = self._stack = self._ann = None
        return False

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"{self.dur_s * 1e3:.3f} ms, {self.attrs}, {self.counts})")


class Recorder:
    """A bounded ring of finished spans, and each thread's open ones."""

    def __init__(self, capacity: int = CAPACITY):
        self._buf: deque = deque(maxlen=capacity)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self.dropped = 0           # finished spans pushed out of the ring
        self.dropped_max_id = 0    # the largest id among them

    def _stack(self) -> List[Span]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def count(self, name: str, n: float = 1) -> None:
        stack = self._stack()
        if stack:
            c = stack[-1].counts
            c[name] = c.get(name, 0) + n

    def tag(self, **attrs) -> None:
        stack = self._stack()
        if stack:
            stack[-1].attrs.update(attrs)

    def add(self, span: Span) -> None:
        """Append a finished span, dropping the oldest where full."""
        buf = self._buf
        self._lock.acquire()
        if len(buf) == buf.maxlen:
            self.dropped += 1
            self.dropped_max_id = max(self.dropped_max_id, buf[0].id)
        buf.append(span)
        self._lock.release()

    def spans(self) -> List[Span]:
        """The finished spans held, oldest first (in the order they ended)."""
        with self._lock:
            return list(self._buf)

    def last(self, name: str) -> Optional[Span]:
        """The span named ``name`` that ended last; None where none is
        held."""
        with self._lock:
            for s in reversed(self._buf):
                if s.name == name:
                    return s
        return None

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.dropped = self.dropped_max_id = 0

    def _on_event(self, event: str, duration_secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count("compiles")


RECORDER = Recorder()
monitoring.register_event_duration_secs_listener(RECORDER._on_event)

# the process's recorder, as plain functions: ``with span("plan.dp"): ...``
span = RECORDER.span
count = RECORDER.count
tag = RECORDER.tag
