"""Host spans and counters — the program's own timeline.

Every layer boundary of the training path opens a span: the trainer's
run, step and the parts of a step, the prefetch thread's batches, a
policy link's replacement.  A span records its name, start and end on
``time.perf_counter_ns``, its id, its parent's id, its thread and a few
attributes (``run``, ``step``).  The parent is the innermost span open
on the same thread, or the id a caller passes when it works on another
thread for that span (the prefetch thread for the trainer's run).

Each span is also a ``jax.profiler.TraceAnnotation`` of the same name
(``StepTraceAnnotation`` for a step), so a profile taken while the
program runs carries every span on its host plane, on the clock that
profile aligns with the device's: an idle gap on the device can be put
down to the span the host was in.  A process that has not imported JAX
runs no profiler, so its spans skip the annotation, and importing this
module does not import JAX.

The store is always on and bounded like the flight recorder: a ring of
``CAPACITY`` spans that overwrites the oldest and counts each one it
overwrote in ``dropped``.  A span enters the store when it opens (its
``end_ns`` is None until it closes), so the store also shows the work in
flight.  Counters are named totals.  :func:`snapshot` and
:func:`counters` are the read surface.

Every name starts with ``repro.``.
"""

from __future__ import annotations

import collections
import itertools
import sys
import threading
import time
from typing import Any, Deque, Dict, List, Optional

CAPACITY = 1 << 16      # a long training job's last ~8k steps


class Span:
    """One span; ``end_ns`` is None while it is open."""

    __slots__ = ("name", "id", "parent", "thread", "start_ns", "end_ns",
                 "attrs")

    def __init__(self, name: str, id: int, parent: Optional[int],
                 attrs: Dict[str, Any]):
        self.name = name
        self.id = id
        self.parent = parent
        self.thread = threading.get_ident()
        self.attrs = attrs
        self.start_ns = time.perf_counter_ns()
        self.end_ns: Optional[int] = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def as_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "id": self.id, "parent": self.parent,
                "thread": self.thread, "start_ns": self.start_ns,
                "end_ns": self.end_ns, "attrs": dict(self.attrs)}


_ANNOTATIONS = None     # (TraceAnnotation, StepTraceAnnotation)


def _annotations():
    global _ANNOTATIONS
    if _ANNOTATIONS is None and "jax" in sys.modules:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        _ANNOTATIONS = (TraceAnnotation, StepTraceAnnotation)
    return _ANNOTATIONS


class _Open:
    """The context manager :meth:`SpanStore.span` returns; ``with`` binds
    the :class:`Span`."""

    __slots__ = ("store", "name", "parent", "step_trace", "attrs", "span",
                 "trace")

    def __init__(self, store, name, parent, step_trace, attrs):
        self.store, self.name, self.parent = store, name, parent
        self.step_trace, self.attrs = step_trace, attrs

    def __enter__(self) -> Span:
        ann = _annotations()
        self.trace = None
        if ann is not None:
            self.trace = ann[1](self.name, step_num=self.attrs["step"]) \
                if self.step_trace else ann[0](self.name, **self.attrs)
            self.trace.__enter__()
        self.span = self.store._open(self.name, self.parent, self.attrs)
        return self.span

    def __exit__(self, *exc):
        self.span.end_ns = time.perf_counter_ns()
        self.store._stack().pop()
        if self.trace is not None:
            self.trace.__exit__(*exc)
        return False


class SpanStore:
    """A bounded store of spans and named counters, safe to use from any
    thread."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self.dropped = 0
        self._spans: Deque[Span] = collections.deque(maxlen=capacity)
        self._counters: Dict[str, int] = {}
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._local = threading.local()

    def span(self, name: str, *, parent: Optional[int] = None,
             step_trace: bool = False, **attrs) -> _Open:
        """``with store.span(name, step=n) as s:`` records the block as a
        child of ``parent`` (default: this thread's innermost open span).
        ``step_trace`` makes the profile's annotation a step marker
        numbered by ``attrs["step"]``."""
        return _Open(self, name, parent, step_trace, attrs)

    def _stack(self) -> List[int]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _open(self, name, parent, attrs) -> Span:
        st = self._stack()
        if parent is None and st:
            parent = st[-1]
        s = Span(name, next(self._ids), parent, attrs)
        with self._lock:
            if len(self._spans) == self.capacity:
                self.dropped += 1
            self._spans.append(s)
        st.append(s.id)
        return s

    def current(self) -> Optional[int]:
        """The id of this thread's innermost open span, if any."""
        st = self._stack()
        return st[-1] if st else None

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def snapshot(self) -> Dict[str, Any]:
        """``{"capacity", "dropped", "spans"}``: every span held, oldest
        first, as a dict (``end_ns`` None while open)."""
        with self._lock:
            spans = list(self._spans)
            dropped = self.dropped
        return {"capacity": self.capacity, "dropped": dropped,
                "spans": [s.as_dict() for s in spans]}


_STORE = SpanStore()

span = _STORE.span
count = _STORE.count
counters = _STORE.counters
current = _STORE.current
snapshot = _STORE.snapshot
