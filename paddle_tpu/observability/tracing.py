"""Trace spans: named, nested, context-propagated timing scopes.

One recorder feeds three sinks: every span wraps a ``profiler.RecordEvent``
(so an active Profiler window sees it in chrome-trace exports and the
summary table, host-tracer tier included), enters a
``jax.profiler.TraceAnnotation`` (so inside a ``jax.profiler.start_trace``
window it lies in the host plane of the ``.xplane.pb``, on the clock of
the device events; with no session that is a flag check) AND observes its
duration into the ``span_duration_seconds`` histogram of the metrics
registry (so p50/p95/p99 per span name are queryable with no profiler
attached).

Nesting is tracked per thread; ``capture_context()`` / ``attach_context``
carry the active span path across thread (or executor) boundaries, the
way the reference's host tracer threads its correlation ids.
"""
from __future__ import annotations

import threading
import time
from typing import Optional, Tuple

from jax.profiler import TraceAnnotation

from ..profiler import RecordEvent
from .metrics import get_registry

__all__ = ["Span", "span", "current_span", "span_path",
           "capture_context", "attach_context", "traced"]

_TLS = threading.local()


def _stack():
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = []
        _TLS.stack = st
    return st


_HIST_CHILD = {}     # span name -> its span_duration_seconds series


def _span_hist(name: str):
    """The histogram series of one span name, looked up once: the
    registry is process-wide and ``reset()`` zeroes series in place."""
    child = _HIST_CHILD.get(name)
    if child is None:
        child = _HIST_CHILD[name] = get_registry().histogram(
            "span_duration_seconds",
            "trace span wall time by span name",
            labelnames=("span",)).labels(span=name)
    return child


class Span:
    """One named timing scope (context manager, re-usable via span()).
    ``tags`` are small values (a request id, token counts) that go to the
    profiler's annotation as its stats."""

    __slots__ = ("name", "tags", "path", "start_ns", "end_ns", "_record",
                 "_annotation")

    def __init__(self, name: str, **tags):
        self.name = name
        self.tags = tags
        self.path = name          # finalized at __enter__ from the stack
        self.start_ns = None
        self.end_ns = None
        self._record = None
        self._annotation = None

    @property
    def duration_s(self) -> Optional[float]:
        if self.start_ns is None or self.end_ns is None:
            return None
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "Span":
        st = _stack()
        self.path = (st[-1].path + "/" + self.name) if st else self.name
        st.append(self)
        self._record = RecordEvent(self.name)
        self._record.begin()
        self._annotation = TraceAnnotation(self.name, **self.tags)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if self._record is not None:
            self._record.end()
            self._record = None
        st = _stack()
        if self in st:       # tolerate mis-nested exits instead of corrupting
            while st and st[-1] is not self:
                st.pop()
            st.pop()
        _span_hist(self.name).observe(self.duration_s)
        return False


def span(name: str, **tags) -> Span:
    """``with span("decode_step", rid=7): ...`` — the primary entry point."""
    return Span(name, **tags)


def current_span() -> Optional[Span]:
    st = _stack()
    return st[-1] if st else None


def span_path() -> str:
    """Slash-joined active span path of this thread ("" outside spans)."""
    st = _stack()
    return st[-1].path if st else ""


def capture_context() -> Tuple[str, ...]:
    """Token carrying this thread's active span names (for propagation)."""
    return tuple(s.name for s in _stack())


class attach_context:
    """Re-establish a captured span context in another thread::

        token = capture_context()        # producer thread
        ...
        with attach_context(token):      # worker thread
            with span("stage"): ...      # path includes the producer's spans

    The attached parents are name-only placeholders: they do not time or
    re-record the producer's spans, they only restore the nesting path.
    """

    def __init__(self, token: Tuple[str, ...]):
        self._token = tuple(token or ())
        self._placeholders = []

    def __enter__(self):
        st = _stack()
        for name in self._token:
            ph = Span(name)
            ph.path = (st[-1].path + "/" + name) if st else name
            st.append(ph)
            self._placeholders.append(ph)
        return self

    def __exit__(self, *exc):
        st = _stack()
        for ph in reversed(self._placeholders):
            if ph in st:
                while st and st[-1] is not ph:
                    st.pop()
                st.pop()
        self._placeholders = []
        return False


def traced(name: Optional[str] = None):
    """Decorator form: time every call of the function as a span."""
    import functools

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with Span(label):
                return fn(*args, **kwargs)
        return wrapper
    return deco
