"""Nested trace spans: always-on ring buffer + jax.profiler annotations.

``span("train_step")`` is the one annotation primitive instrumented code
uses.  It does two things:

- ALWAYS records (name, start, duration, nesting depth, thread) into a
  bounded in-process ring buffer — cheap enough (<~2 us/span: two
  monotonic clock reads and a deque append) to leave on in production,
  exportable as Chrome-trace JSON via :mod:`observability.export`;
- while ANY jax profiler capture is active — ``paddle_tpu.profiler``,
  a bare ``jax.profiler.start_trace`` / ``trace``, or one asked for
  through ``jax.profiler.start_server`` (jax's own
  ``TraceAnnotation.is_enabled()``) — ALSO opens a
  ``jax.profiler.TraceAnnotation`` carrying the span's attributes as the
  event's stats — those given at entry and those ``set`` before the
  exit — so the span lies on the capture's clock next to the XLA device
  activity.

Spans inside a ``to_static``-traced function fire at TRACE time (host
side), which is exactly when the interesting wall-clock cost (retrace +
compile) is paid; the per-execution device time is the profiler's job.

``set_enabled(False)`` turns span recording into a near-free boolean
check — the bench overhead lane flips this to measure instrumentation
cost honestly.

Distributed tracing (docs/observability.md "Fleet tracing"): a
:class:`TraceContext` names one end-to-end request trace.  Install one
ambiently with :class:`use_context` (thread-local), or pass it to a
single span via ``span(..., ctx=...)`` — every span closed under a
context records the trace id, a fresh span id, and its parent span id,
and NESTED spans automatically parent to it.  With no context set
(the default everywhere outside the serving fleet) nothing changes:
one extra thread-local read per span.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
import uuid
from collections import deque

from jax.profiler import TraceAnnotation

__all__ = [
    "span", "SpanRecord", "SpanRecorder", "recorder",
    "set_enabled", "enabled",
    "TraceContext", "use_context", "current_context",
]

_state = [True]                 # list, not bool: mutation without `global`
_tls = threading.local()
_span_seq = itertools.count(1)


def _new_span_id():
    # unique across processes (fleet spools merge): pid + local counter
    return f"{os.getpid():x}.{next(_span_seq)}"


class TraceContext:
    """Identity of one distributed trace: ``(trace_id,
    parent_span_id)``.  Generated once per request at admission
    (:meth:`new`), then carried across processes on the KV-RPC wire
    envelope / handoff blob and re-installed with :class:`use_context`
    so every replica's spans land under the originating request's
    trace id."""

    __slots__ = ("trace_id", "parent_span_id")

    def __init__(self, trace_id, parent_span_id=None):
        self.trace_id = str(trace_id)
        self.parent_span_id = (None if parent_span_id is None
                               else str(parent_span_id))

    @classmethod
    def new(cls, hint=None):
        tid = uuid.uuid4().hex[:16]
        return cls(f"{hint}-{tid}" if hint else tid)

    def to_dict(self):
        return {"t": self.trace_id, "s": self.parent_span_id}

    @classmethod
    def from_dict(cls, d):
        if not d:
            return None
        return cls(d["t"], d.get("s"))

    def __repr__(self):
        return (f"TraceContext({self.trace_id!r}, "
                f"parent={self.parent_span_id!r})")


def current_context():
    """The thread's ambient :class:`TraceContext` (or None)."""
    return getattr(_tls, "ctx", None)


class use_context:
    """Install `ctx` as the thread's ambient trace context for the
    ``with`` scope (``None`` clears it — safe to pass through).  Spans
    opened inside record under it; the previous context is restored on
    exit, so nesting is safe."""

    __slots__ = ("ctx", "_prev")

    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = self.ctx
        return self.ctx

    def __exit__(self, exc_type, exc, tb):
        _tls.ctx = self._prev
        return False


def set_enabled(flag=True):
    """Globally enable/disable span recording; returns previous value."""
    prev = _state[0]
    _state[0] = bool(flag)
    return prev


def enabled():
    return _state[0]


class SpanRecord:
    """One closed span (times in ns, perf_counter_ns clock base).
    ``trace_id``/``span_id``/``parent_id`` are set only for spans
    closed under a :class:`TraceContext`."""

    __slots__ = ("name", "start_ns", "dur_ns", "depth", "thread_id",
                 "attrs", "trace_id", "span_id", "parent_id")

    def __init__(self, name, start_ns, dur_ns, depth, thread_id, attrs,
                 trace_id=None, span_id=None, parent_id=None):
        self.name = name
        self.start_ns = start_ns
        self.dur_ns = dur_ns
        self.depth = depth
        self.thread_id = thread_id
        self.attrs = attrs
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id

    def to_dict(self):
        d = {"name": self.name, "start_ns": self.start_ns,
             "dur_ns": self.dur_ns, "depth": self.depth,
             "thread_id": self.thread_id}
        if self.attrs:
            d["attrs"] = self.attrs
        if self.trace_id is not None:
            d["trace"] = self.trace_id
            d["span"] = self.span_id
            if self.parent_id is not None:
                d["parent"] = self.parent_id
        return d

    def __repr__(self):
        return (f"SpanRecord({self.name!r}, {self.dur_ns / 1e6:.3f} ms, "
                f"depth={self.depth})")


class SpanRecorder:
    """Bounded ring buffer of closed spans + per-name aggregates.

    The buffer holds the most recent `cap` spans (deque maxlen: O(1)
    eviction); aggregates (count, total ns) are kept per name so the
    metrics report can summarize even spans the ring has dropped."""

    def __init__(self, cap=4096):
        # spans close on any thread (thread_id is part of the record);
        # the counter/aggregate read-modify-writes need a guard
        self._lock = threading.Lock()
        self._buf = deque(maxlen=int(cap))
        self._agg = {}              # name -> [count, total_ns]
        self._sinks = ()            # immutable tuple: lock-free read
        self.total_recorded = 0

    @property
    def capacity(self):
        return self._buf.maxlen

    def set_capacity(self, cap):
        with self._lock:
            self._buf = deque(self._buf, maxlen=int(cap))

    def add_sink(self, fn):
        """Attach ``fn(SpanRecord)``, called on every record — the
        fleet telemetry spool's tap.  Sinks run OUTSIDE the recorder
        lock (they do file IO) and a raising sink is dropped from the
        record path's fast tuple read only by :meth:`remove_sink`."""
        with self._lock:
            self._sinks = self._sinks + (fn,)

    def remove_sink(self, fn):
        with self._lock:
            self._sinks = tuple(s for s in self._sinks if s is not fn)

    def record(self, rec):
        with self._lock:
            self.total_recorded += 1
            self._buf.append(rec)
            agg = self._agg.get(rec.name)
            if agg is None:
                self._agg[rec.name] = [1, rec.dur_ns]
            else:
                agg[0] += 1
                agg[1] += rec.dur_ns
        for s in self._sinks:       # tuple snapshot: safe lock-free
            try:
                s(rec)
            except Exception:
                pass                # a broken spool must not kill serving

    def spans(self):
        """Snapshot list of buffered spans, oldest first."""
        with self._lock:
            return list(self._buf)

    @property
    def dropped(self):
        return self.total_recorded - len(self._buf)

    def aggregates(self):
        """{name: {"count": n, "total_ms": t}} over EVERY recorded span
        (including ones the ring buffer has since evicted)."""
        with self._lock:
            items = [(name, c, ns)
                     for name, (c, ns) in sorted(self._agg.items())]
        return {name: {"count": c, "total_ms": round(ns / 1e6, 3)}
                for name, c, ns in items}

    def clear(self):
        with self._lock:
            self._buf.clear()
            self._agg.clear()
            self.total_recorded = 0


_RECORDER = SpanRecorder()


def recorder():
    """THE process-wide span ring buffer (module singleton)."""
    return _RECORDER


class span:
    """Context manager: ``with span("serving.decode", batch=8): ...``.

    Reentrant by construction (each ``with`` entry uses its own
    instance); nesting depth is tracked per thread.  ``ctx`` ties the
    span to a :class:`TraceContext` explicitly; with no ``ctx`` the
    thread's ambient context (see :class:`use_context`) applies, and
    with neither the record carries no trace identity — exactly the
    pre-tracing behavior."""

    __slots__ = ("name", "attrs", "_t0", "_depth", "_ann", "_ctx",
                 "_sid", "_prev")

    def __init__(self, name, ctx=None, **attrs):
        self.name = name
        self.attrs = attrs or None
        self._ctx = ctx

    @property
    def span_id(self):
        """This span's id under its trace (None untraced / unentered)."""
        return getattr(self, "_sid", None)

    def set(self, **attrs):
        """Attributes known only at the end (``admitted``, ``hit``):
        set before the exit, they reach the ring-buffer record and,
        under a capture, the open annotation's event as its stats."""
        self.attrs = {**self.attrs, **attrs} if self.attrs else attrs
        ann = getattr(self, "_ann", None)
        if ann is not None:
            ann.set_metadata(**attrs)

    def __enter__(self):
        if not _state[0]:
            self._t0 = None
            return self
        depth = getattr(_tls, "depth", 0)
        _tls.depth = depth + 1
        self._depth = depth
        ctx = self._ctx
        if ctx is None:
            ctx = getattr(_tls, "ctx", None)
        if ctx is not None:
            self._ctx = ctx
            self._sid = _new_span_id()
            # nested spans parent to THIS span for the with scope
            self._prev = getattr(_tls, "ctx", None)
            _tls.ctx = TraceContext(ctx.trace_id, self._sid)
        else:
            self._sid = None
        self._ann = None
        # under ANY active jax capture: on its timeline, attrs as stats
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name, **(self.attrs or {}))
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb, record=True):
        if self._t0 is None:
            return False
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        _tls.depth = self._depth
        trace = ()
        if self._sid is not None:
            _tls.ctx = self._prev
            trace = (self._ctx.trace_id, self._sid,
                     self._ctx.parent_span_id)
        if record:
            _RECORDER.record(SpanRecord(
                self.name, self._t0, dur, self._depth,
                threading.get_ident(), self.attrs, *trace))
        return False

    def discard(self):
        """Close now, unrecorded (the ``with`` exit is then a no-op):
        an exhausted iterator's last ``next`` waited for nothing."""
        self.__exit__(None, None, None, record=False)
        self._t0 = None
