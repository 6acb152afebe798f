"""Spans and counters of the program's own layers.

Off by default. Then :func:`span` makes one check and hands back a shared
no-op context, and :func:`count` returns at once. :func:`enable` turns
tracing on for the process and returns the :class:`Recorder` that keeps
what follows:

* every span opens ``jax.profiler.TraceAnnotation("repro.<name>")``, so
  that a profiler trace running at the time holds it on its host plane,
  on the same timeline as the device's operations;
* every span is also kept in memory with its start and end on
  ``time.perf_counter_ns``, its parent (the innermost span open on the
  same thread when it opened), its attributes and the counters recorded
  while it was the innermost one open. The buffer holds at most
  :data:`MAX_RECORDS` spans and as many counter records; the oldest are
  dropped first and counted in ``Recorder.dropped``.

JAX's compile events are counted too: trace, lowering (Pallas to Mosaic
included), backend compile and persistent-cache load each add their
seconds as a ``jax.*`` counter of the innermost open span.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Deque, Dict, List, Optional

#: most spans (and, apart, most counter records) a recorder keeps
MAX_RECORDS = 200_000

#: JAX's compile events -> the counters they are recorded as
JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower_s",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load_s",
}

_recorder: Optional["Recorder"] = None
_listening = False


class Span:
    """One span: ``name`` without the ``repro.`` prefix, ``start`` and
    ``end`` in ``perf_counter`` nanoseconds, the enclosing ``parent``
    (a :class:`Span` or ``None``), ``attrs`` and ``counters``."""

    __slots__ = ("rec", "name", "attrs", "start", "end", "parent",
                 "child_ns", "counters", "_ann")

    def __init__(self, rec, name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs
        self.start = self.end = self.child_ns = 0
        self.parent = None
        self.counters: Dict[str, float] = {}

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9

    def __enter__(self) -> "Span":
        rec = self.rec
        if rec is not None:
            import jax
            stack = rec._stack()
            self.parent = stack[-1] if stack else None
            self._ann = jax.profiler.TraceAnnotation(f"repro.{self.name}",
                                                     **self.attrs)
            self._ann.__enter__()
            stack.append(self)
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter_ns()
        rec = self.rec
        if rec is not None:
            rec._stack().pop()
            self._ann.__exit__(*exc)
            self._ann = None
            rec._close(self)
        return False

    def __repr__(self):
        return (f"Span({self.name}, {self.seconds:.6f} s, "
                f"{self.attrs}, {self.counters})")


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


#: what :func:`span` returns while tracing is off
NO_SPAN = _NoSpan()

Count = collections.namedtuple("Count", "name value t_ns span attrs")


class Recorder:
    """What one :func:`enable` recorded: :meth:`spans`, :meth:`counts`
    and the per-name :meth:`totals`."""

    def __init__(self):
        self._spans: Deque[Span] = collections.deque(maxlen=MAX_RECORDS)
        self._counts: Deque[Count] = collections.deque(maxlen=MAX_RECORDS)
        self.dropped = 0
        #: span name -> [count, seconds, self seconds]
        self._span_totals: Dict[str, List[float]] = {}
        #: counter name -> [count, value]
        self._count_totals: Dict[str, List[float]] = {}
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _keep(self, buf: Deque, item):
        if len(buf) == buf.maxlen:
            self.dropped += 1
        buf.append(item)

    def _close(self, s: Span):
        dur = s.end - s.start
        if s.parent is not None:
            s.parent.child_ns += dur
        with self._lock:
            self._keep(self._spans, s)
            t = self._span_totals.setdefault(s.name, [0, 0.0, 0.0])
            t[0] += 1
            t[1] += dur * 1e-9
            t[2] += (dur - s.child_ns) * 1e-9

    def _count(self, name: str, value: float, attrs: dict):
        stack = self._stack()
        top = stack[-1] if stack else None
        if top is not None:
            top.counters[name] = top.counters.get(name, 0) + value
        with self._lock:
            self._keep(self._counts, Count(name, value, time.perf_counter_ns(),
                                           top, attrs))
            t = self._count_totals.setdefault(name, [0, 0.0])
            t[0] += 1
            t[1] += value

    def spans(self, name: Optional[str] = None) -> List[Span]:
        """Closed spans in the order they closed (named ``name``, if
        given)."""
        with self._lock:
            return [s for s in self._spans if name is None or s.name == name]

    def counts(self, name: Optional[str] = None) -> List[Count]:
        with self._lock:
            return [c for c in self._counts if name is None or c.name == name]

    def totals(self) -> Dict[str, dict]:
        """Per span name ``count``, ``seconds`` and ``self_seconds`` (the
        time its child spans do not cover); per counter name ``count``
        and ``value``, the sum."""
        with self._lock:
            out = {k: {"count": n, "seconds": s, "self_seconds": own}
                   for k, (n, s, own) in self._span_totals.items()}
            out.update({k: {"count": n, "value": v}
                        for k, (n, v) in self._count_totals.items()})
            return out


def span(name: str, **attrs):
    """A span named ``repro.<name>`` while tracing is on; the shared
    :data:`NO_SPAN` while it is off."""
    rec = _recorder
    if rec is None:
        return NO_SPAN
    return Span(rec, name, attrs)


def timed(name: str, **attrs) -> Span:
    """A span that times itself (``seconds``) whether tracing is on or
    not, and is recorded only when it is: for a duration the program
    reports anyway, measured once."""
    return Span(_recorder, name, attrs)


def count(name: str, value: float = 1, **attrs):
    """Add ``value`` to counter ``name`` of the innermost open span."""
    rec = _recorder
    if rec is not None:
        rec._count(name, value, attrs)


def _on_jax_event(event: str, duration: float, **kw):
    rec = _recorder
    if rec is not None and event in JAX_EVENTS:
        rec._count(JAX_EVENTS[event], duration, {})


def enable() -> Recorder:
    """Turn tracing on with a new :class:`Recorder`, and return it."""
    global _recorder, _listening
    if not _listening:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        _listening = True
    _recorder = Recorder()
    return _recorder


def disable():
    """Turn tracing off; a recorder already handed out stays readable."""
    global _recorder
    _recorder = None


def recorder() -> Optional[Recorder]:
    """The recorder in use, or ``None`` while tracing is off."""
    return _recorder
