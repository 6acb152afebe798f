"""The program's own spans and counters (``repro.tracing``), for the
per-layer readers.

A traced run reads them in two places:

* the recorder (``repro.tracing.Recorder``) that ``tracing.enable()``
  hands back: every span with its start and end on
  ``time.perf_counter_ns``, the clock the harness stamps its window
  with, and every counter. A run record carries it as ``spans``, beside
  ``window_start`` and ``window_end`` in ``perf_counter`` seconds;
* the profiler trace, where each span is a ``repro.*`` host annotation
  on the same timeline as the device's operations. :func:`from_planes`
  keeps them beside the harness's ``bench.*`` ones, so that
  ``trace.breakdown`` names an idle gap by the innermost span open over
  it, a program span inside a harness one.

Every reduction returns ``None`` where the run holds nothing to read: a
run record without a recorder, or a trace without program spans.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from bench import trace as tr

PROGRAM_PREFIX = "repro."
#: the staged compiler's stages, outermost first
COMPILER_STAGES = ("frontend", "lower", "optimize", "compile")
#: where a jitted program's first call records JAX's compile events
CALL = "call"


def from_planes(planes) -> tr.Trace:
    """:func:`bench.trace.from_planes`, with the program's ``repro.*``
    host spans kept beside the harness's annotations."""
    planes = list(planes)  # ``ProfileData.planes`` is read only once
    t = tr.from_planes(planes)
    extra = [tr.Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PROGRAM_PREFIX)]
    if not extra:
        return t
    host = sorted(t.host + extra, key=lambda s: s.start)
    return dataclasses.replace(t, host=host)


def load(trace_dir: str) -> tr.Trace:
    """:func:`bench.trace.load` with the program's spans kept."""
    from jax.profiler import ProfileData
    return from_planes(ProfileData.from_file(tr.find_xplane(trace_dir))
                       .planes)


def window_ns(run) -> Optional[Tuple[int, int]]:
    """The run's window on ``perf_counter_ns``, or ``None``."""
    lo = getattr(run, "window_start", None)
    hi = getattr(run, "window_end", None)
    if getattr(run, "spans", None) is None or lo is None or hi is None:
        return None
    return int(lo * 1e9), int(hi * 1e9)


def under(span, name: str) -> bool:
    """``span`` or one of its ancestors is named ``name``."""
    while span is not None:
        if span.name == name:
            return True
        span = span.parent
    return False


def sdfg_compile_s(run) -> Optional[float]:
    """Seconds in the staged compiler's outermost stages before the
    window opened: frontend, lower, optimize and compile (passes and
    codegen inside them)."""
    w = window_ns(run)
    if w is None:
        return None
    roots = [s for s in run.spans.spans() if s.parent is None
             and s.name in COMPILER_STAGES and s.end <= w[0]]
    return sum(s.seconds for s in roots) if roots else None


def xla_compile_s(run) -> Optional[float]:
    """Seconds in which JAX traced, lowered (Pallas to Mosaic included),
    compiled or loaded from the persistent cache under the program's
    calls before the window opened. JAX reports each event when it ends,
    and reports a trace nested inside a lowering as an event of its own,
    so the seconds are the union of the events' intervals, not their
    sum."""
    w = window_ns(run)
    if w is None:
        return None
    iv = [(c.t_ns - c.value * 1e9, c.t_ns) for c in run.spans.counts()
          if c.name.startswith("jax.") and c.t_ns <= w[0]
          and under(c.span, CALL)]
    if not iv:
        return None
    return tr.union_ns(iv, min(a for a, _ in iv), w[0]) * 1e-9


def mean_ms(run, name: str) -> Optional[float]:
    """Mean duration of the ``name`` spans inside the window, in ms."""
    w = window_ns(run)
    if w is None:
        return None
    spans = [s for s in run.spans.spans(name)
             if s.start >= w[0] and s.end <= w[1]]
    if not spans:
        return None
    return 1e3 * sum(s.seconds for s in spans) / len(spans)


def padding_lane_share(run) -> Optional[float]:
    """Percent of the decode steps' lanes in the window that carried no
    request: ``1 - sum(live_lanes) / sum(lanes)``."""
    w = window_ns(run)
    if w is None:
        return None

    def total(name):
        return sum(c.value for c in run.spans.counts(name)
                   if w[0] <= c.t_ns <= w[1])

    lanes = total("sched.lanes")
    if lanes <= 0:
        return None
    return 100.0 * (1.0 - total("sched.live_lanes") / lanes)


def idle_in(run, name: str) -> Optional[float]:
    """Percent of the traced window's device idle time (first chip) in
    which a ``repro.<name>`` span was open on the host."""
    t = getattr(run, "trace", None)
    if t is None or not t.devices():
        return None
    full = PROGRAM_PREFIX + name
    spans = [(s.start, s.end) for s in t.host if s.name == full]
    if not spans:
        return None
    lo, hi = t.window
    gaps = tr.idle_gaps(t.ops[t.devices()[0]], lo, hi)
    idle = sum(b - a for a, b in gaps)
    if idle <= 0:
        return None
    inside = sum(tr.union_ns(spans, a, b) for a, b in gaps)
    return 100.0 * inside / idle
