"""From a profiler trace to the numbers the per-layer metrics read.

A traced run wraps its measured window in the host annotation
``bench.window`` and its own calls into the program in ``bench.*``
annotations. The profiler writes an ``.xplane.pb``; :func:`load` keeps
of it only what the reduction needs:

* on each accelerator plane, the operations of the ``XLA Ops`` line and
  the programs of the ``XLA Modules`` line, each as ``(name, start, end)``
  in nanoseconds, on the host's clock as the profiler aligns them; each
  operation is given the program whose execution contains it;
* on the host, the ``bench.*`` annotations.

Busy time is the union of the operation intervals inside the window,
averaged over the chips used; the idle share is one minus busy over the
window. Every reduction here clips to the window first.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.window"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    #: the program (XLA module) an operation belongs to, where known
    module: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    """One traced window. Times are nanoseconds on one clock."""
    window: Interval
    #: device id -> operations, sorted by start
    ops: Dict[int, List[Span]]
    #: device id -> program executions, sorted by start
    modules: Dict[int, List[Span]]
    #: the harness's own host annotations (``bench.*``), sorted by start
    host: List[Span]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def devices(self) -> List[int]:
        return sorted(self.ops)

    def busy_s(self, select=None) -> float:
        """Seconds in which some operation (``select(span)`` true, if
        given) ran, inside the window, averaged over the chips."""
        devs = self.devices()
        if not devs:
            return 0.0
        tot = 0.0
        for d in devs:
            spans = self.ops[d] if select is None else \
                [s for s in self.ops[d] if select(s)]
            tot += union_ns(((s.start, s.end) for s in spans), *self.window)
        return tot / len(devs) * 1e-9

    def op_seconds(self, select) -> float:
        """Summed device time of the operations ``select`` accepts,
        clipped to the window, averaged over the chips."""
        devs = self.devices()
        if not devs:
            return 0.0
        lo, hi = self.window
        tot = sum(max(0.0, min(s.end, hi) - max(s.start, lo))
                  for d in devs for s in self.ops[d] if select(s))
        return tot / len(devs) * 1e-9

    def module_runs(self, select) -> List[Span]:
        """Program executions ``select`` accepts that lie wholly inside
        the window, on the first chip."""
        devs = self.devices()
        if not devs:
            return []
        lo, hi = self.window
        return [s for s in self.modules.get(devs[0], ())
                if select(s) and s.start >= lo and s.end <= hi]


def union_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    tot, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        tot += cur_e - cur_s
    return tot


def idle_gaps(spans: Sequence[Span], lo: float, hi: float) -> List[Interval]:
    """The intervals of ``[lo, hi]`` in which no span runs."""
    gaps, t = [], lo
    for s in sorted(spans, key=lambda s: s.start):
        if s.end <= t:
            continue
        if s.start > t:
            gaps.append((t, min(s.start, hi)))
        t = max(t, s.end)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(a, b) for a, b in gaps if b > a]


def program_name(module: str) -> str:
    """A program's name without the fingerprint the trace appends:
    ``jit_positional(1130344114676152293)`` -> ``jit_positional``."""
    return re.sub(r"\(\d+\)$", "", module)


def op_name(name: str) -> str:
    """An operation's HLO name without its text and ``.N`` suffix:
    ``%copy.53 = bf16[...] copy(...)`` -> ``copy``."""
    m = re.match(r"%?([^\s=]+)", name)
    base = m.group(1) if m else name
    return re.sub(r"\.\d+$", "", base)


def op_group(span: Span) -> str:
    """A name under which one kind of operation adds up across layers
    and steps: the program and the operation, each without its id."""
    mod, op = program_name(span.module), op_name(span.name)
    return f"{mod}/{op}" if mod else op


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took the most time, grouped by
    :func:`op_group`, and the longest idle gaps, each named by the
    innermost harness annotation open at its midpoint. On the first
    chip."""
    devs = tr.devices()
    if not devs:
        return {"device_ops": [], "idle_gaps": []}
    lo, hi = tr.window
    by = defaultdict(float)
    for s in tr.ops[devs[0]]:
        d = min(s.end, hi) - max(s.start, lo)
        if d > 0:
            by[op_group(s)] += d * 1e-9
    ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(tr.ops[devs[0]], lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    named = [[host_at(tr.host, (a + b) / 2), (b - a) * 1e-9]
             for a, b in gaps]
    return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": named}


def host_at(host: Sequence[Span], t: float) -> str:
    """The innermost (latest-starting) ``bench.*`` annotation open at
    ``t``, other than the window itself."""
    best = None
    for s in host:
        if s.start > t:
            break
        if s.end >= t and s.name != WINDOW:
            if best is None or s.start >= best.start:
                best = s
    return best.name if best is not None else "outside harness calls"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(trace_dir: str) -> Trace:
    """Read the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    return from_planes(pd.planes)


def from_planes(planes) -> Trace:
    """Build a :class:`Trace` from profiler planes (``ProfileData.planes``
    or anything with the same ``name``/``lines``/``events`` shape)."""
    ops: Dict[int, List[Span]] = {}
    modules: Dict[int, List[Span]] = {}
    host: List[Span] = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            raw = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    raw = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[dev] = sorted(
                        (Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events), key=lambda s: s.start)
            mods = modules.get(dev, [])
            starts = [m.start for m in mods]
            ops[dev] = sorted(
                (Span(n, s, e, module_at(mods, s, starts))
                 for n, s, e in raw), key=lambda s: s.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Span(e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
    host.sort(key=lambda s: s.start)
    wins = [s for s in host if s.name == WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return Trace(window=(wins[0].start, wins[0].end), ops=ops,
                 modules=modules, host=host)


def module_at(modules: Sequence[Span], t: float, starts=None) -> str:
    """The program running at ``t``: the operations of an ``XLA Ops``
    line carry no program of their own, but each lies inside one
    execution on the ``XLA Modules`` line. ``modules`` is sorted by
    start; ``starts`` are their starts, where already at hand."""
    if starts is None:
        starts = [m.start for m in modules]
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i].end >= t:
        return modules[i].name
    return ""


def to_fixture(tr: Trace) -> dict:
    """A JSON-able copy of a trace, for recorded test fixtures."""
    def spans(ss):
        return [[s.name, s.start, s.end, s.module] for s in ss]
    return {"window": list(tr.window),
            "ops": {str(d): spans(v) for d, v in tr.ops.items()},
            "modules": {str(d): spans(v) for d, v in tr.modules.items()},
            "host": spans(tr.host)}


def from_fixture(d: dict) -> Trace:
    def spans(ss):
        return [Span(n, s, e, m) for n, s, e, m in ss]
    return Trace(window=tuple(d["window"]),
                 ops={int(k): spans(v) for k, v in d["ops"].items()},
                 modules={int(k): spans(v) for k, v in d["modules"].items()},
                 host=spans(d["host"]))
