"""Run one cell traced and write what its trace holds.

A look at a trace by hand before trusting the reduction: per program,
how often it ran, its time on the ``XLA Modules`` line and the union of
its operations; the operations that took the most time in each of the
busiest programs; and a fixture in the form of
``bench.trace.to_fixture``: a slice of the window around one run of a
chosen program, every operation and program in it, names cut short. Writes
``bench-out/trace_dump/<cell>.json``::

    python bench/tools/trace_dump.py --workload sc2-3b.chat --seconds 8
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import readers  # noqa: E402


def summary(tr, trace):
    """Per program on the first chip: runs, module seconds, op seconds
    (union), Pallas kernel runs, and the top operation groups."""
    lo, hi = tr.window
    dev = tr.devices()[0]
    mods = collections.defaultdict(lambda: [0, 0.0])
    for m in tr.modules.get(dev, []):
        if lo <= m.start and m.end <= hi:
            g = mods[trace.program_name(m.name)]
            g[0] += 1
            g[1] += m.dur * 1e-9
    by_prog = collections.defaultdict(list)
    for s in tr.ops[dev]:
        by_prog[trace.program_name(s.module)].append(s)
    progs = {}
    for p, spans in by_prog.items():
        u = trace.union_ns(((s.start, s.end) for s in spans), lo, hi) * 1e-9
        groups = collections.Counter()
        for s in spans:
            groups[trace.op_name(s.name)] += s.dur * 1e-9
        n, mod_s = mods.get(p, (0, 0.0))
        kernels = sum(1 for s in spans if readers.PALLAS_KERNEL.search(s.name))
        progs[p] = {"runs": n, "module_s": mod_s, "ops_union_s": u,
                    "pallas_kernels": kernels,
                    "top_ops": groups.most_common(12)}
    return {"busy_ops_s": tr.busy_s(),
            "busy_modules_s": trace.union_ns(
                ((m.start, m.end) for m in tr.modules.get(dev, [])),
                lo, hi) * 1e-9,
            "window_s": tr.window_s,
            "programs": dict(sorted(progs.items(),
                                    key=lambda kv: -kv[1]["ops_union_s"]))}


def short(name: str, cut: int = 100) -> str:
    """The head of an operation's HLO text, and the target of its call
    if it is a Pallas kernel (what ``bench.readers`` looks for)."""
    m = readers.PALLAS_KERNEL.search(name)
    return name[:cut] + (", " + m.group(0) if m and m.start() >= cut else "")


def excerpt(tr, trace, at: str, length_ns: float):
    """A slice of the window as a fixture: from just before the first
    run of a program matching ``at`` in the window's second half (or
    from its middle, where none ran)."""
    lo, hi = tr.window
    a = (lo + hi) / 2
    runs = [m for m in tr.modules.get(tr.devices()[0], [])
            if m.start >= a and re.match(at, m.name)]
    if runs:
        a = max(lo, runs[0].start - 1e6)
    b = min(hi, a + length_ns)

    def keep(ss):
        return [dataclasses.replace(s, name=short(s.name))
                for s in ss if a <= s.start and s.end <= b]
    part = trace.Trace(
        window=(a, b), ops={d: keep(v) for d, v in tr.ops.items()},
        modules={d: keep(v) for d, v in tr.modules.items()},
        host=[s for s in tr.host if s.end >= a and s.start <= b
              and s.name != trace.WINDOW])
    part.host.append(trace.Span(trace.WINDOW, a, b))
    part.host.sort(key=lambda s: s.start)
    return trace.to_fixture(part)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=8)
    ap.add_argument("--excerpt-ms", type=float, default=60)
    ap.add_argument("--excerpt-at", default=r"jit_positional\b")
    args = ap.parse_args(argv)
    from bench import harness, trace
    harness.prepare_process()
    cell = harness.resolve(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    keep: dict = {}
    plain = harness.traced

    @contextlib.contextmanager
    def traced(enabled, name):
        with plain(enabled, name) as h:
            yield h
        keep["trace"] = h.trace

    harness.traced = traced
    out = harness.driver(cell.config).run(
        cell, args.seed, args.seconds, True, devices,
        harness.CompileCounter(), lambda m: print(m, file=sys.stderr))
    tr = keep["trace"]
    line = harness.result_line(cell, out, devices, 0.0, True)
    dump = {"result": line, "summary": summary(tr, trace),
            "fixture": excerpt(tr, trace, args.excerpt_at,
                               args.excerpt_ms * 1e6)}
    dest = harness.OUT_DIR / "trace_dump"
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{cell.name}.json").write_text(json.dumps(dump, indent=1))
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
