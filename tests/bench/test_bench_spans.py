"""The program's spans and counters as the benchmark reads them: kept
from the profiler trace beside the harness's annotations, naming idle
gaps, and reduced by the per-layer readers of ``bench/spans.py``."""
import json
import time
import types
from pathlib import Path

import pytest

from bench_cells import run as run_cell, tiny_program_cell
from bench import harness, program, readers, spans, trace
from repro import tracing

FIXTURES = Path(__file__).resolve().parent / "fixtures"
READERS = ("sdfg_compile_s.axpydot", "xla_compile_s.axpydot",
           "admit_ms.chat", "idle_in_admit.chat", "padding_lane_share.chat")


def _ev(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start)


def _plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k, events=[_ev(*e) for e in v])
        for k, v in lines.items()])


def _planes(ops, host, modules=()):
    return [_plane("/host:CPU", python=host),
            _plane("/device:TPU:0", **{"XLA Ops": ops,
                                       "XLA Modules": modules})]


#: one timeline: the device runs 0-10 and 30-40; the host is inside an
#: admission at 12-28 and inside the step's execute at 29-45
PLANES = _planes(
    ops=[("%fusion.1 = f32[8]{0} fusion()", 0, 10),
         ("%fusion.2 = f32[8]{0} fusion()", 30, 40)],
    host=[("bench.window", 0, 60), ("bench.step", 5, 50),
          ("repro.step", 6, 49), ("repro.admit", 12, 28),
          ("repro.execute", 29, 45), ("other", 0, 60)])


def test_from_planes_keeps_program_spans():
    t = spans.from_planes(PLANES)
    assert [s.name for s in t.host] == ["bench.window", "bench.step",
                                        "repro.step", "repro.admit",
                                        "repro.execute"]
    assert [s.name for s in trace.from_planes(PLANES).host] == \
        ["bench.window", "bench.step"]
    # ``ProfileData.planes`` can be iterated once
    assert spans.from_planes(iter(PLANES)).host == t.host


def test_breakdown_names_a_gap_by_the_program_span_over_it():
    gaps = trace.breakdown(spans.from_planes(PLANES))["idle_gaps"]
    # 10-30 falls in the admission; 40-60 after the program's step ended
    assert gaps == [["repro.admit", pytest.approx(20e-9)],
                    ["bench.step", pytest.approx(20e-9)]]
    assert [g[0] for g in trace.breakdown(trace.from_planes(PLANES))
            ["idle_gaps"]] == ["bench.step", "bench.step"]


def _fixture_planes(t):
    return _planes(ops=[(s.name, s.start, s.end) for s in t.ops[0]],
                   host=[(s.name, s.start, s.end) for s in t.host],
                   modules=[(s.name, s.start, s.end) for s in t.modules[0]])


def test_recorded_fixture_reads_as_before():
    """The recorded chat trace holds no program spans: every reading and
    the breakdown are those the harness's own reduction gives."""
    rec = trace.from_fixture(json.loads((FIXTURES / "chat_trace.json")
                                        .read_text()))
    planes = _fixture_planes(rec)
    old, new = trace.from_planes(planes), spans.from_planes(planes)
    assert new == old
    assert trace.breakdown(new) == trace.breakdown(old) == \
        trace.breakdown(rec)
    for t in (old, new):
        run = types.SimpleNamespace(trace=t, prefill_lens=[64])
        assert readers.idle_share(run) == readers.idle_share(
            types.SimpleNamespace(trace=rec))
        assert readers.decode_step_ms(run) == readers.decode_step_ms(
            types.SimpleNamespace(trace=rec))
        assert readers.prefill_device_share(run) == \
            readers.prefill_device_share(
                types.SimpleNamespace(trace=rec, prefill_lens=[64]))
    assert spans.idle_in(types.SimpleNamespace(trace=new), "admit") is None


class _Recorder:
    """Stands in for ``tracing.Recorder`` with spans of chosen times."""

    def __init__(self, spans_, counts):
        self._spans, self._counts = spans_, counts

    def spans(self, name=None):
        return [s for s in self._spans if name in (None, s.name)]

    def counts(self, name=None):
        return [c for c in self._counts if name in (None, c.name)]


def _span(name, start, end, parent=None):
    s = tracing.Span(None, name, {})
    s.start, s.end, s.parent = int(start), int(end), parent
    return s


def _count(name, value, t, span=None):
    return tracing.Count(name, value, int(t), span, {})


def _program_run():
    """Compiler stages and calls around a window open from 1 s to 2 s."""
    opt = _span("optimize", 1.5e8, 3e8)
    comp = _span("compile", 3e8, 5e8)
    call = _span("call", 6e8, 9e8)
    inner = _span("inner", 7e8, 8e8, parent=call)
    other = _span("other", 6e8, 9e8)
    late = _span("call", 1.1e9, 1.2e9)
    ss = [_span("frontend", 0, 1e8), _span("lower", 1e8, 1.5e8),
          _span("pass", 1.6e8, 2e8, parent=opt), opt,
          _span("codegen", 4e8, 4.5e8, parent=comp), comp, inner, call,
          other, late, _span("compile", 1.3e9, 1.4e9)]
    # JAX reports each event at its end: a trace 0.60-0.65 s, a lowering
    # 0.65-0.75 s with a trace 0.70-0.72 s inside it, a compile 0.75-0.85
    # s, a cache load 0.85-0.86 s in a span inside the call
    cs = [_count("jax.trace_s", 0.05, 6.5e8, call),
          _count("jax.trace_s", 0.02, 7.2e8, call),
          _count("jax.lower_s", 0.1, 7.5e8, call),
          _count("jax.backend_compile_s", 0.1, 8.5e8, call),
          _count("jax.cache_load_s", 0.01, 8.6e8, inner),
          _count("jax.backend_compile_s", 0.5, 6.5e8, other),
          _count("compile_cache.miss", 1, 3.1e8, comp),
          _count("jax.backend_compile_s", 0.3, 1.15e9, late)]
    return types.SimpleNamespace(trace=None, spans=_Recorder(ss, cs),
                                 window_start=1.0, window_end=2.0)


def _chat_run():
    ss = [_span("admit", 0.5e9, 0.6e9), _span("admit", 1.1e9, 1.13e9),
          _span("admit", 1.5e9, 1.51e9), _span("admit", 1.99e9, 2.1e9)]
    # (B, live lanes) of three decode steps, the first before the window
    cs = [_count(name, v, t) for t, b, live in ((0.9e9, 8, 8), (1.2e9, 4, 3),
                                                (1.6e9, 4, 1))
          for name, v in (("sched.lanes", b), ("sched.live_lanes", live))]
    t = spans.from_planes(_planes(
        ops=[("%a = f32[] add()", 0, 20), ("%b = f32[] add()", 50, 100)],
        host=[("bench.window", 0, 100), ("repro.admit", 10, 30),
              ("repro.admit", 40, 45)]))
    return types.SimpleNamespace(trace=t, spans=_Recorder(ss, cs),
                                 window_start=1.0, window_end=2.0)


@pytest.mark.parametrize("name,make,want", [
    ("sdfg_compile_s.axpydot", _program_run, 0.1 + 0.05 + 0.15 + 0.2),
    # the nested trace is not counted twice
    ("xla_compile_s.axpydot", _program_run, 0.05 + 0.1 + 0.1 + 0.01),
    ("admit_ms.chat", _chat_run, (30 + 10) / 2),
    # device idle 20-50; admissions open over 20-30 and 40-45 of it
    ("idle_in_admit.chat", _chat_run, 100 * 15 / 30),
    ("padding_lane_share.chat", _chat_run, 100 * (1 - 4 / 8)),
])
def test_readers_on_synthetic_runs(name, make, want):
    assert harness.metric_reader(name)(make()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_program_spans(name):
    """The run records of a harness that never turns tracing on carry no
    recorder, and their traces no program spans."""
    t = trace.from_planes(PLANES)
    for run in (types.SimpleNamespace(trace=t),
                types.SimpleNamespace(trace=None, spans=None,
                                      window_start=1.0, window_end=2.0)):
        assert harness.metric_reader(name)(run) is None


def test_compile_readers_on_a_real_program_run(monkeypatch):
    """The AXPYDOT cell run traced on the CPU with tracing on: its run
    record carries the recorder and the window; its compile before the
    window splits into the staged compiler's part and JAX's, and both
    lie inside the set-up."""
    monkeypatch.setattr(program, "require_compiled", lambda r, c: None)
    t0 = time.perf_counter()
    rec = tracing.enable()
    try:
        # a size no other test compiles, so that nothing comes from a cache
        out = run_cell(tiny_program_cell(3 << 13), seed=5, seconds=0.3,
                       trace=True)
    finally:
        tracing.disable()
    run = out.run
    assert run.spans is rec
    assert run.window_start == out.window_start < run.window_end
    assert out.correct, out.checks
    sdfg = harness.metric_reader("sdfg_compile_s.axpydot")(run)
    xla = harness.metric_reader("xla_compile_s.axpydot")(run)
    assert sdfg > 0 and xla > 0
    # JAX's compile lies inside the calls that compiled
    assert xla <= sum(s.seconds for s in rec.spans("call")
                      if any(k.startswith("jax.") for k in s.counters))
    assert sdfg + xla < out.window_start - t0
    assert [s.name for s in rec.spans() if s.parent is None][:4] == \
        list(spans.COMPILER_STAGES)
    calls = rec.spans("call")
    assert len(calls) == out.attempted + 2
