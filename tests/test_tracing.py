"""Spans and counters (``repro.tracing``): off by default and free there,
nested with parents and self time when on, bounded, and placed in the
staged compiler and the serving scheduler."""
import dataclasses
import glob
import itertools
import os
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_config
from repro.frontends import blas
from repro.frontends.api import Program
from repro.models.transformer import TransformerLM
from repro.pipeline import (CompilationCache, DeviceOffloadPass,
                            StreamingCompositionPass, lower)
from repro.serving import Scheduler


@pytest.fixture
def rec():
    r = tracing.enable()
    try:
        yield r
    finally:
        tracing.disable()


def _axpydot(n=256):
    p = Program("axpydot")
    a = p.scalar_input("a", "float32")
    x, y, w = (p.input(nm, (n,)) for nm in ("x", "y", "w"))
    p.output("result", blas.dot(blas.axpy(a, x, y), w))
    return p.finalize()


def _allocated(f, n=10_000):
    """Bytes that ``n`` calls of ``f`` keep, and the most they held at
    once, after a warm-up."""
    calls = itertools.repeat(None, n)
    for _ in range(10):
        f()
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        for _ in calls:
            f()
        now, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    return now - before, peak - before


def test_off_records_nothing_and_allocates_nothing():
    assert tracing.recorder() is None
    assert tracing.span("a") is tracing.NO_SPAN
    assert tracing.span("b", rid=3) is tracing.NO_SPAN

    def bare():  # what a ``with`` statement itself costs
        with tracing.NO_SPAN:
            pass

    def spans():
        with tracing.span("a", rid=3):
            tracing.count("c", 2)

    assert _allocated(lambda: tracing.count("c", 2)) == (0, 0)
    assert _allocated(spans) == _allocated(bare)
    assert _allocated(spans)[0] == 0
    assert tracing.recorder() is None


def test_nesting_gives_parents_and_self_time(rec):
    with tracing.span("outer", rid=7) as outer:
        with tracing.span("inner") as inner:
            pass
        with tracing.span("inner"):
            pass
    spans = rec.spans()
    assert [s.name for s in spans] == ["inner", "inner", "outer"]
    assert spans[0].parent is outer and outer.parent is None
    assert outer.attrs == {"rid": 7}
    assert outer.start <= inner.start <= inner.end <= outer.end
    t = rec.totals()
    assert t["inner"]["count"] == 2 and t["outer"]["count"] == 1
    assert t["inner"]["seconds"] == pytest.approx(
        sum(s.seconds for s in rec.spans("inner")))
    assert t["outer"]["self_seconds"] == pytest.approx(
        t["outer"]["seconds"] - t["inner"]["seconds"])
    assert rec.dropped == 0


def test_the_cap_drops_the_oldest_and_counts_them(monkeypatch):
    monkeypatch.setattr(tracing, "MAX_RECORDS", 3)
    r = tracing.enable()
    try:
        for i in range(5):
            with tracing.span("s", i=i):
                pass
    finally:
        tracing.disable()
    assert [s.attrs["i"] for s in r.spans()] == [2, 3, 4]
    assert r.dropped == 2
    assert r.totals()["s"]["count"] == 5


def test_counters(rec):
    tracing.count("free", 2.5)
    with tracing.span("s") as s:
        tracing.count("hits")
        tracing.count("hits", 2, rid=1)
    assert s.counters == {"hits": 3}
    cs = rec.counts("hits")
    assert [(c.value, c.span, c.attrs) for c in cs] == \
        [(1, s, {}), (2, s, {"rid": 1})]
    assert rec.counts("free")[0].span is None
    assert rec.totals()["hits"] == {"count": 2, "value": 3}


def test_first_call_of_a_jitted_function_counts_jax_compile_under_span(rec):
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.25)
    with tracing.span("call") as s:
        f(jnp.arange(7, dtype=jnp.float32)).block_until_ready()
    assert s.counters.get("jax.trace_s", 0) > 0
    assert s.counters.get("jax.lower_s", 0) > 0
    assert (s.counters.get("jax.backend_compile_s", 0)
            + s.counters.get("jax.cache_load_s", 0)) > 0
    with tracing.span("again") as s2:
        f(jnp.arange(7, dtype=jnp.float32)).block_until_ready()
    assert not any(k.startswith("jax.") for k in s2.counters)


def test_pass_seconds_are_the_pass_spans_duration(rec):
    low = lower(_axpydot())
    low.optimize([DeviceOffloadPass(), StreamingCompositionPass()])
    entries = [e for e in low.reports[-1]["passes"] if not e["skipped"]]
    spans = rec.spans("pass")
    assert [s.attrs["pass"] for s in spans] == [e["name"] for e in entries]
    assert [s.seconds for s in spans] == [e["seconds"] for e in entries]
    opt, = rec.spans("optimize")
    assert all(s.parent is opt for s in spans)


def test_pass_seconds_fill_with_tracing_off():
    low = lower(_axpydot())
    low.optimize([DeviceOffloadPass()])
    entry, = low.reports[-1]["passes"]
    assert entry["seconds"] > 0


def test_compiler_stages_and_cache_counters(rec):
    cache = CompilationCache()
    sdfg = _axpydot()
    c1 = lower(sdfg).optimize([DeviceOffloadPass()]).compile(
        "jnp", cache=cache)
    lower(_axpydot()).optimize([DeviceOffloadPass()]).compile(
        "jnp", cache=cache)
    assert rec.totals()["compile_cache.miss"]["value"] == 1
    assert rec.totals()["compile_cache.hit"]["value"] == 1
    roots = [s.name for s in rec.spans() if s.parent is None]
    assert roots == ["frontend", "lower", "optimize", "compile",
                     "frontend", "lower", "optimize", "compile"]
    comp = rec.spans("compile")[0]
    assert comp.attrs == {"program": "axpydot", "backend": "jnp"}
    codegen, = rec.spans("codegen")
    assert codegen.parent is comp
    ones = {k: np.ones(256, np.float32) for k in ("x", "y", "w")}
    c1(a=np.float32(2.0), **ones)
    call, = rec.spans("call")
    assert call.attrs == {"program": "axpydot"}
    assert call.counters.get("jax.trace_s", 0) > 0


def test_spans_land_on_the_profiler_trace(rec, tmp_path):
    """A span is a ``repro.*`` annotation of the profiler's host plane,
    over the same interval the recorder keeps."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("admit", rid=5) as s:
            jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    events = [e for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:") for ln in p.lines
              for e in ln.events if e.name == "repro.admit"]
    assert len(events) == 1
    assert events[0].duration_ns == pytest.approx(s.end - s.start,
                                                  rel=0.5, abs=2e5)


# ---------------------------------------------------------------------------
# the serving scheduler
# ---------------------------------------------------------------------------
def _tiny_scheduler(**kw):
    cfg = dataclasses.replace(get_config("starcoder2-3b").reduced(),
                              activation_dtype="float32")
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    kw = dict(dict(max_slots=4, page_size=8, n_pages=32, max_model_len=64,
                   prefill_chunk=8), **kw)
    return Scheduler(model, params, **kw)


def test_scheduler_spans_and_counters(rec):
    sched = _tiny_scheduler()
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [3, 1, 4], [2, 7, 1, 8, 2]]
    rids = [sched.submit(p, 4) for p in prompts]
    submit = {r.rid: r.submit_time for r in sched.queue}
    n = 0
    while sched.queue or any(sched.slots):
        sched.step()
        n += 1
    for r in sched.finished:
        assert np.all(np.diff(r.token_times) > 0)
    steps = rec.spans("step")
    assert [s.attrs["step"] for s in steps] == list(range(n))
    assert all(s.parent is None for s in steps)
    for name in ("expire", "bind", "execute", "sample"):
        assert all(s.parent.name == "step" for s in rec.spans(name))
    assert len(rec.spans("expire")) == n
    admits = rec.spans("admit")
    assert sorted(s.attrs["rid"] for s in admits) == rids
    assert {s.attrs["rid"]: s.attrs["tokens"] for s in admits} == \
        {rid: len(p) for rid, p in zip(rids, prompts)}
    for name in ("prefill", "scatter"):
        kids = rec.spans(name)
        assert len(kids) == 3 and all(s.parent.name == "admit" for s in kids)
    # queue wait: from submit to the admission's start
    for c in rec.counts("sched.queue_wait_s"):
        admit, = [s for s in admits if s.attrs["rid"] == c.attrs["rid"]]
        assert c.value == pytest.approx(admit.start * 1e-9
                                        - submit[c.attrs["rid"]], abs=1e-3)
    assert len(rec.counts("sched.queue_wait_s")) == 3
    # prefill chunks of 8: 2 + 1 + 1
    assert rec.totals()["sched.prefill_chunks"]["value"] == 4
    # one lanes/live_lanes pair per decode step: B and the live count
    executes = rec.spans("execute")
    lanes = rec.counts("sched.lanes")
    live = rec.counts("sched.live_lanes")
    assert len(lanes) == len(live) == len(executes) == sched.n_decode_steps
    for e, b, a in zip(executes, lanes, live):
        assert b.value == e.attrs["B"] and b.span.name == "step"
        assert 1 <= a.value <= b.value
    assert max(c.value for c in live) == 3
    first = rec.counts("sched.bucket_first_use")
    assert len(first) == len({(e.attrs["B"], e.attrs["ctx"])
                              for e in executes})
    assert all(c.span.name == "execute" for c in first)


def test_token_times_are_sampling_stamps():
    now = [100.0]
    sched = _tiny_scheduler(clock=lambda: now[0])
    sched.submit([1, 2, 3], 5)
    sched.submit([4, 5, 6, 7], 3)
    while sched.queue or any(sched.slots):
        sched.step()
        now[0] += 0.25
    for r in sched.finished:
        gaps = np.diff(r.token_times)
        assert len(r.token_times) == len(r.tokens_out)
        assert r.token_times[0] == r.first_token_time
        # the first decoded token comes in the step that admitted it
        assert gaps[0] == 0 and np.allclose(gaps[1:], 0.25)
