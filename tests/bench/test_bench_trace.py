"""The yardstick's arithmetic: the trace reduction, the FLOP and byte
functions hand-counted, and the peaks table."""
import json
import types
from pathlib import Path

import pytest

from bench_cells import ROOT  # noqa: F401  (puts the checkout on sys.path)
from bench import costs, peaks, program, readers, trace

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _planes():
    # operations name no program: each belongs to the execution on the
    # ``XLA Modules`` line that contains it
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="XLA Ops", events=[
            _ev("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 0, 10),
            _ev("%kernel.1 = bf16[2,128]{1,0} custom-call(bf16[2,128]{1,0} "
                "%q), custom_call_target=\"tpu_custom_call\"", 5, 10),
            _ev("%scatter.1 = bf16[9]{0} scatter(bf16[9]{0} %a)", 20, 10)]),
        types.SimpleNamespace(name="XLA Modules", events=[
            _ev("jit_positional(7)", 0, 15), _ev("jit_scatter(9)", 20, 10)]),
        types.SimpleNamespace(name="Steps", events=[_ev("0", 0, 40)])])
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(name="python", events=[
            _ev("bench.window", 0, 40), _ev("bench.step", 0, 18),
            _ev("bench.wait", 30, 10), _ev("other", 0, 40)])])
    return [host, dev]


def test_union_and_gaps():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30)], 0, 40) == 25
    assert trace.union_ns([(0, 10), (5, 15)], 8, 12) == 4
    spans = [trace.Span("a", 0, 10), trace.Span("b", 5, 15),
             trace.Span("c", 20, 30)]
    assert trace.idle_gaps(spans, 0, 40) == [(15, 20), (30, 40)]


def test_reduction_of_a_small_trace():
    tr = trace.from_planes(_planes())
    assert tr.window == (0, 40) and tr.devices() == [0]
    assert [s.module for s in tr.ops[0]] == \
        ["jit_positional(7)", "jit_positional(7)", "jit_scatter(9)"]
    assert tr.busy_s() == pytest.approx(25e-9)
    assert tr.op_seconds(readers.is_attention_kernel) == pytest.approx(10e-9)
    assert tr.busy_s(lambda s: not readers.is_decode_step(s)) == \
        pytest.approx(10e-9)
    run = types.SimpleNamespace(trace=tr, prefill_lens=[64])
    assert readers.idle_share(run) == pytest.approx(37.5)
    assert readers.prefill_device_share(run) == pytest.approx(40.0)
    assert readers.decode_step_ms(run) == pytest.approx(15e-6)
    bd = trace.breakdown(tr)
    assert bd["device_ops"][0][0] == "jit_positional/fusion"
    assert bd["idle_gaps"] == [["bench.wait", pytest.approx(10e-9)],
                               ["bench.step", pytest.approx(5e-9)]]
    # a fixture round-trips
    again = trace.from_fixture(json.loads(json.dumps(trace.to_fixture(tr))))
    assert again.busy_s() == tr.busy_s()


def test_recorded_chip_trace():
    """18 ms of a chat window recorded on one TPU v5e around one run of
    the compiled decode step. Operation names are cut to their first 100
    characters; the attention kernels' ``custom_call_target``, which the
    cut removed, is restored on the ops named after the step's function
    (``%positional.N``). Each operation finds its program by time alone,
    the step's 30 attention kernels (one per layer) are found, and the
    step's device time is its run on the ``XLA Modules`` line."""
    tr = trace.from_fixture(json.loads((FIXTURES / "chat_trace.json")
                                       .read_text()))
    assert tr.devices() == [0]
    mods = tr.modules[0]
    assert all(trace.module_at(mods, s.start) == s.module for s in tr.ops[0])
    assert 0 < tr.busy_s() <= tr.window_s
    steps = tr.module_runs(
        lambda s: bool(readers.DECODE_STEP_MODULE.match(s.name)))
    assert len(steps) == 1
    step = steps[0]
    kernels = [s for s in tr.ops[0] if readers.is_attention_kernel(s)]
    assert len(kernels) == 30
    assert all(step.start <= s.start and s.end <= step.end for s in kernels)
    assert 0 < tr.op_seconds(readers.is_attention_kernel) < step.dur * 1e-9
    run = types.SimpleNamespace(trace=tr, prefill_lens=[64])
    assert readers.decode_step_ms(run) == pytest.approx(step.dur * 1e-6)
    assert 0 < readers.prefill_device_share(run) < 100
    assert 0 < readers.idle_share(run) < 100


def test_costs_hand_counted():
    d = costs.DecoderDims(n_layers=30, d_model=3072, n_heads=24,
                          n_kv_heads=2, head_dim=128, d_ff=12288,
                          vocab=49152)
    # per layer: q and o 3072x3072, k and v 3072x256, MLP 2 x 3072x12288
    assert costs.matmul_params(d) == \
        30 * (3072 * 128 * 52 + 2 * 3072 * 12288) + 3072 * 49152
    # one decode bucket, two live lanes at contexts 100 and 300
    flops, nbytes = costs.decode_attention_cost(d, [100, 300])
    assert flops == 30 * 4 * 24 * 128 * 400 == 147_456_000
    kv = 2 * 400 * 2 * 128 * 2          # K and V, 2 KV heads, bf16
    qo = 2 * 2 * 24 * 128 * 2           # q and output of 2 lanes
    assert nbytes == 30 * (kv + qo) == 13_025_280
    assert costs.prefill_flops(d, 3) == sum(
        costs.token_flops(d, c) for c in (1, 2, 3))
    # AXPYDOT at the paper's n: x, y, w read once, float32
    n = 209_715_200
    assert costs.axpydot_cost(n) == (4 * n, 12 * n)
    p = peaks.peaks("TPU v5 lite")
    assert costs.roofline_seconds(*costs.axpydot_cost(n), p["bf16_flops"],
                                  p["hbm_bytes_per_s"]) == \
        pytest.approx(12 * n / 819e9)


def test_axpydot_roofline_share():
    tr = trace.from_planes(_planes())
    run = program.ProgramRun(trace=tr, peaks=peaks.peaks("TPU v5 lite"),
                             n=1000, itemsize=4, calls=2)
    share = program.roofline_share(run, lambda s: "scatter(" in s.name)
    assert share == pytest.approx(100 * 2 * 12000 / 819e9 / 10e-9)
    assert program.roofline_share(run, lambda s: False) is None


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")
