"""The chat cell as the benchmark names it: its files and metrics found
by name, its end-to-end metrics from the stamps of a run, its logit
check against the configuration's limit, and a traced run whose program
spans the per-layer readers read."""
import dataclasses

import pytest

from bench_cells import CpuAsChip, run, tiny_serving_cell
from bench import harness, serving, traffic
from repro import tracing

CHAT = "sc2-3b.chat"
E2E = ["setup_s", "output_tps", "itl_p50_ms", "itl_p99_ms"]


def test_chat_cell_resolves_its_files_and_metrics():
    bm = harness.load_benchmark()
    cell = harness.resolve(CHAT)
    assert cell.chips == 1
    assert cell.config["name"] == "starcoder2-3b"
    assert cell.config["driver"] == "serving"
    assert cell.config["check"]["max_mean_logit_gap"] is not None
    assert cell.traffic == traffic.load("chat")
    assert [m["name"] for m in cell.end_to_end] == E2E
    listed = [m["name"] for m in bm["per_layer"]
              if CHAT in m.get("workloads", ())]
    assert [m["name"] for m in cell.per_layer] == listed
    assert len(listed) == 9 and all(n.endswith(".chat") for n in listed)


def _chat_cell():
    """The tiny serving cell with the chat cell's metrics."""
    real = harness.resolve(CHAT)
    return dataclasses.replace(tiny_serving_cell(),
                               end_to_end=real.end_to_end,
                               per_layer=real.per_layer)


def test_result_line_carries_the_serving_metrics():
    cell = _chat_cell()
    out = run(cell, seed=2**34 + 5)
    assert out.correct, out.checks
    line = harness.result_line(cell, out, [CpuAsChip()], 1.5, False)
    assert list(line["metrics"]) == E2E
    # printed on standard error, too unsteady for a bound
    assert "ttft_p80_ms" in out.end_to_end
    assert line["metrics"]["setup_s"] == {"value": 1.5, "unit": "s"}
    assert line["metrics"]["output_tps"]["unit"] == "tokens/s"
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "checks"


def _rec(due, stamps):
    spec = traffic.Request(0.0, [1, 2], len(stamps))
    return serving.Rec(spec, due=due, submitted=due, stamps=list(stamps))


def test_latencies_from_hand_built_stamps():
    """Eleven requests due in a 10 s window, their first tokens 100 to
    1100 ms after they were due, then gaps of 20 and 25 ms, two of them
    400 ms instead, as when another request is admitted; one more due
    after the window, whose tokens count for nothing."""
    t0, seconds = 100.0, 10.0
    recs = []
    for i in range(11):
        due = t0 + 0.5 * i
        first = due + 0.1 * (i + 1)
        last = 0.42 if i < 2 else 0.045
        recs.append(_rec(due, [first, first + 0.020, first + last]))
    late = t0 + seconds + 0.5
    recs.append(_rec(late, [late + 0.1, late + 0.2]))
    lat = serving.latencies(recs, t0, seconds)
    e2e = serving.end_to_end(recs, t0, seconds, lat)
    # p80 of 100, 200, ..., 1100 ms lies on the ninth, 900 ms
    assert e2e["ttft_p80_ms"] == pytest.approx(900.0)
    # of 22 gaps, eleven of 20 ms, nine of 25 and two of 400: the median
    # between the eleventh and twelfth, p99 at 20.79 of 21 between the
    # two 400 ms gaps
    assert e2e["itl_p50_ms"] == pytest.approx(22.5)
    assert e2e["itl_p99_ms"] == pytest.approx(400.0)
    assert e2e["output_tps"] == pytest.approx(33 / seconds)
    assert set(e2e) == set(E2E) - {"setup_s"} | {"ttft_p80_ms"}
    # the same gaps in the log: two of 22 hold an admission, and p95
    # lies between the last 25 ms gap and the first 400 ms one
    detail = serving.latency_detail(lat)
    assert detail["itl_stall_share"] == pytest.approx(2 / 22, abs=1e-5)
    assert detail["itl_p95_ms"] == pytest.approx(25 + 0.95 * 375, abs=1e-3)
    assert detail["itl_n"] == 22 and detail["ttft_n"] == 11


@pytest.mark.parametrize("gap,limit,tokens,want", [
    (0.0012, 0.002, 1500, True),
    (0.0021, 0.002, 1500, False),     # above the limit
    (0.0012, None, 1500, False),      # a limit not yet set never passes
    (0.0012, 0.002, 599, False),      # too few tokens compared
])
def test_mean_gap_against_its_limit(gap, limit, tokens, want):
    checks = {"mean_logit_gap": {"value": gap, "limit": limit},
              "checked_tokens": {"value": tokens, "limit": 600}}
    assert serving.passed(checks) is want


#: mean logit gaps read on one TPU v5e at the chat cell's own size: the
#: program's highest over 12 seeds, and the float8 control's on 3 seeds
CHIP_PROGRAM_HIGHEST = 0.001093
CHIP_CONTROL = [0.005186, 0.004632, 0.004492]


@pytest.mark.parametrize("control", CHIP_CONTROL)
def test_chip_control_readings_fail_the_cells_limit(control):
    """The harness's own comparison, at the limit the cell runs with,
    passes the program's highest reading and fails each control's."""
    limit = harness.resolve(CHAT).config["check"]["max_mean_logit_gap"]
    checks = {"mean_logit_gap": {"value": CHIP_PROGRAM_HIGHEST,
                                 "limit": limit},
              "checked_tokens": {"value": 1531, "limit": 600},
              "control_mean_logit_gap": {"value": control, "limit": limit}}
    assert serving.passed(checks)
    assert not serving.passed(serving.as_control(checks))


def test_traced_run_carries_program_spans_the_readers_read():
    cell = _chat_cell()
    rec = tracing.enable()
    try:
        out = run(cell, seed=2**34 + 7, trace=True)
    finally:
        tracing.disable()
    assert out.correct, out.checks
    r = out.run
    assert r.spans is rec
    assert r.window_start == out.window_start < r.window_end
    assert r.trace.window_s == pytest.approx(r.window_end - r.window_start,
                                             abs=0.05)
    assert any(s.name == "repro.admit" for s in r.trace.host)
    read = {m: harness.metric_reader(m)(r) for m in
            ("admit_ms.chat", "padding_lane_share.chat",
             "compiles_in_window.chat")}
    assert read["admit_ms.chat"] > 0
    assert 0 <= read["padding_lane_share.chat"] < 100
    assert read["compiles_in_window.chat"] == 0
    line = harness.result_line(cell, out, [CpuAsChip()], 1.0, True)
    got = set(line["metrics"])
    assert set(read) <= got <= {m["name"] for m in cell.per_layer}
    assert "breakdown" in line and "busy_s" in line["device"]
