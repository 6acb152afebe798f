"""The harness is driven by data: every cell finds its files by name,
and new files in a copy are found without an edit. Without a chip the
run exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

from bench_cells import ROOT, copy_checkout
from bench import harness, traffic


def test_every_cell_resolves_its_files():
    bm = harness.load_benchmark()
    for w in bm["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell.chips == w["chips"] == 1
        assert harness.driver(cell.config).run
        if "reference" in cell.config:
            assert harness.reference(cell.config)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
            assert m["moves"] in e2e
    for c in bm["configs"]:
        assert (ROOT / c["file"]).is_file()


GENERATOR = """
from bench.traffic import Request

def generate(mix, seed, seconds, vocab, max_len):
    # every request waiting at the start, a backlog
    return [Request(0.0, [1 + (seed + i) % (vocab - 1)] * mix["prompt_len"],
                    mix["answer_len"]) for i in range(mix["backlog"])]
"""

SWIGLU_MODEL = """
def model_config(cfg):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=cfg["name"], family="dense", n_layers=2,
                       d_model=64, n_heads=4, n_kv_heads=1, d_head=16,
                       d_ff=128, vocab=256, norm="rmsnorm", act="swiglu",
                       param_dtype="bfloat16")
"""


def test_new_files_are_found_without_an_edit(tmp_path):
    bm = copy_checkout(tmp_path)
    cfg = json.loads((tmp_path / "bench/configs/starcoder2-3b.json")
                     .read_text())
    cfg["name"] = "other-model"
    (tmp_path / "bench/configs/other-model.json").write_text(json.dumps(cfg))
    # a second model family: its own configuration and model file
    (tmp_path / "bench/configs/swiglu_model.py").write_text(SWIGLU_MODEL)
    (tmp_path / "bench/configs/swiglu-tiny.json").write_text(json.dumps(
        dict(cfg, name="swiglu-tiny", model="swiglu_model.py")))
    mix = dict(traffic.load("chat"), rate_per_s=1.5)
    (tmp_path / "bench/traffic/slow-chat.json").write_text(json.dumps(mix))
    # a new kind of arrivals: a mix that names its own generator
    (tmp_path / "bench/traffic/backlog_gen.py").write_text(GENERATOR)
    (tmp_path / "bench/traffic/backlog.json").write_text(json.dumps(
        {"kind": "backlog", "generator": "backlog_gen.py", "backlog": 5,
         "prompt_len": 7, "answer_len": 3}))
    (tmp_path / "bench/metrics/queue_len.slow.py").write_text(
        "def read(run):\n    return 41 + run\n")
    bm["configs"] += [
        dict(bm["configs"][0], name="other-model",
             file="bench/configs/other-model.json"),
        dict(bm["configs"][0], name="swiglu-tiny",
             file="bench/configs/swiglu-tiny.json")]
    bm["workloads"] += [
        {"name": "other.slow", "config": "other-model",
         "traffic": "slow-chat", "chips": 1, "why": "x"},
        {"name": "swiglu.backlog", "config": "swiglu-tiny",
         "traffic": "backlog", "chips": 1, "why": "x"}]
    bm["per_layer"].append({"name": "queue_len.slow", "unit": "count",
                            "better": "lower", "source": "program_counter",
                            "layer": "scheduler",
                            "moves": bm["end_to_end"][0]["name"],
                            "workloads": ["other.slow"]})
    for m in bm["end_to_end"]:
        if "workloads" in m:
            m["workloads"] += ["other.slow", "swiglu.backlog"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = harness.resolve("other.slow", root=tmp_path)
    assert cell.config["name"] == "other-model"
    assert cell.traffic["rate_per_s"] == 1.5
    assert [m["name"] for m in cell.per_layer] == ["queue_len.slow"]
    assert harness.metric_reader("queue_len.slow", root=tmp_path)(1) == 42

    cell = harness.resolve("swiglu.backlog", root=tmp_path)
    reqs = traffic.generate(cell.traffic, 2**40 + 3, 10.0, 256, 64)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in reqs] \
        == [(0.0, 7, 3)] * 5
    from bench import serving
    model = serving.build_model(cell.config)
    assert model.cfg.act == "swiglu" and model.cfg.norm == "rmsnorm"


def _run_cpu(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = harness.load_benchmark()["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell,
         "--seed", str(2**40 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_chip():
    p = _run_cpu(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_bare_checkout_exits_nonzero(tmp_path):
    copy_checkout(tmp_path)
    p = _run_cpu(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    shutil.rmtree(tmp_path)


def _work(reqs):
    return sorted((len(r.prompt), r.max_new_tokens) for r in reqs)


def test_traffic_keeps_the_work_across_seeds():
    mix = traffic.load("chat")
    a = traffic.generate(mix, 1, 200, 49152, 512)
    b = traffic.generate(mix, 2**40 + 7, 200, 49152, 512)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 200)
    assert _work(a) == _work(b)
    # one order for every seed: the seed draws the token ids alone
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(len(r.prompt) % 64 == 0 for r in a)
    assert all(len(r.prompt) + r.max_new_tokens < 512 for r in a)
    for rs in (a, b):
        due = [r.due_s for r in rs]
        assert due == sorted(due) and due[0] == 0 and due[-1] < 200
    gaps = lambda rs: sorted(  # noqa: E731
        round(y.due_s - x.due_s, 6) for x, y in zip(rs, rs[1:]))
    assert len(set(gaps(a)) & set(gaps(b))) >= len(a) - 3


def test_on_off_arrivals_are_data():
    """Bursts are a mix's data: arrivals fall only in the on phases, the
    work is the same for every seed, and the mean rate is kept."""
    on, off = 2.0, 3.0
    mix = dict(traffic.load("chat"),
               arrivals={"phases": [[on, 2.5], [off, 0.0]]})
    a = traffic.generate(mix, 5, 100, 49152, 512)
    b = traffic.generate(mix, 2**40 + 9, 100, 49152, 512)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 100)
    assert _work(a) == _work(b)
    for rs in (a, b):
        due = [r.due_s for r in rs]
        assert due == sorted(due) and due[-1] < 100
        assert all(d % (on + off) <= on + 1e-9 for d in due)
