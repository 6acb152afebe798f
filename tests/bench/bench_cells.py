"""Small copies of the benchmark's cells, for running the harness on the
CPU: the configuration files of the real cells with their widths and
depth cut, so that a run fits in a test."""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, traffic  # noqa: E402

#: the mean logit gap the program reads at these sizes, and the float8
#: control's, are in ``test_bench_reference.py``
TINY_MEAN_GAP = 0.002

#: on/off arrivals: 0.5 s at three times the mean rate, then 1 s off
ON_OFF = {"phases": [[0.5, 3.0], [1.0, 0.0]]}


def tiny_serving_cell(arrivals: str = "poisson") -> harness.Cell:
    """The chat cell's files, starcoder2-3b under the chat mix, cut
    small; ``arrivals="on_off"`` sends its requests in bursts."""
    path = ROOT / "bench" / "configs" / "starcoder2-3b.json"
    cfg = dict(json.loads(path.read_text()), config_dir=str(path.parent))
    cfg.update(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, intermediate_size=256,
               vocab_size=512)
    cfg["serving"].update(max_slots=2, max_model_len=64, prefill_chunk=16)
    cfg["check"].update(max_mean_logit_gap=TINY_MEAN_GAP, sample_tokens=120,
                        max_requests=16, min_tokens=40)
    mix = dict(traffic.load("chat"), rate_per_s=10.0,
               prompt={"dist": "cycle", "values": [16, 32]},
               output={"dist": "cycle", "values": [4, 8, 12]})
    if arrivals == "on_off":
        mix["arrivals"] = ON_OFF
    return harness.Cell("sc2-3b.chat", 1, cfg, mix, [], [])


def tiny_program_cell(n: int = 1 << 16) -> harness.Cell:
    cell = harness.resolve("axpydot.paper")
    cfg = dict(cell.config, n=n)
    return harness.Cell(cell.name, 1, cfg, cell.traffic, cell.end_to_end,
                        cell.per_layer)


class CpuAsChip:
    """Stands in for the chip in the result line; the harness's look for
    a chip is skipped, everything after it runs."""
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return None


def run(cell, seed: int, seconds: float = 2.0, trace: bool = False):
    import jax
    del jax  # the driver imports it; keep JAX on the CPU the tests use
    return harness.driver(cell.config).run(
        cell, seed, seconds, trace, [CpuAsChip()], harness.CompileCounter(),
        lambda m: None)


def copy_checkout(dest: Path):
    """``BENCHMARK.json`` and the files under its paths alone: a
    checkout that holds the benchmark and nothing of the program."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    (dest / "BENCHMARK.json").write_text(json.dumps(bm))
    for p in bm["paths"]:
        for f in (ROOT / p).rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                out = dest / f.relative_to(ROOT)
                out.parent.mkdir(parents=True, exist_ok=True)
                out.write_bytes(f.read_bytes())
    return bm
