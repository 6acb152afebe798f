"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found
by name from ``BENCHMARK.json``. With ``--trace 0`` the result carries
the cell's end-to-end metrics; with ``--trace 1`` the window, cut to
``harness.TRACE_SECONDS``, is traced with the program's own spans and
counters turned on (``repro.tracing``), and the result carries its
per-layer metrics. The last line of standard
output is the result, one JSON object; the numbers compared with the
reference are the last lines of standard error and the result's last
key. Without an accelerator, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result: there is no CPU path.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness
    harness.prepare_process()
    if args.trace:
        from repro import tracing
        tracing.enable()
    cell = harness.resolve(args.workload)
    harness.configure_client(cell)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        log(f"bench: {e}")
        return 3
    log(f"device: {devices[0].device_kind} x {len(devices)} "
        f"(platform {devices[0].platform})")
    log(f"compile cache: {harness.enable_compile_cache()}")
    counter = harness.CompileCounter()
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, harness.TRACE_SECONDS)
    out = harness.driver(cell.config).run(
        cell, args.seed, seconds, bool(args.trace), devices, counter, log)
    setup_s = out.window_start - T_START
    log(f"setup_s {setup_s:.6f}")
    line = harness.result_line(cell, out, devices, setup_s,
                               bool(args.trace))
    harness.print_checks(out.checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
