"""Benchmark harness: one module per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--only axpydot,...]
                                               [--small] [--json OUT]
                                               [--calibrate]
Prints ``name,value,derived`` CSV lines; exits non-zero on any failure.
``--small`` shrinks problem sizes for CI smoke runs; ``--json OUT``
additionally writes one machine-readable ``BENCH_<name>.json`` per module
(entries: name, value, derived, backend, small) so the perf trajectory
can be tracked across commits. ``--calibrate`` additionally runs each
module's tile-size sweep (``calibrate(report, small)``) on the current
backend and records the measured per-tile times plus the winning tile —
the measured numbers the GridConversion cost model's static thresholds
should be recalibrated against.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--small", action="store_true",
                    help="reduced problem sizes (CI smoke)")
    ap.add_argument("--json", dest="json_out", default=None, metavar="OUT",
                    help="directory to write BENCH_<name>.json records")
    ap.add_argument("--calibrate", action="store_true",
                    help="sweep tile sizes per module and record the "
                         "measured crossover")
    args = ap.parse_args(argv)

    from repro.codegen.device import enable_compile_cache
    enable_compile_cache()

    from . import (axpydot, gemver, jacobi_chain, lenet, serve_bench,
                   stencil_bench)
    modules = {
        "axpydot": axpydot,            # paper Table 1
        "gemver": gemver,              # paper Table 2
        "lenet": lenet,                # paper Table 3 + fused conv stack
        "stencil": stencil_bench,      # paper Fig. 19
        "jacobi_chain": jacobi_chain,  # halo-fused deep stencil pipeline
        "serve": serve_bench,          # ROADMAP: serve-heavy-traffic
    }
    only = set(args.only.split(",")) if args.only else set(modules)

    if args.json_out:
        os.makedirs(args.json_out, exist_ok=True)

    failed = []
    print("name,value,derived")
    for name, mod in modules.items():
        if name not in only:
            continue
        entries = []

        def report(bname, value, derived="", backend="jnp", **extra):
            print(f"{bname},{value:.6g},{derived}", flush=True)
            entries.append({"name": bname, "value": float(value),
                            "derived": derived, "backend": backend,
                            "small": bool(args.small), **extra})

        try:
            if "small" in inspect.signature(mod.run).parameters:
                mod.run(report, small=args.small)
            else:
                mod.run(report)
            if args.calibrate and hasattr(mod, "calibrate"):
                mod.calibrate(report, small=args.small)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
            print(f"{name},ERROR,{type(e).__name__}: {e}")
        if args.json_out and name not in failed:
            # never write partial records for a failed module: a truncated
            # file would read as a complete (fast!) run to perf tracking
            path = os.path.join(args.json_out, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(entries, f, indent=1)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
