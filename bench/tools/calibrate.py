"""Readings that set a cell's correctness limit: the program's and the
control's, on several seeds, in one process.

For a serving cell each seed gets a fresh set-up and a short window at
the cell's own load; then the sample of finished requests goes through
the reference twice, in float32 (the program's reading) and with every
product's operands in float8 e4m3 and the residual stream in bfloat16
(the control's reading, read in the float32 logits). The harness's own
comparison judges each at the cell's limit (``passed``,
``control_passed``), and the window's end-to-end metrics are printed
beside. For a program cell each seed makes fresh inputs; the program's
relative error against the float64 reference is read beside the
control's, the same arithmetic computed in bfloat16. One JSON line per
seed::

    python bench/tools/calibrate.py --workload sc2-3b.chat \
        --seconds 20 --seeds 11 12 13
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def log(m):
    print(m, file=sys.stderr, flush=True)


def serving_seed(cell, seed, seconds):
    import jax.numpy as jnp
    from bench import harness, serving
    counter = harness.CompileCounter()
    sess = serving.setup(cell, seed, seconds, log)
    serving.measure(sess, cell.name, seconds, False, counter)
    serving.report(sess, seconds, counter, log)
    lat = serving.latencies(sess.recs, sess.t0, seconds)
    e2e = {**serving.end_to_end(sess.recs, sess.t0, seconds, lat),
           **serving.latency_detail(lat)}
    checks = serving.check(sess, seed, log, control_dtype=jnp.float8_e4m3fn)
    return {**e2e, **{k: v["value"] for k, v in checks.items()},
            "limit": checks["mean_logit_gap"]["limit"],
            "passed": serving.passed(checks),
            "control_passed": serving.passed(serving.as_control(checks))}


def program_seed(cell, seed, compiled):
    import jax.numpy as jnp
    import numpy as np
    from bench import harness, program
    inputs = program.make_inputs(cell.config, seed)
    got = program.call(compiled, inputs)
    ref_mod = harness.reference(cell.config)
    low = ref_mod.axpydot_low(inputs["a"], inputs["x"], inputs["y"],
                              inputs["w"], jnp.bfloat16)
    host = {k: np.asarray(v) for k, v in inputs.items()}
    del inputs
    gc.collect()
    ref = ref_mod.axpydot(host["a"], host["x"], host["y"], host["w"])
    return {"rel_err": abs(got - ref) / abs(ref),
            "control_rel_err": abs(low - ref) / abs(ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    from bench import harness
    harness.prepare_process()
    cell = harness.resolve(args.workload)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    compiled = None
    if cell.config["driver"] == "program":
        from bench import program
        compiled = program.compile_program(cell.config)
    for seed in args.seeds:
        if compiled is None:
            r = serving_seed(cell, seed, args.seconds)
        else:
            r = program_seed(cell, seed, compiled)
        gc.collect()
        print(json.dumps({"workload": cell.name, "seed": seed, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
