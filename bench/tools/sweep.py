"""Find a serving cell's knee: the highest offered rate it sustains.

One set-up, then one window per rate on the same scheduler, each with
fresh open-loop traffic from the cell's mix at that rate, and a drain
between windows. For each rate it prints the cell's end-to-end latency
and token metrics, and how many requests due in the window were still
queued when it closed: a queue that grows through the window means the
rate is past the knee. One JSON line per rate::

    python bench/tools/sweep.py --workload sc2-3b.chat --seconds 30 \
        --rates 3 4.5 6 7.5 9
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def log(m):
    print(m, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    from bench import harness, serving, traffic
    harness.prepare_process()
    cell = harness.resolve(args.workload)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    top = dict(cell.traffic, rate_per_s=max(args.rates))
    sess = serving.setup(dataclasses.replace(cell, traffic=top), args.seed,
                         args.seconds, log)
    sv = cell.config["serving"]
    for rate in args.rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        specs = traffic.generate(mix, args.seed, args.seconds,
                                 cell.config["vocab_size"],
                                 sv["max_model_len"])
        recs = [serving.Rec(s) for s in specs]
        t0, _, _ = serving.serve(sess.sched, recs, args.seconds,
                                 contextlib.nullcontext(),
                                 drain_first_tokens=False)
        queued = len(sess.sched.queue)
        lat = serving.latencies(recs, t0, args.seconds)
        e2e = {**serving.end_to_end(recs, t0, args.seconds, lat),
               **serving.latency_detail(lat)}
        due = [r for r in recs if r.due < t0 + args.seconds]
        first = sum(1 for r in due if r.stamps)
        sess.sched.queue.clear()
        sess.sched.run()
        print(json.dumps({"rate_per_s": rate, **e2e, "due": len(due),
                          "got_first_token": first,
                          "queued_at_close": queued}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
