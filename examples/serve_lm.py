"""Serving driver: continuous batching over the compiled decode step.

Run: PYTHONPATH=src python examples/serve_lm.py --arch starcoder2-3b
(uses the reduced config on CPU; the full config is exercised by the
multi-pod dry-run.)

Requests with mixed prompt/output lengths stream through the
:class:`repro.serving.Scheduler`: chunked prefill, paged KV cache with a
per-slot block table, and one (B, ctx)-bucketed SDFG-compiled decode
step per iteration — the per-layer attention runs as Pallas grid
kernels inside it. Prints per-request latency, the compiled-step report
(grid kernels vs fallbacks), and the compilation-cache hit rate.

Fault-tolerance modes (ISSUE 8):

* ``--faults`` arms a :class:`repro.serving.ServeFaultPlan` combining a
  step exception, forced page pressure (>= 1 preemption), and a NaN
  logits step, then asserts every request finished with a typed reason
  and that the greedy token streams are byte-identical to a fault-free
  run — the CI fault-injection smoke.
* ``--snapshot-at N`` snapshots mid-decode after N steps, restores into
  a fresh scheduler, and asserts the resumed streams match.
* ``--small`` shrinks everything for CI wall-clock.

Elastic multi-host mode (ISSUE 9): ``--cluster-sim --shrink-at N`` runs
the decode step sharded across a 2-host mesh (shard_map over the
ShardMapPass-partitioned SDFG), shrinks the mesh to 1 host after N
steps mid-decode — preempting the requests living on the dropped
shard — and asserts the greedy streams stay byte-identical to an
unsharded run, with typed ``shrink_preempt``/``mesh_shrink`` events.
"""
import argparse
import os
import sys
import time

# device count is fixed at jax import: simulate the hosts first
if "--cluster-sim" in sys.argv:
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.codegen.device import enable_compile_cache
from repro.configs import get_config
from repro.models import build_model
from repro.pipeline.cache import COMPILATION_CACHE
from repro.serving import FaultInjector, Scheduler, ServeFaultPlan


def build(args, cfg, model, params, injector=None):
    n_pages = args.slots * (args.max_model_len // args.page_size) + 1
    return Scheduler(model, params, max_slots=args.slots,
                     page_size=args.page_size, n_pages=n_pages,
                     max_model_len=args.max_model_len,
                     cache_dtype=args.cache_dtype, injector=injector)


def submit_all(sched, cfg, args):
    rng = np.random.default_rng(0)
    for _ in range(args.requests):  # mixed lengths: continuous batching
        plen = int(rng.integers(4, min(32, args.max_model_len // 2)))
        new = int(rng.integers(4, args.tokens + 1))
        sched.submit(list(rng.integers(0, cfg.vocab, plen)), new)


def streams(reqs):
    return {r.rid: list(r.tokens_out) for r in reqs}


def run_cluster_sim(args, cfg, model, params):
    """Sharded decode across 2 simulated hosts + live mesh shrink."""
    kw = dict(max_slots=4, page_size=4, n_pages=16, max_model_len=16,
              prefill_chunk=4, cache_dtype="float32")
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, cfg.vocab,
                                          int(rng.integers(2, 6)))))
               for _ in range(4)]

    def submit(s):
        for p in prompts:
            s.submit(p, 6)

    base = Scheduler(model, params, **kw)
    submit(base)
    baseline = streams(base.run())
    base.check_invariants()

    sh = Scheduler(model, params, n_shards=2, **kw)
    submit(sh)
    out = streams(sh.run())
    sh.check_invariants()
    assert out == baseline, "sharded streams diverged from unsharded"
    print(f"2-shard mesh: {len(out)} requests byte-identical to the "
          f"unsharded run (mesh {sh.stats()['mesh_signature'][:48]}...)")

    s = Scheduler(model, params, n_shards=2, **kw)
    submit(s)
    for _ in range(args.shrink_at):
        s.step()
    s.shrink(1)
    evs = [e for e in s.events
           if e["kind"] in ("mesh_shrink", "shrink_preempt")]
    print("shrink events:", [(e["kind"], e.get("rid")) for e in evs])
    assert any(e["kind"] == "mesh_shrink" for e in evs)
    out = streams(s.run())
    s.check_invariants()
    assert out == baseline, "streams diverged after the mesh shrink"
    preempted = [e["rid"] for e in evs if e["kind"] == "shrink_preempt"]
    print(f"shrink at step {args.shrink_at}: preempted rids {preempted} "
          f"recomputed; all streams byte-identical after 2 -> 1 hosts")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=24,
                    help="max new tokens per request")
    ap.add_argument("--max-model-len", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--cache-dtype", default="bfloat16")
    ap.add_argument("--small", action="store_true",
                    help="CI-sized run (fewer slots/requests/tokens)")
    ap.add_argument("--faults", action="store_true",
                    help="inject exception+pressure+NaN; assert recovery")
    ap.add_argument("--snapshot-at", type=int, default=None, metavar="N",
                    help="snapshot after N steps, restore, assert "
                         "token-exact resume")
    ap.add_argument("--cluster-sim", action="store_true",
                    help="shard the decode step across 2 simulated "
                         "hosts; assert byte-identical streams")
    ap.add_argument("--shrink-at", type=int, default=3, metavar="N",
                    help="cluster-sim: shrink the mesh 2 -> 1 after N "
                         "steps")
    args = ap.parse_args()
    if args.small:
        args.requests = min(args.requests, 6)
        args.slots = min(args.slots, 4)
        args.tokens = min(args.tokens, 8)
        args.max_model_len = min(args.max_model_len, 64)
        args.page_size = min(args.page_size, 8)
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    if args.cluster_sim:
        import dataclasses
        cfg = dataclasses.replace(cfg, activation_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    if args.cluster_sim:
        run_cluster_sim(args, cfg, model, params)
        return

    baseline = None
    if args.faults or args.snapshot_at is not None:
        base_sched = build(args, cfg, model, params)
        submit_all(base_sched, cfg, args)
        baseline = streams(base_sched.run())
        base_sched.check_invariants()
        print(f"fault-free baseline: {len(baseline)} requests, "
              f"{sum(map(len, baseline.values()))} tokens")

    injector = None
    if args.faults:
        plan = ServeFaultPlan(step_exception_at=1, page_pressure_at=2,
                              page_pressure_release_at=8, nan_logits_at=5)
        injector = FaultInjector(plan)
    sched = build(args, cfg, model, params, injector=injector)
    submit_all(sched, cfg, args)

    if args.snapshot_at is not None:
        for _ in range(args.snapshot_at):
            sched.step()
        snap = sched.snapshot()
        resumed = build(args, cfg, model, params).restore(snap)
        out = streams(resumed.run())
        resumed.check_invariants()
        assert out == baseline, "restored run diverged from baseline"
        print(f"snapshot at step {args.snapshot_at}: restored run is "
              "token-exact")

    t0 = time.perf_counter()
    reqs = sched.run()
    wall = time.perf_counter() - t0
    sched.check_invariants()

    total = sum(len(r.tokens_out) for r in reqs)
    print(f"arch={args.arch} (reduced) slots={args.slots} "
          f"requests={args.requests}")
    print(f"{total} tokens in {wall:.2f}s -> {total / wall:.1f} tok/s "
          f"({sched.n_decode_steps} decode steps)\n")
    print(f"{'rid':>4} {'prompt':>7} {'new':>4} {'reason':>10} "
          f"{'ttft_ms':>8} {'p50_ms':>7} {'p99_ms':>7}")
    for r in reqs:
        steady = np.diff(r.token_times) if len(r.token_times) > 1 \
            else [r.ttft]
        print(f"{r.rid:>4} {len(r.prompt):>7} {len(r.tokens_out):>4} "
              f"{r.finish_reason:>10} {r.ttft * 1e3:>8.1f} "
              f"{np.percentile(steady, 50) * 1e3:>7.2f} "
              f"{np.percentile(steady, 99) * 1e3:>7.2f}")

    if args.faults:
        st = sched.stats()
        print("\nfault recovery:", {k: st[k] for k in
                                    ("preemptions", "fallback_steps",
                                     "recomputes")})
        print("injected:", [e["kind"] for e in injector.events])
        print("watchdog:", [e["kind"] for e in st["watchdog_events"]])
        assert st["preemptions"] >= 1, "page pressure caused no preemption"
        assert all(r.finish_reason for r in reqs), "untyped finish"
        out = streams(reqs)
        assert out == baseline, "faulted streams diverged from fault-free"
        print("faulted run recovered: streams byte-identical to "
              "fault-free baseline")

    print("\ncompiled (B, ctx) buckets:", sorted(sched.compiler._steps))
    for (B, ctx), step in sorted(sched.compiler._steps.items()):
        rep = step.report
        print(f"  ({B}, {ctx}): grid_kernels={rep.get('grid_kernels')} "
              f"fallbacks={rep.get('grid_fallbacks')} rung={step.rung}")
    stats = COMPILATION_CACHE.stats
    print(f"compilation cache: {stats['hits']} hits / "
          f"{stats['misses']} misses ({stats['entries']} entries)")


if __name__ == "__main__":
    main()
