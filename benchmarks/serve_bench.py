"""Serving throughput: compiled decode step vs ``jax.jit(decode_step)``.

For each config the baseline decodes against the full dense
``max_model_len`` cache through ``jax.jit(model.decode_step)`` — the
straightforward serving loop — while the compiled path runs the
:mod:`repro.serving` scheduler: paged KV cache, (B, ctx) shape-bucketed
SDFG steps with the attention lowered to Pallas grid kernels, donated
page buffers, and contexts bounded by the live sequences instead of the
model limit.

Entries (tokens/sec, higher is better):
  ``serve_<arch>_b<B>_baseline_tps`` / ``serve_<arch>_b<B>_compiled_tps``
with p50/p99 per-token decode latency and the grid-kernel count as
extras. At batch >= 64 the run itself asserts the compiled path beats
the baseline for the attention configs (starcoder2, gemma3) — the
paper-style claim this PR gates in CI.

The ``*_bf16_tps`` row compiles with dtype-aware sublane tiling
(``second_size=None``) so the grid blocks show the bf16 16-row packing in
their ``derived`` record; ``--small`` swaps it for a fp32 row at B=16
(8-row sublanes) so the smoke run still converts a grid kernel.

The ``*_faulted_tps`` row (ISSUE 8) reruns the compiled path under a
combined fault plan — one injected step exception, a forced page-pressure
window (>= 1 preemption + re-prefill), one NaN-logits step — and records
recovery overhead: the run asserts faulted throughput stays within 1.5x
of the fault-free run at the same batch (the ``fault_free_tps`` extra,
gated again by check_bench against the committed baseline).

The ``*_sharded_tps`` and ``*_shrink_recovery_tps`` rows (ISSUE 9) run
the scheduler with the decode step partitioned across a 2-host mesh
(``shard_map`` over the ShardMapPass-partitioned SDFG). Both record the
in-run unsharded throughput (``unsharded_tps`` extra) so check_bench can
bound the sharding overhead; the shrink row kills a host mid-decode
(``Scheduler.shrink``), records ``resharding_events``, and asserts the
streams stay byte-identical. Requires >= 2 jax devices — CI sets
``XLA_FLAGS=--xla_force_host_platform_device_count=2``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np

ARCHS = ("starcoder2-3b", "gemma3-4b", "rwkv6-7b")
PROMPT, NEW = 16, 24
PAGE = 16
#: compiled must beat baseline at these batches (attention configs only;
#: rwkv has no attention, so the paged-context win does not apply)
ASSERT_BATCHES = (64, 256)
ASSERT_ARCHS = ("starcoder2-3b", "gemma3-4b")


def _slug(arch: str) -> str:
    return arch.replace("-", "_").replace(".", "_")


def _baseline_tps(model, params, prompts, new_tokens: int,
                  max_model_len: int) -> float:
    import jax
    import jax.numpy as jnp
    B = prompts.shape[0]
    cache = model.init_cache(B, max_model_len)
    step = jax.jit(model.decode_step)
    logits, cache = step(params, cache, jnp.asarray(prompts, jnp.int32))
    toks = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    logits, cache = step(params, cache, toks)  # warm the decode shape
    toks = jnp.argmax(logits, -1).astype(jnp.int32)
    t0 = time.perf_counter()
    for _ in range(new_tokens):
        logits, cache = step(params, cache, toks)
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
    jax.block_until_ready(logits)
    return B * new_tokens / (time.perf_counter() - t0)


def _compiled_run(model, params, prompts, new_tokens: int,
                  max_model_len: int, **sched_kw):
    """Returns (tokens/sec, p50 ms, p99 ms, report) for one scheduler run."""
    from repro.serving import Scheduler
    B = prompts.shape[0]
    n_pages = B * ((PROMPT + new_tokens) // PAGE + 1) + 1
    sched = Scheduler(model, params, max_slots=B, page_size=PAGE,
                      n_pages=n_pages, max_model_len=max_model_len,
                      prefill_chunk=PROMPT, **sched_kw)
    for b in range(B):
        sched.submit(list(map(int, prompts[b])), new_tokens)
    reqs = sched.run()
    sched.check_invariants()
    # steady state: the gaps between a request's tokens, without the
    # first two (the compile-warmup steps)
    gaps = [np.diff(r.token_times) for r in reqs]
    steady: List[float] = [t for g in gaps for t in g[2:]]
    if not steady:
        steady = [t for g in gaps for t in g]
    med = float(np.median(steady))
    report = sched.compiler._steps[max(sched.compiler._steps)].report
    return (B / med, float(np.percentile(steady, 50) * 1e3),
            float(np.percentile(steady, 99) * 1e3), report)


def _grid_derived(report) -> str:
    conv = report.get("grid_converted") or []
    if not conv:
        return "grid_kernels=0"
    shape = conv[0].get("block_shape")
    return f"grid_kernels={len(conv)} blocks={shape}"


def run(report, small: bool = False):
    import jax
    from repro.configs import get_config
    from repro.models.transformer import TransformerLM

    new_tokens = 8 if small else NEW
    max_model_len = 128 if small else 512
    batches = (1, 8) if small else (1, 8, 64, 256)
    rng = np.random.RandomState(0)

    results = {}
    for arch in ARCHS:
        cfg = get_config(arch).reduced()
        model = TransformerLM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        prompts_all = rng.randint(0, cfg.vocab, size=(max(batches), PROMPT))
        for B in batches:
            prompts = prompts_all[:B]
            base = _baseline_tps(model, params, prompts, new_tokens,
                                 max_model_len)
            tps, p50, p99, rep = _compiled_run(
                model, params, prompts, new_tokens, max_model_len)
            nk = len(rep.get("grid_kernels", []))
            slug = _slug(arch)
            report(f"serve_{slug}_b{B}_baseline_tps", base,
                   derived=f"dense ctx={max_model_len}", backend="pallas")
            report(f"serve_{slug}_b{B}_compiled_tps", tps,
                   derived=_grid_derived(rep), backend="pallas",
                   p50_ms=p50, p99_ms=p99, grid_kernels=nk)
            results[(arch, B)] = (base, tps, nk)

    for arch in ASSERT_ARCHS:
        for B in ASSERT_BATCHES:
            if (arch, B) not in results:
                continue
            base, tps, nk = results[(arch, B)]
            assert tps > base, (
                f"{arch} b{B}: compiled {tps:.0f} tok/s does not beat "
                f"baseline {base:.0f} tok/s")
            assert nk >= 1, (
                f"{arch} b{B}: compiled step converted no grid kernels")

    # per-dtype sublane row: grid blocks sized by element width, not the
    # calibrated crossover table
    arch = "starcoder2-3b"
    cfg = get_config(arch).reduced()
    if small:  # fp32 -> 8-row sublanes: converts already at B=16
        cfg = dataclasses.replace(cfg, activation_dtype="float32")
        B, tag = 16, "f32"
    else:      # bf16 -> 16-row sublanes
        B, tag = 64, "bf16"
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = rng.randint(0, cfg.vocab, size=(B, PROMPT))
    tps, p50, p99, rep = _compiled_run(model, params, prompts, new_tokens,
                                       max_model_len,
                                       dtype_aware_sublanes=True)
    nk = len(rep.get("grid_kernels", []))
    assert nk >= 1, f"dtype-aware {tag} row converted no grid kernels"
    report(f"serve_{_slug(arch)}_b{B}_{tag}_tps", tps,
           derived=_grid_derived(rep), backend="pallas",
           p50_ms=p50, p99_ms=p99, grid_kernels=nk)

    _faulted_row(report, small, new_tokens, max_model_len)
    _sharded_rows(report, small, new_tokens)


def _sharded_rows(report, small: bool, new_tokens: int):
    """2-host sharded decode throughput + live-shrink recovery, each with
    the unsharded throughput of the same workload as in-run comparator."""
    import dataclasses as dc
    import jax
    from repro.configs import get_config
    from repro.models.transformer import TransformerLM
    from repro.serving import Scheduler

    if jax.device_count() < 2:
        print("serve: < 2 devices — skipping sharded rows (set XLA_FLAGS="
              "--xla_force_host_platform_device_count=2 before running)")
        return

    arch = "starcoder2-3b"
    B = 8 if small else 16
    mml = 64 if small else 128
    # sharded exactness is byte-level only without cross-batch reductions;
    # keep activations f32 so the comparator is exact, not approximate
    cfg = dc.replace(get_config(arch).reduced(),
                     activation_dtype="float32")
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    prompts = [list(map(int, rng.randint(0, cfg.vocab, size=PROMPT)))
               for _ in range(B)]
    ppr = (PROMPT + new_tokens) // PAGE + 1
    n_pages = B * ppr + 2  # one null page per shard

    def one(n_shards=1, shrink_at=None):
        sched = Scheduler(model, params, max_slots=B, page_size=PAGE,
                          n_pages=n_pages, max_model_len=mml,
                          prefill_chunk=PROMPT, cache_dtype="float32",
                          n_shards=n_shards)
        for p in prompts:
            sched.submit(p, new_tokens)
        t0 = time.perf_counter()
        if shrink_at is not None:
            for _ in range(shrink_at):
                sched.step()
            sched.shrink(1)
        reqs = sched.run()
        wall = time.perf_counter() - t0
        sched.check_invariants()
        total = sum(len(r.tokens_out) for r in reqs)
        return (total / wall, {r.rid: list(r.tokens_out) for r in reqs},
                sched)

    base_tps, base_streams, _ = one()
    tps, got, sched = one(n_shards=2)
    assert got == base_streams, "sharded streams diverged from unsharded"
    sm = sched.compiler._steps[max(sched.compiler._steps)].report.get(
        "shard_map") or {}
    report(f"serve_{_slug(arch)}_sharded_tps", tps, backend="pallas",
           derived=f"n_shards=2 sharded={sm.get('sharded')}",
           unsharded_tps=base_tps, batch=B, n_shards=2)

    tps, got, sched = one(n_shards=2, shrink_at=3)
    assert got == base_streams, "streams diverged after mesh shrink"
    evs = [e for e in sched.events if e["kind"] == "mesh_shrink"]
    pre = [e for e in sched.events if e["kind"] == "shrink_preempt"]
    assert evs, "shrink produced no mesh_shrink event"
    report(f"serve_{_slug(arch)}_shrink_recovery_tps", tps,
           backend="pallas",
           derived=f"2->1 hosts, {len(pre)} preempted",
           unsharded_tps=base_tps, batch=B,
           resharding_events=len(evs), preempted=len(pre))


def _faulted_row(report, small: bool, new_tokens: int, max_model_len: int):
    """Recovery overhead: the combined ISSUE-8 fault plan vs fault-free
    at the same batch, wall-clock tokens/sec over the whole run."""
    import jax
    from repro.configs import get_config
    from repro.models.transformer import TransformerLM
    from repro.serving import FaultInjector, Scheduler, ServeFaultPlan

    arch = "starcoder2-3b"
    B = 8 if small else 64
    cfg = get_config(arch).reduced()
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    # staggered one-page prompts: lanes cross their first page boundary
    # at different steps, so the pressure window hits a live crossing
    plens = rng.randint(8, PAGE + 1, size=B)
    prompts = [list(map(int, rng.randint(0, cfg.vocab, size=p)))
               for p in plens]
    n_pages = B * ((PAGE + new_tokens) // PAGE + 1) + 1

    def one(injector=None):
        sched = Scheduler(model, params, max_slots=B, page_size=PAGE,
                          n_pages=n_pages, max_model_len=max_model_len,
                          prefill_chunk=PAGE, injector=injector)
        if injector is not None:
            # compile time is a one-off; the row measures steady-state
            # recovery overhead, so warm the fallback rung off-clock
            for ctx in (2 * PAGE, 4 * PAGE):
                if ctx <= max_model_len:
                    sched.compiler.fallback_for(B, ctx)
        for p in prompts:
            sched.submit(p, new_tokens)
        t0 = time.perf_counter()
        reqs = sched.run()
        wall = time.perf_counter() - t0
        sched.check_invariants()
        total = sum(len(r.tokens_out) for r in reqs)
        return total / wall, sched

    clean_tps, _ = one()
    plan = ServeFaultPlan(step_exception_at=1, page_pressure_at=2,
                          page_pressure_release_at=6, nan_logits_at=4)
    tps, sched = one(FaultInjector(plan))
    st = sched.stats()
    assert st["preemptions"] >= 1, "pressure window caused no preemption"
    assert st["fallback_steps"] >= 1, "no fallback re-run happened"
    assert all(r.finish_reason == "max_tokens" for r in sched.finished)
    overhead = clean_tps / tps
    assert overhead <= 1.5, (
        f"faulted run {tps:.0f} tok/s is {overhead:.2f}x slower than "
        f"fault-free {clean_tps:.0f} tok/s (budget 1.5x)")
    report(f"serve_{_slug(arch)}_faulted_tps", tps, backend="pallas",
           derived=(f"preemptions={st['preemptions']} "
                    f"fallback_steps={st['fallback_steps']}"),
           fault_free_tps=clean_tps, batch=B,
           preemptions=st["preemptions"],
           fallback_steps=st["fallback_steps"])


if __name__ == "__main__":
    import sys

    from benchmarks import run
    raise SystemExit(run.main(["--only", "serve"] + sys.argv[1:]))
