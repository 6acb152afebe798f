"""Bring-up smoke run on a TPU.

Drives the system's main path once, compiled for the chip (no Pallas
interpreter anywhere), and checks what comes out against references:

* serving: ``Scheduler`` -> ``DecodeStepCompiler`` -> Pallas grid kernels
  at starcoder2-3b's published widths with random weights from ``--seed``,
  16 requests through continuous batching; the compiled step's logits are
  compared with ``jax.jit(model.decode_step)`` on the same tokens;
* the paper's programs through ``Lowered.compile("pallas")``: the
  streamed AXPYDOT kernel at n = 2**26 against a float64 numpy reference,
  and the 4-stage jacobi chain fused into ONE grid kernel against
  ``benchmarks.jacobi_chain._reference``.

Run from the checkout root on a machine with a TPU::

    python chip_smoke.py             # one chip: both phases
    python chip_smoke.py --chips 4   # only sharded serving over four chips,
                                     # compared with the unsharded run

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check exits non-zero before it. There is no CPU path: without
a TPU the script exits 1. Times it prints are smoke wall times, compiles
included, not benchmark results.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

ARCH = "starcoder2-3b"
MAX_SLOTS = 8
PAGE_SIZE = 16
#: the largest context bucket whose per-(b, h) attention blocks fit the
#: 16 MiB VMEM budget at published widths; at ctx 1024 they need 25 MB
#: and GridConversion would leave attention out of the grid kernels
MAX_MODEL_LEN = 512
#: every slot can hold MAX_MODEL_LEN tokens, plus one null page for each
#: of up to four shards
N_PAGES = MAX_SLOTS * MAX_MODEL_LEN // PAGE_SIZE + 4
N_REQUESTS = 16
NEW_TOKENS = 32
#: prompt lengths are whole prefill chunks, so prefill compiles once per
#: length and not once more for a ragged last chunk
PREFILL_CHUNK = 16
PROMPT_LENS = np.arange(64, 193, PREFILL_CHUNK)
EOS_ID = 0
#: Both sides run every layer in bfloat16 with float32 accumulation but
#: round at different points (the grid kernel reduces q.k in f32 on the
#: vector unit, the reference through XLA's dot). One bf16 rounding
#: (2**-8 relative) per residual update, uncorrelated over 30 layers x 2
#: updates, grows like sqrt(60) * 2**-8 ~ 0.03 of the residual scale,
#: which the final norm carries into the logits: allow 2**-4 of the
#: largest reference logit. An argmax may differ only where the
#: reference's top two logits lie within twice that bound.
LOGIT_RTOL = 2.0 ** -4
AXPYDOT_N = 1 << 26
#: 2**26 positive float32 terms in 1024 running partial sums: rounding
#: drifts ~1e-7 of the sum; a dropped or doubled 8192-element block moves
#: it by 1.2e-4. 1e-5 lies between the two.
AXPYDOT_RTOL = 1e-5


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


def require_tpu(chips: int):
    import jax
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX runs on {dev.platform!r}; this smoke run has no "
          f"other path")
    log(f"device: {dev.device_kind} x {len(devices)} "
        f"(platform {dev.platform})")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, found {len(devices)}")
    return dev, devices


def memory_line(dev) -> str:
    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    if peak is None:
        return "peak_bytes_in_use: not reported"
    return (f"peak_bytes_in_use {peak} of bytes_limit {limit} "
            f"({peak / 2**30:.2f} GiB)")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def serving_config():
    """starcoder2-3b at its published widths; weights cut to bfloat16."""
    from repro.configs import get_config
    return dataclasses.replace(get_config(ARCH), param_dtype="bfloat16")


def build(cfg, seed: int):
    import jax
    from repro.models import build_model
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return model, params


def traffic(seed: int, vocab: int):
    rng = np.random.default_rng(seed)
    lens = rng.choice(PROMPT_LENS, size=N_REQUESTS)
    return [rng.integers(1, vocab, size=int(n)).tolist() for n in lens]


def serve(model, params, prompts, n_shards: int = 1):
    """Run ``prompts`` to completion, one arrival per scheduler step, so
    slots fill and free one at a time and batch buckets 1 to MAX_SLOTS
    compile. Returns the scheduler, the logits of the first step with
    every slot busy, and that step's lanes as ``(slot, rid, prompt, fed
    tokens)``."""
    from repro.serving import Scheduler
    sched = Scheduler(model, params, max_slots=MAX_SLOTS,
                      page_size=PAGE_SIZE, n_pages=N_PAGES,
                      max_model_len=MAX_MODEL_LEN,
                      prefill_chunk=PREFILL_CHUNK, cache_dtype="bfloat16",
                      n_shards=n_shards)
    full, lanes = None, []
    t0 = time.perf_counter()
    for p in prompts:
        sched.submit(p, NEW_TOKENS, eos_id=EOS_ID)
        sched.step()
        if full is None and all(r is not None for r in sched.slots):
            full = np.asarray(sched.last_logits, np.float32)
            # the step fed each lane all but the token it just sampled
            lanes = [(r.slot, r.rid, r.prompt, r.tokens_out[:-1])
                     for r in sched.slots]
    t1 = time.perf_counter()
    sched.run()
    t2 = time.perf_counter()
    check(full is not None, "no step ran with every slot busy")
    n_tok = sum(len(r.tokens_out) for r in sched.finished)
    log(f"  smoke wall time, n_shards={n_shards}: arrivals "
        f"{t1 - t0:.3f} s, drain {t2 - t1:.3f} s; {n_tok} tokens in "
        f"{sched.n_decode_steps} decode steps, prefills and compiles "
        f"included")
    return sched, full, lanes


def check_serving(sched, n_attn_layers: int):
    """Everything ran compiled, through grid kernels, without a fault."""
    comp = sched.compiler
    check(comp.interpret is False,
          f"decode steps compiled with interpret={comp.interpret}")
    steps = comp.steps
    check(bool(steps), "no decode step was compiled")
    for (B, ctx), step in sorted(steps.items()):
        rep = step.report
        log(f"  bucket B={B} ctx={ctx}: rung {step.rung}, interpret "
            f"{rep['interpret']}, {len(rep['grid_kernels'])} grid kernels, "
            f"grid_fallbacks {rep['grid_fallbacks']}")
        check(step.rung == "grid" and rep["interpret"] is False,
              f"bucket {(B, ctx)} is not a compiled grid step")
        check(len(rep["grid_kernels"]) == n_attn_layers,
              f"bucket {(B, ctx)} has {len(rep['grid_kernels'])} grid "
              f"kernels, want {n_attn_layers}")
        check(not rep["grid_fallbacks"],
              f"bucket {(B, ctx)} grid fallbacks {rep['grid_fallbacks']}")
        if sched.n_shards > 1:
            check((rep.get("shard_map") or {}).get("sharded"),
                  f"bucket {(B, ctx)} did not shard")
    faults = [e for e in sched.watchdog.events
              if e["kind"] not in ("straggler", "dead")]
    timing = [e["kind"] for e in sched.watchdog.events
              if e["kind"] in ("straggler", "dead")]
    reasons = sorted({r.finish_reason for r in sched.finished})
    log(f"  compiler events {comp.events}; fallback steps "
        f"{sched.n_fallback_steps}; recomputes {sched.n_recomputes}; "
        f"preemptions {sched.n_preemptions}; watchdog faults {faults}; "
        f"watchdog timing flags {timing} (a bucket's first step includes "
        f"its compile); finish reasons {reasons}")
    check(not comp.events, f"compiler events {comp.events}")
    check(sched.n_fallback_steps == 0,
          f"{sched.n_fallback_steps} fallback steps")
    check(sched.n_recomputes == 0, f"{sched.n_recomputes} recomputes")
    # nan_logits faults would name any active lane with non-finite logits
    check(not faults, f"watchdog faults {faults}")
    check(len(sched.finished) == N_REQUESTS,
          f"{len(sched.finished)} of {N_REQUESTS} requests finished")
    check(set(reasons) <= {"eos", "max_tokens"},
          f"finish reasons {reasons}")
    sched.check_invariants()


def compare_logits(got, ref, vocab: int, what: str):
    got, ref = got[:vocab], ref[:vocab]
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          f"{what}: non-finite logits")
    scale = float(np.abs(ref).max())
    diff = float(np.abs(got - ref).max())
    bound = LOGIT_RTOL * scale
    top2 = np.sort(ref)[-2:]
    gap = float(top2[1] - top2[0])
    agree = int(got.argmax()) == int(ref.argmax())
    log(f"  {what}: max|dlogit| {diff:.6g}, bound {bound:.6g} "
        f"(2**-4 x max|ref| {scale:.6g}); argmax "
        f"{'agrees' if agree else 'differs'} (ref top-2 gap {gap:.6g})")
    check(diff <= bound, f"{what}: max|dlogit| {diff} > {bound}")
    check(agree or gap <= 2 * bound,
          f"{what}: argmax differs with ref top-2 gap {gap}")


def reference_logits(model, params, prompt, fed):
    """``jax.jit(model.decode_step)`` over a dense bf16 cache: prefill
    ``prompt``, then one step per token of ``fed``; the last logits."""
    import jax
    import jax.numpy as jnp
    step = jax.jit(model.decode_step)
    seq = jnp.asarray(prompt, jnp.int32)[None]
    cache = model.init_cache(1, len(prompt) + len(fed), dtype=jnp.bfloat16)
    for i in range(0, len(prompt), PREFILL_CHUNK):
        logits, cache = step(params, cache, seq[:, i:i + PREFILL_CHUNK])
    for t in fed:
        logits, cache = step(params, cache, jnp.asarray([[t]], jnp.int32))
    return np.asarray(logits[0, -1], np.float32)


def announce(cfg):
    log(f"model: {cfg.name} at published widths: {cfg.n_layers} layers, "
        f"d_model {cfg.d_model}, {cfg.n_heads} heads / {cfg.n_kv_heads} KV "
        f"heads, head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}, {cfg.n_params() / 1e9:.2f}e9 parameters")
    log("cut: param_dtype float32 -> bfloat16 (float32 weights alone are "
        "12.7 GB of the chip's 16 GiB); random weights from --seed")
    log(f"traffic: {N_REQUESTS} requests arriving one per step, prompts "
        f"of {PROMPT_LENS[0]}-{PROMPT_LENS[-1]} tokens, {NEW_TOKENS} new "
        f"tokens each, greedy, max_slots {MAX_SLOTS}, page_size "
        f"{PAGE_SIZE}, max_model_len {MAX_MODEL_LEN}, bf16 KV cache")


def serving_phase(dev, seed: int):
    cfg = serving_config()
    announce(cfg)
    t0 = time.perf_counter()
    model, params = build(cfg, seed)
    log(f"  smoke wall time: random weights {time.perf_counter() - t0:.3f} s")
    prompts = traffic(seed, cfg.vocab)
    sched, full, lanes = serve(model, params, prompts)
    check_serving(sched, cfg.n_layers)
    log(f"  {memory_line(dev)}")
    slot, rid, prompt, fed = lanes[0]
    ref = reference_logits(model, params, prompt, fed)
    compare_logits(full[slot], ref, cfg.vocab,
                   f"first full step (B={MAX_SLOTS}) vs jax.jit(decode_step)"
                   f", request {rid} ({len(prompt)}-token prompt + "
                   f"{len(fed)} generated)")


def sharded_phase(devices, seed: int, chips: int):
    """The same model and traffic sharded over ``chips`` devices,
    compared with the unsharded run."""
    cfg = serving_config()
    announce(cfg)
    model, params = build(cfg, seed)
    prompts = traffic(seed, cfg.vocab)
    ref_sched, ref_full, ref_lanes = serve(model, params, prompts)
    check_serving(ref_sched, cfg.n_layers)
    ref_streams = {r.rid: list(r.tokens_out) for r in ref_sched.finished}
    # drop the comparator's flat weight copy before the sharded one
    del ref_sched
    gc.collect()

    sched, full, lanes = serve(model, params, prompts, n_shards=chips)
    check_serving(sched, cfg.n_layers)
    for d in devices[:chips]:
        log(f"  device {d.id}: {memory_line(d)}")
    want = set(devices[:chips])
    for li in sorted(sched.pool.k_pages):
        for kind, a in (("k", sched.pool.k_pages[li]),
                        ("v", sched.pool.v_pages[li])):
            shards = a.addressable_shards
            on = {s.device for s in shards}
            check(on == want and len(shards) == chips
                  and all(s.data.shape[0] == N_PAGES // chips
                          for s in shards),
                  f"{kind} pages of layer {li} sit on {sorted(map(str, on))}"
                  f" in shards {[s.data.shape for s in shards]}")
    log(f"  page arrays: {2 * len(sched.pool.k_pages)} arrays, each in "
        f"{chips} shards of {N_PAGES // chips} pages on devices "
        f"{sorted(d.id for d in want)}")
    check([lane[:2] for lane in lanes] == [lane[:2] for lane in ref_lanes],
          "the first full step placed requests in other slots than "
          "unsharded")
    for slot, rid, _, _ in ref_lanes:
        compare_logits(full[slot], ref_full[slot], cfg.vocab,
                       f"sharded vs unsharded first full step, slot "
                       f"{slot} (request {rid})")
    streams = {r.rid: list(r.tokens_out) for r in sched.finished}
    same = sum(streams[rid] == ref_streams[rid] for rid in ref_streams)
    log(f"  greedy streams identical to unsharded: {same} of "
        f"{len(ref_streams)}")
    check(streams.keys() == ref_streams.keys(),
          "sharded and unsharded runs finished different requests")
    for rid in sorted(ref_streams):
        if streams[rid] != ref_streams[rid]:
            check_parting(model, params, cfg.vocab, rid, prompts[rid],
                          ref_streams[rid], streams[rid])


def check_parting(model, params, vocab: int, rid: int, prompt, want, got):
    """Two greedy streams of one request may part only at a tie: where
    the reference logits that pick the first differing token hold both
    choices within twice the bf16 bound of their maximum (the rule of
    ``compare_logits``). Batch buckets differ between the two runs (the
    unsharded one steps at B=1, 2, 4, 8, each shard at its own width), so
    each rounds its matmuls its own way, and over 512 greedy picks one
    near-tie can flip."""
    k = next((i for i, (a, b) in enumerate(zip(want, got)) if a != b),
             None)
    check(k is not None,
          f"request {rid}: one stream is a strict prefix of the other")
    ref = reference_logits(model, params, prompt, want[:k])[:vocab]
    check(np.isfinite(ref).all(), f"request {rid}: non-finite logits")
    bound = LOGIT_RTOL * float(np.abs(ref).max())
    a, b = want[k], got[k]
    short = float(ref.max() - min(ref[a], ref[b]))
    log(f"  request {rid}: streams part at new token {k} ({a} unsharded, "
        f"{b} sharded); jax.jit(decode_step) logits {ref[a]:.6g} and "
        f"{ref[b]:.6g}, max {ref.max():.6g}: the lower choice is "
        f"{short:.6g} below the max, tie bound {2 * bound:.6g}")
    check(short <= 2 * bound,
          f"request {rid}: streams part at token {k} where the reference "
          f"holds no tie ({short} > {2 * bound})")


# ---------------------------------------------------------------------------
# the paper's programs through the staged compiler
# ---------------------------------------------------------------------------
def axpydot_phase():
    import repro.kernels  # noqa: F401  (registers the fused Axpy+Dot)
    from benchmarks.axpydot import build as build_axpydot
    from repro.pipeline import (DeviceOffloadPass, StreamingCompositionPass,
                                lower)
    n = AXPYDOT_N
    rng = np.random.default_rng(0)
    a = np.float32(0.7)
    x, y, w = (rng.random(n, dtype=np.float32) for _ in range(3))
    t0 = time.perf_counter()
    c = lower(build_axpydot(n)).optimize(
        [DeviceOffloadPass(), StreamingCompositionPass()]).compile("pallas")
    got = float(np.asarray(c(a=a, x=x, y=y, w=w)["result"]).ravel()[0])
    dt = time.perf_counter() - t0
    ref = float(np.dot(np.float64(a) * x + y, w.astype(np.float64)))
    rel = abs(got - ref) / abs(ref)
    log(f"axpydot streamed, n={n}: fused regions "
        f"{c.report['fused_regions']}, interpret {c.report['interpret']}; "
        f"result {got!r} vs float64 {ref!r}, rel err {rel:.3g} "
        f"(bound {AXPYDOT_RTOL}); smoke wall time {dt:.3f} s")
    check(c.report["interpret"] is False, "axpydot compiled to interpret")
    check(bool(c.report["fused_regions"]),
          "axpydot: no fused region, a jnp lowering stood in")
    check(rel <= AXPYDOT_RTOL, f"axpydot rel err {rel} > {AXPYDOT_RTOL}")


def jacobi_phase():
    from benchmarks.jacobi_chain import N, STAGES, _chain_sdfg, _reference
    from repro.pipeline import lower
    a = np.random.default_rng(7).standard_normal(N).astype(np.float32)
    t0 = time.perf_counter()
    c = lower(_chain_sdfg(N)).compile("pallas")
    got = np.asarray(c(a=a)["b"])
    dt = time.perf_counter() - t0
    ref = _reference(a)
    err = float(np.abs(got - ref).max())
    log(f"jacobi chain, n={N}, {STAGES} stages: grid kernels "
        f"{c.report['grid_kernels']}, interpret {c.report['interpret']}; "
        f"max|err| {err:.3g}; smoke wall time {dt:.3f} s")
    check(c.report["interpret"] is False, "jacobi compiled to interpret")
    check(len(c.report["grid_kernels"]) == 1,
          f"jacobi chain is not ONE grid kernel: {c.report['grid_kernels']}")
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the traffic")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only sharded serving over four chips")
    args = ap.parse_args(argv)
    try:
        dev, devices = require_tpu(args.chips)
        sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
        from repro.codegen.device import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        if args.chips > 1:
            sharded_phase(devices, args.seed, args.chips)
        else:
            serving_phase(dev, args.seed)
            axpydot_phase()
            jacobi_phase()
    except SmokeFailure as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
