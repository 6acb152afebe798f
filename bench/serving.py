"""Serving cells: a model behind the program's ``Scheduler``, driven by
an open loop of requests from one host thread.

Set-up makes the weights on the device from the seed, builds the
scheduler, and warms every shape the cell's traffic can reach: one
prefill per prompt length, and one compiled decode step per reachable
(batch, context) bucket. Then the window opens. The harness submits each
request when it is due, steps the scheduler, and stamps on the host
clock, when ``Scheduler.step()`` returns, every token a request gained.
A request's first token carries the scheduler's own stamp, taken when
its prefill was sampled.

After the window, the chip's memory peak is read, the scheduler is
dropped, and a sample of the finished requests, the longest among them,
goes through the configuration's plain reference: the mean, over the
sample's served tokens, of the gap by which a served token's reference
logit lies below the reference's best at its position decides
``correct``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import json
import time
from typing import List, Optional

import numpy as np

from bench import costs, harness, traffic
from bench.peaks import peaks

#: how long past the window a request due in it may wait for its first
#: token before it counts as failed
DRAIN_S = 60.0
CLOCK = time.perf_counter


# ---------------------------------------------------------------------------
# configuration -> the program's model, weights made from the seed
# ---------------------------------------------------------------------------
def load_config(name: str) -> dict:
    return json.loads((harness.BENCH / "configs" / f"{name}.json").read_text())


def model_file(cfg: dict):
    """The configuration's model file: ``model_config(cfg)`` and
    ``init_params(cfg, key)``."""
    return harness.config_module(cfg, "model")


def build_model(cfg: dict):
    from repro.models import build_model as build
    return build(model_file(cfg).model_config(cfg))


def n_pages(cfg: dict) -> int:
    """Every slot can hold ``max_model_len`` tokens, plus the null page."""
    sv = cfg["serving"]
    return sv["max_slots"] * sv["max_model_len"] // sv["page_size"] + 1


def seed_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


def make_params(cfg: dict, seed: int):
    import jax
    init = functools.partial(model_file(cfg).init_params, cfg)
    params = jax.jit(init)(seed_key(seed))
    return jax.block_until_ready(params)


def make_scheduler(cfg: dict, model, params):
    from repro.serving import Scheduler
    sv = cfg["serving"]
    return Scheduler(model, params, max_slots=sv["max_slots"],
                     page_size=sv["page_size"], n_pages=n_pages(cfg),
                     max_model_len=sv["max_model_len"],
                     prefill_chunk=sv["prefill_chunk"],
                     cache_dtype=sv["cache_dtype"])


# ---------------------------------------------------------------------------
# warm-up: every shape the traffic can reach, and no other
# ---------------------------------------------------------------------------
def _pow2_at_least(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def reachable_buckets(cfg: dict, reqs) -> List[tuple]:
    """(batch, context) buckets the scheduler can step at serving
    ``reqs``. Context: the bucket of the shortest live sequence up to
    ``max_model_len``. Batch: an open loop fills slots from the first
    free one and can reach any power of two up to ``max_slots``."""
    sv = cfg["serving"]
    ps, mml, slots = sv["page_size"], sv["max_model_len"], sv["max_slots"]
    lo = min(len(r.prompt) for r in reqs) + 1
    ctxs, pages = [], _pow2_at_least(-(-lo // ps))
    while True:
        ctxs.append(min(pages * ps, mml))
        if pages * ps >= mml:
            break
        pages *= 2
    batches = sorted({min(_pow2_at_least(k), slots)
                      for k in range(1, slots + 1)})
    return [(b, c) for b in batches for c in ctxs]


def warm(sched, cfg: dict, reqs) -> dict:
    """Run one prefill per distinct prompt length and one decode step per
    reachable bucket, so that nothing compiles in the window."""
    from repro.serving.compile import attention_layer_shapes
    lens = sorted({len(r.prompt) for r in reqs})
    for n in lens:
        sched.submit([1 + i % 97 for i in range(n)], 1)
        sched.step()
    if sched.queue or any(s is not None for s in sched.slots):
        raise RuntimeError("a warm-up prefill did not finish in its step")
    buckets = reachable_buckets(cfg, reqs)
    layers = attention_layer_shapes(sched.model)
    for B, ctx in buckets:
        out = sched.compiler.step_for(B, ctx)(sched._step_kwargs(B, ctx))
        np.asarray(out["logits"])
        for li in layers:
            sched.pool.k_pages[li] = out[f"kp{li}"]
            sched.pool.v_pages[li] = out[f"vp{li}"]
    return {"prompt_lengths": lens, "buckets": buckets}


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Rec:
    spec: traffic.Request
    due: float = 0.0                 # absolute, host clock
    submitted: Optional[float] = None
    req: object = None               # the scheduler's Request
    stamps: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ServingRun:
    """What the per-layer readers read."""
    trace: object
    peaks: dict
    dims: costs.DecoderDims
    kv_bytes: int
    #: per decode step in the traced window: each decoded lane's context
    decode_steps: List[List[int]]
    #: prompt lengths of the prefills whose first token came in it
    prefill_lens: List[int]
    compiles_in_window: int
    #: the program's recorder (``repro.tracing``), ``None`` when off
    spans: object = None
    #: the window on the host clock (``time.perf_counter`` seconds)
    window_start: float = 0.0
    window_end: float = 0.0


def serve(sched, recs: List[Rec], seconds: float, window, *,
          drain_first_tokens: bool = True):
    """Drive the scheduler: submit each request when due, step, stamp.
    ``window`` is entered when the window opens and left when it closes;
    after it, with ``drain_first_tokens``, the loop runs on until every
    request due in the window has its first token (at most ``DRAIN_S``).
    Returns ``(t0, t_close, steps)``; ``steps`` holds, per step, the
    stamp and the records that gained tokens."""
    n, i = len(recs), 0
    live: List[Rec] = []
    steps = []
    stack = contextlib.ExitStack()
    stack.enter_context(window)
    t0 = CLOCK()
    end = t0 + seconds
    for r in recs:
        r.due = t0 + r.spec.due_s
    t_close = None
    waiting_first = 0                 # due in the window, no token yet
    while True:
        now = CLOCK()
        if t_close is None and now >= end:
            t_close = CLOCK()
            stack.close()
        if t_close is not None:
            if not drain_first_tokens or (
                    waiting_first == 0 and (i == n or recs[i].due >= end)) \
                    or now > end + DRAIN_S:
                break
        while i < n and recs[i].due <= now:
            r = recs[i]
            with harness.annotate("submit"):
                sched.submit(r.spec.prompt, r.spec.max_new_tokens)
            r.req = sched.queue[-1]
            r.submitted = CLOCK()
            if r.due < end:
                waiting_first += 1
            live.append(r)
            i += 1
        if sched.queue or any(s is not None for s in sched.slots):
            with harness.annotate("step"):
                sched.step()
            t = CLOCK()
            gained, still = [], []
            for r in live:
                k = len(r.req.tokens_out)
                if k > len(r.stamps):
                    if not r.stamps:
                        r.stamps.append(r.req.first_token_time)
                        if r.due < end:
                            waiting_first -= 1
                    r.stamps.extend([t] * (k - len(r.stamps)))
                    gained.append(r)
                if not r.req.done:
                    still.append(r)
            live = still
            steps.append((t, gained))
        else:
            nxt = recs[i].due if i < n else end
            if t_close is None:
                nxt = min(nxt, end)
            with harness.annotate("wait"):
                time.sleep(max(0.0, nxt - CLOCK()))
    return t0, t_close, steps


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def latencies(recs: List[Rec], t0: float, seconds: float):
    """TTFT of every request due in the window (infinite where none
    came) and every gap between tokens whose later token came in it, in
    milliseconds."""
    end = t0 + seconds
    due_in = [r for r in recs if r.submitted is not None and r.due < end]
    ttft = [((r.stamps[0] - r.due) if r.stamps else np.inf) * 1e3
            for r in due_in]
    itl = [(b - a) * 1e3 for r in recs
           for a, b in zip(r.stamps, r.stamps[1:]) if t0 <= b <= end]
    return ttft, itl


def end_to_end(recs: List[Rec], t0: float, seconds: float, lat) -> dict:
    """The cell's end-to-end metrics; ``lat`` is what :func:`latencies`
    returns for the same run."""
    end = t0 + seconds
    tokens = sum(1 for r in recs for s in r.stamps if t0 <= s <= end)
    ttft, itl = lat
    out = {"output_tps": tokens / seconds}
    if ttft:
        # p80: the highest percentile with ten requests beyond it among
        # the fifty or sixty a chat window holds (p90 leaves 5 or 6)
        out["ttft_p80_ms"] = percentile(ttft, 80)
    if itl:
        # the median is the decode step as users feel it. p99 falls among
        # the gaps that hold another request's admission (0.4-0.5 s of
        # host time): they are 3.4-4.3% of all gaps at the chat cell's
        # load, so p95 lies just below them and would leap at a share
        # past 5%, where p99 stays among them while their share is
        # between 1% and the share of gaps that hold two admissions
        out["itl_p50_ms"] = percentile(itl, 50)
        out["itl_p99_ms"] = percentile(itl, 99)
    return out


#: a gap between tokens longer than this holds more than a decode step:
#: an admission, a stall of the host
STALL_MS = 100.0


def latency_detail(lat) -> dict:
    """Quantiles and means of both latencies (what :func:`latencies`
    returns), and the share of gaps longer than ``STALL_MS``, for the
    log."""
    ttft, itl = lat
    out = {}
    for name, v, qs in (("ttft", ttft, (50, 75, 90)),
                        ("itl", itl, (50, 90, 95, 99))):
        if v:
            out.update({f"{name}_p{q}_ms": round(percentile(v, q), 3)
                        for q in qs})
            out[f"{name}_mean_ms"] = round(float(np.mean(v)), 3)
            out[f"{name}_n"] = len(v)
    if itl:
        out["itl_stall_share"] = round(
            float(np.mean(np.asarray(itl) > STALL_MS)), 5)
    return out


def reader_counters(recs: List[Rec], steps, t0: float, t_close: float):
    """Decode contexts per step and prefills, inside ``[t0, t_close]``."""
    decode_steps = []
    for t, gained in steps:
        if not (t0 <= t <= t_close):
            continue
        # token k >= 1 was produced feeding token k-1 at position
        # L + k - 1: it attended over L + k keys (a first token carries
        # the scheduler's own stamp, never the step's)
        ctxs = [len(r.spec.prompt) + k for r in gained
                for k, s in enumerate(r.stamps) if k and s == t]
        if ctxs:
            decode_steps.append(ctxs)
    prefill_lens = [len(r.spec.prompt) for r in recs
                    if r.stamps and t0 <= r.stamps[0] <= t_close]
    return decode_steps, prefill_lens


# ---------------------------------------------------------------------------
# the comparison with the reference
# ---------------------------------------------------------------------------
def sample(recs: List[Rec], seed: int, want_tokens: int, max_requests: int):
    """Finished requests drawn from the seed, the longest first, until
    they hold ``want_tokens`` served tokens."""
    done = [r for r in recs if r.req is not None and r.req.done
            and r.req.finish_reason in ("max_tokens", "eos")]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.req.tokens_out))
    rest = [r for r in done if r is not longest]
    order = traffic._rng(seed, 7).permutation(len(rest))
    out, tok = [longest], len(longest.req.tokens_out)
    for j in order:
        if tok >= want_tokens or len(out) >= max_requests:
            break
        out.append(rest[j])
        tok += len(rest[j].req.tokens_out)
    return out


def served_gaps(cfg: dict, ref_mod, params, picked: List[Rec], *,
                low=None, read_with=None):
    """For every served token of ``picked``, the gap between the
    reference's best logit at its position and the reference's logit of
    the served token: 0 where the token is the reference's choice.

    With ``low`` the reference runs at that precision and the token it
    puts first at each position stands for the served one (the
    control); its gaps are read in ``read_with``, the full-precision
    logits. Returns the gaps and the logits read."""
    seqs, rows, served = [], [], []
    for r in picked:
        p, toks = r.spec.prompt, list(r.req.tokens_out)
        seqs.append(p + toks[:-1])
        rows.append(list(range(len(p) - 1, len(p) + len(toks) - 1)))
        served.append(toks)
    mml = cfg["serving"]["max_model_len"]
    lg = ref_mod.logits(cfg, params, seqs, rows, pad_to=mml, low=low)
    if low is not None:
        served = [x.argmax(axis=1) for x in lg]
        lg = read_with
    gaps = [x.max(axis=1) - x[np.arange(len(t)), np.asarray(t)]
            for x, t in zip(lg, served)]
    return np.concatenate(gaps).astype(np.float64), lg


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def window_ctx(counter, trace_on: bool, name: str, holder: list):
    """The measured window: compiles counted, and traced when asked."""
    with counter.armed_for(), harness.traced(trace_on, name) as h:
        holder.append(h)
        yield


@dataclasses.dataclass
class Session:
    """One serving run's state between set-up, window and check."""
    cfg: dict
    mix: dict
    params: object
    sched: object
    recs: List[Rec]
    t0: float = 0.0
    t_close: float = 0.0
    steps: list = dataclasses.field(default_factory=list)
    holder: list = dataclasses.field(default_factory=list)


def setup(cell, seed: int, seconds: float, log) -> Session:
    cfg, mix = cell.config, cell.traffic
    sv = cfg["serving"]
    t = CLOCK()
    model = build_model(cfg)
    params = make_params(cfg, seed)
    sched = make_scheduler(cfg, model, params)
    specs = traffic.generate(mix, seed, seconds, cfg["vocab_size"],
                             sv["max_model_len"])
    t_warm = CLOCK()
    warmed = warm(sched, cfg, specs)
    log(f"warmed {len(warmed['prompt_lengths'])} prompt lengths "
        f"{warmed['prompt_lengths']} and {len(warmed['buckets'])} decode "
        f"buckets {warmed['buckets']}; model, weights and traffic "
        f"{t_warm - t:.3f} s, warm-up {CLOCK() - t_warm:.3f} s")
    return Session(cfg, mix, params, sched, [Rec(s) for s in specs])


def measure(sess: Session, name: str, seconds: float, trace_on: bool,
            counter):
    sess.t0, sess.t_close, sess.steps = serve(
        sess.sched, sess.recs, seconds,
        window_ctx(counter, trace_on, name, sess.holder))


def report(sess: Session, seconds: float, counter, log):
    """Log what the run did: lateness, scheduler and compiler counters."""
    sched, recs = sess.sched, sess.recs
    end = sess.t0 + seconds
    due_in = [r for r in recs if r.submitted is not None and r.due < end]
    late = [r.submitted - r.due for r in due_in]
    log(f"generator lateness over {len(late)} requests due in the window: "
        f"median {percentile(late, 50) * 1e3:.3f} ms, p99 "
        f"{percentile(late, 99) * 1e3:.3f} ms, max {max(late) * 1e3:.3f} ms"
        if late else "generator lateness: no request was due")
    st = sched.stats()
    log("scheduler: " + json.dumps(
        {k: st[k] for k in ("n_steps", "n_decode_steps", "finished",
                            "queued", "active", "finish_reasons",
                            "preemptions", "fallback_steps", "recomputes")}))
    log(f"compiler: events {st['compiler_events']}; buckets "
        + ", ".join(f"{b}: {s.rung}/{len(s.report['grid_kernels'])} grid "
                    f"kernels" for b, s in sorted(sched.compiler.steps.items())))
    log(f"compiles in the window: {counter.count}")
    failed = sum(1 for r in due_in if not r.stamps or (
        r.req.done and r.req.finish_reason not in ("max_tokens", "eos")))
    return due_in, failed


def describe_gaps(gaps) -> str:
    return (f"{int((gaps > 0).sum())} of {len(gaps)} not the reference's "
            f"choice, mean gap {mean_gap(gaps)!r}, widest "
            f"{float(gaps.max()) if len(gaps) else 0.0!r}")


def mean_gap(gaps) -> float:
    return float(gaps.mean()) if len(gaps) else float("inf")


def check(sess: Session, seed: int, log, control_dtype=None) -> dict:
    """Drop the program's state, run the reference over a sample of the
    finished requests and compare the mean, over the sample's served
    tokens, of the gap by which a served token's reference logit lies
    below the reference's best at its position. With ``control_dtype``
    the control's reading is added (``control_mean_logit_gap``)."""
    cfg, chk = sess.cfg, sess.cfg["check"]
    picked = sample(sess.recs, seed, chk["sample_tokens"], chk["max_requests"])
    sess.sched = None
    gc.collect()
    ref = harness.reference(cfg)
    t_ref = CLOCK()
    gaps, lg = served_gaps(cfg, ref, sess.params, picked)
    log(f"reference: {len(picked)} requests, {CLOCK() - t_ref:.3f} s; "
        + describe_gaps(gaps))
    limit = chk["max_mean_logit_gap"]
    checks = {"mean_logit_gap": {"value": mean_gap(gaps), "limit": limit},
              "checked_tokens": {"value": len(gaps),
                                 "limit": chk["min_tokens"]}}
    if control_dtype is not None:
        cgaps, _ = served_gaps(cfg, ref, sess.params, picked,
                               low=control_dtype, read_with=lg)
        log("control: " + describe_gaps(cgaps))
        checks["control_mean_logit_gap"] = {"value": mean_gap(cgaps),
                                            "limit": limit}
    return checks


def as_control(checks: dict) -> dict:
    """``checks`` with the control's gap in the program's place: what
    :func:`passed` would judge had the control served the tokens."""
    return {**{k: v for k, v in checks.items()
               if k != "control_mean_logit_gap"},
            "mean_logit_gap": checks["control_mean_logit_gap"]}


def passed(checks: dict) -> bool:
    """Within the limits; a configuration whose limit is not yet set
    from readings (``null``) never passes."""
    gap = checks["mean_logit_gap"]
    return (gap["limit"] is not None and gap["value"] <= gap["limit"]
            and checks["checked_tokens"]["value"]
            >= checks["checked_tokens"]["limit"])


def run(cell, seed: int, seconds: float, trace_on: bool, devices,
        counter, log) -> harness.Outcome:
    import jax.numpy as jnp
    sess = setup(cell, seed, seconds, log)
    measure(sess, cell.name, seconds, trace_on, counter)
    due_in, failed = report(sess, seconds, counter, log)
    run_rec = None
    if trace_on:
        from repro import tracing
        dsteps, plens = reader_counters(sess.recs, sess.steps, sess.t0,
                                        sess.t_close)
        run_rec = ServingRun(
            trace=sess.holder[0].trace, peaks=peaks(devices[0].device_kind),
            dims=costs.DecoderDims.from_config(sess.cfg),
            kv_bytes=jnp.dtype(sess.cfg["serving"]["cache_dtype"]).itemsize,
            decode_steps=dsteps, prefill_lens=plens,
            compiles_in_window=counter.count, spans=tracing.recorder(),
            window_start=sess.t0, window_end=sess.t_close)
    mem = harness.memory_peak(devices)
    lat = latencies(sess.recs, sess.t0, seconds)
    e2e = end_to_end(sess.recs, sess.t0, seconds, lat)
    ttft, itl = lat
    if "ttft_p80_ms" in e2e:
        log(f"ttft_p80_ms {e2e['ttft_p80_ms']!r} over {len(ttft)} requests, "
            f"{sum(v > e2e['ttft_p80_ms'] for v in ttft)} beyond it; "
            f"itl_p50_ms {e2e.get('itl_p50_ms')!r}, itl_p99_ms "
            f"{e2e.get('itl_p99_ms')!r} over {len(itl)} gaps")
    log("latencies: " + json.dumps(latency_detail(lat)))
    checks = check(sess, seed, log)
    return harness.Outcome(correct=passed(checks), attempted=len(due_in),
                           failed=failed, end_to_end=e2e, checks=checks,
                           memory_peak_bytes=mem, run=run_rec,
                           window_start=sess.t0)
