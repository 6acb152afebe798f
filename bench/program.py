"""Program cells: one of the paper's programs, compiled by the staged
compiler and called back to back on inputs that live on the device.

Set-up builds the program's SDFG from the configuration's program file,
runs the configuration's passes, compiles for its backend, makes the
inputs on the device from the seed and calls the program twice. The
window then calls it until ``--seconds`` have passed, each call
dispatched up to the mix's ``ahead_s`` seconds of calls ahead of the one
whose scalar result is read back, so that the chip stays fed while the
host stands still. When the time is up nothing more is sent, every call
sent is read back, and the clock is read after that: the window's calls
over all of its time. After the window every result is compared with
the configuration's reference, computed on the host in float64 from the
same inputs.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time
from typing import List

import numpy as np

from bench import costs, harness
from bench.peaks import peaks

CLOCK = time.perf_counter


@dataclasses.dataclass
class ProgramRun:
    """What the per-layer readers read."""
    trace: object
    peaks: dict
    n: int
    itemsize: int
    calls: int
    #: the program's recorder (``repro.tracing``), ``None`` when off
    spans: object = None
    #: the window on the host clock (``time.perf_counter`` seconds)
    window_start: float = 0.0
    window_end: float = 0.0


def make_inputs(cfg: dict, seed: int):
    """x, y and w uniform in [0, 1) and a in [0.5, 1.5), on the device."""
    import functools

    import jax
    import jax.numpy as jnp
    from bench.serving import seed_key
    dt = jnp.dtype(cfg["dtype"])

    @functools.partial(jax.jit, static_argnums=0)
    def gen(n, key):
        ka, kx, ky, kw = jax.random.split(key, 4)
        return {"a": 0.5 + jax.random.uniform(ka, (), dt),
                "x": jax.random.uniform(kx, (n,), dt),
                "y": jax.random.uniform(ky, (n,), dt),
                "w": jax.random.uniform(kw, (n,), dt)}

    return jax.block_until_ready(gen(int(cfg["n"]), seed_key(seed)))


def compile_program(cfg: dict):
    import repro.kernels  # noqa: F401  (registers the fused kernels)
    import repro.pipeline as pipeline
    build = harness.config_module(cfg, "program").build
    passes = [getattr(pipeline, p)() for p in cfg["passes"]]
    compiled = pipeline.lower(build(int(cfg["n"]))).optimize(passes).compile(
        cfg["backend"])
    require_compiled(compiled.report, cfg)
    return compiled


def require_compiled(report: dict, cfg: dict):
    """The program runs as the chip runs it: no interpreter, and the
    fusion the configuration names."""
    if report.get("interpret"):
        raise RuntimeError("the program compiled for the Pallas interpreter")
    if report["fused_regions"] != cfg["expect_fused"]:
        raise RuntimeError(f"fused regions {report['fused_regions']}"
                           f", want {cfg['expect_fused']}")


def dispatch(compiled, inputs):
    """Send one call; its result stays on the device until read."""
    return compiled(**inputs)["result"]


def read(result) -> float:
    return float(np.asarray(result).ravel()[0])


def call(compiled, inputs) -> float:
    return read(dispatch(compiled, inputs))


def run(cell, seed: int, seconds: float, trace_on: bool, devices,
        counter, log) -> harness.Outcome:
    cfg = cell.config
    compiled = compile_program(cfg)
    inputs = make_inputs(cfg, seed)
    call(compiled, inputs)
    t_call = CLOCK()
    call(compiled, inputs)
    t_call = CLOCK() - t_call
    ahead = max(1, int(cell.traffic["ahead_s"] / t_call))
    log(f"compiled: fused regions {compiled.report['fused_regions']}, "
        f"grid kernels {compiled.report['grid_kernels']}, interpret "
        f"{compiled.report['interpret']}; one call {t_call * 1e3:.3f} ms, "
        f"{ahead} calls dispatched ahead")
    results: List[float] = []
    pending: collections.deque = collections.deque()
    holder: list = []
    with counter.armed_for(), \
            harness.traced(trace_on, cell.name) as h:
        holder.append(h)
        t0 = CLOCK()
        end = t0 + seconds
        while CLOCK() < end:
            with harness.annotate("call"):
                pending.append(dispatch(compiled, inputs))
            if len(pending) > ahead:
                with harness.annotate("read"):
                    results.append(read(pending.popleft()))
        with harness.annotate("drain"):
            results.extend(read(r) for r in pending)
        t = CLOCK()
    window = t - t0
    log(f"{len(results)} calls in {window:.6f} s ({window - seconds:.6f} s "
        f"of them reading back the calls in flight at {seconds} s); "
        f"compiles in the window: {counter.count}")
    run_rec = None
    if trace_on:
        from repro import tracing
        run_rec = ProgramRun(trace=holder[0].trace,
                             peaks=peaks(devices[0].device_kind),
                             n=int(cfg["n"]),
                             itemsize=np.dtype(cfg["dtype"]).itemsize,
                             calls=len(results), spans=tracing.recorder(),
                             window_start=t0, window_end=t)
    mem = harness.memory_peak(devices)
    host = {k: np.asarray(v) for k, v in inputs.items()}
    del inputs
    gc.collect()
    ref = harness.reference(cfg).axpydot(host["a"], host["x"], host["y"],
                                         host["w"])
    got = np.asarray(results, np.float64)
    rel = float(np.max(np.abs(got - ref)) / abs(ref))
    log(f"result {results[0]!r} vs float64 {ref!r}; {len(set(results))} "
        f"distinct results over the calls")
    limit = cfg["check"]["max_rel_err"]
    checks = {"rel_err": {"value": rel, "limit": limit}}
    return harness.Outcome(
        correct=rel <= limit, attempted=len(results), failed=0,
        end_to_end={"program_ms": window / len(results) * 1e3},
        checks=checks, memory_peak_bytes=mem, run=run_rec, window_start=t0)


def roofline_share(run: ProgramRun, select) -> float:
    """The least time the calls in the window could take, over the
    device time of the kernel ops ``select`` accepts, in percent."""
    kernel_s = run.trace.op_seconds(select) if run.trace else 0.0
    if kernel_s <= 0:
        return None
    flops, nbytes = costs.axpydot_cost(run.n, run.itemsize)
    least = run.calls * costs.roofline_seconds(
        flops, nbytes, run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
