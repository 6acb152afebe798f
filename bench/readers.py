"""Reductions the per-layer metric files share.

Each ``bench/metrics/<metric>.py`` is a small reader that calls one of
these with the names it needs. A reader gets the run's record (a
``ServingRun`` or a ``ProgramRun``) and returns a number, or ``None``
where the run holds nothing to read: then the harness leaves the metric
out of the line. A share of a roofline or of a peak is never 0 for want
of data; it is ``None``.
"""
from __future__ import annotations

import re
from typing import Optional

from bench import costs

#: the compiled serving decode step: ``CompiledDecodeStep`` jits a
#: function named ``positional``
DECODE_STEP_MODULE = re.compile(r"^jit_positional\b")
#: a Pallas kernel in the HLO text of an operation: inside the decode
#: step the only ones are its attention grid kernels, one per layer
#: (``Compiled.report["grid_kernels"]``; the harness logs the count).
#: The step's other custom calls are XLA's own, of other targets.
PALLAS_KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')


def module_name(span) -> str:
    return span.module or ""


def is_decode_step(span) -> bool:
    return bool(DECODE_STEP_MODULE.match(module_name(span)))


def is_attention_kernel(span) -> bool:
    return is_decode_step(span) and bool(PALLAS_KERNEL.search(span.name))


def idle_share(run) -> Optional[float]:
    """Percent of the traced window in which no operation ran."""
    t = run.trace
    if t is None or not t.devices() or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)


def prefill_device_share(run) -> Optional[float]:
    """Percent of the device's busy time spent outside the compiled
    decode step: the batch-1 prefill programs and the page scatters,
    the only other device work a serving run has."""
    t = run.trace
    if t is None or not run.prefill_lens:
        return None
    busy = t.busy_s()
    if busy <= 0:
        return None
    return 100.0 * t.busy_s(lambda s: not is_decode_step(s)) / busy


def decode_step_ms(run) -> Optional[float]:
    """Mean device time of one execution of the compiled decode step."""
    t = run.trace
    if t is None:
        return None
    runs = t.module_runs(lambda s: bool(DECODE_STEP_MODULE.match(s.name)))
    if not runs:
        return None
    return 1e3 * sum(s.dur for s in runs) * 1e-9 / len(runs)


def step_mfu(run) -> Optional[float]:
    """Model FLOPs of the tokens the traced window produced (prefill
    tokens at their causal context, decoded tokens at their real
    context; no padding, no recompute) over the window times the chip's
    bf16 peak, in percent."""
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    flops = sum(costs.prefill_flops(run.dims, n) for n in run.prefill_lens)
    flops += sum(costs.token_flops(run.dims, c)
                 for step in run.decode_steps for c in step)
    if flops == 0:
        return None
    return 100.0 * flops / (t.window_s * run.peaks["bf16_flops"])


def attention_roofline(run) -> Optional[float]:
    """The least time the decode steps' attention needs (live lanes' real
    K/V at ``n_kv_heads``, q and output; the larger of FLOP and byte
    time), over the attention kernels' device time, in percent."""
    t = run.trace
    if t is None or not run.decode_steps:
        return None
    kernel_s = t.op_seconds(is_attention_kernel)
    if kernel_s <= 0:
        return None
    least = 0.0
    for ctxs in run.decode_steps:
        f, b = costs.decode_attention_cost(run.dims, ctxs, run.kv_bytes)
        least += costs.roofline_seconds(f, b, run.peaks["bf16_flops"],
                                        run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
