"""Fused AXPYDOT Pallas kernel — the paper's streaming-composition pipeline
realized as a single TPU kernel.

On FPGA, StreamingComposition turns  z = a*x+y ; r = z.w  into five PEs
chained by FIFOs so z never touches off-chip memory. On TPU, the same
fusion is one Pallas kernel: the grid streams (x, y, w) block-by-block from
HBM into VMEM (the Pallas pipeline double-buffers = the reader PEs), the
AXPY stage feeds the DOT stage through VMEM values (= the z FIFO), and the
accumulator uses **partial-sum interleaving** (paper §3.3.1, the Xilinx
specialization): an (8, 128) fp32 VREG-shaped tile of partial sums breaks
the loop-carried add dependency; a final reduction collapses it.

The vectors are viewed lane-dense as (n / 128, 128), which is a bitcast
on the chip, and streamed in (R, 128) blocks of about 1 MiB each: few
grid steps, each a long DMA, so the stream runs near HBM bandwidth.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...codegen.device import resolve_interpret

SUBLANES, LANES = 8, 128
BLOCK_BYTES = 1 << 20  # per input block
VMEM_LIMIT = 16 << 20  # v5e's default scoped VMEM; 3 inputs x 2 buffers fit


def sublanes(itemsize: int) -> int:
    """Rows of one (sublane, lane) tile of the dtype: 8 for 32 bits, 16
    for 16 bits."""
    return SUBLANES * max(1, 4 // itemsize)


def block_rows(rows: int, itemsize: int) -> int:
    """Rows of one streamed (R, 128) block: ``BLOCK_BYTES`` per input,
    which is a multiple of the dtype's sublane tiling, or all of ``rows``
    where they fit in one block."""
    return min(rows, BLOCK_BYTES // (LANES * itemsize))


def _axpydot_kernel(a_ref, x_ref, y_ref, w_ref, o_ref, acc_ref, *,
                    tail: int):
    step = pl.program_id(0)
    last = pl.num_programs(0) - 1

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(valid_rows=None):
        # AXPY stage (z never leaves VMEM) -> DOT stage
        z = (a_ref[0] * x_ref[...].astype(jnp.float32)
             + y_ref[...].astype(jnp.float32))
        prod = z * w_ref[...].astype(jnp.float32)
        if valid_rows is not None:
            # the block runs past the array's end, where its buffer holds
            # whatever it held
            row = lax.broadcasted_iota(jnp.int32, prod.shape, 0)
            prod = jnp.where(row < valid_rows, prod, 0.0)
        # partial-sum interleaving across an (8,128) accumulator tile
        acc_ref[...] += jnp.sum(prod.reshape(-1, SUBLANES, LANES), axis=0)

    if tail:
        pl.when(step < last)(accumulate)
        pl.when(step == last)(lambda: accumulate(tail))
    else:
        accumulate()

    @pl.when(step == last)
    def _reduce():
        o_ref[...] = jnp.sum(acc_ref[...])[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def axpydot(a, x, y, w, interpret: Optional[bool] = None):
    n = x.shape[0]
    itemsize = min(v.dtype.itemsize for v in (x, y, w))
    sub = sublanes(itemsize)
    pad = -n % (sub * LANES)
    if pad:
        # zeros are exact under +; the paper's sizes never pad
        x, y, w = (jnp.pad(v, (0, pad)) for v in (x, y, w))
    rows = (n + pad) // LANES
    x, y, w = (v.reshape(rows, LANES) for v in (x, y, w))
    block = block_rows(rows, itemsize)
    kernel = functools.partial(_axpydot_kernel, tail=rows % block)
    stream = pl.BlockSpec((block, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, block),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  stream, stream, stream],
        out_specs=pl.BlockSpec((1,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(a, jnp.float32).reshape(1), x, y, w)
