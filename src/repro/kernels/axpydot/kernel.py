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
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...codegen.device import resolve_interpret

SUBLANES, LANES = 8, 128
TILE = SUBLANES * LANES  # 1024-element accumulation tile


def _axpydot_kernel(a_ref, x_ref, y_ref, w_ref, o_ref, acc_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0]
    # AXPY stage (z never leaves VMEM) -> DOT stage
    z = a * x_ref[...].astype(jnp.float32) + y_ref[...].astype(jnp.float32)
    prod = z * w_ref[...].astype(jnp.float32)
    # partial-sum interleaving across an (8,128) accumulator tile
    acc_ref[...] += jnp.sum(prod.reshape(-1, SUBLANES, LANES), axis=0)

    @pl.when(step == pl.num_programs(0) - 1)
    def _reduce():
        o_ref[...] = jnp.sum(acc_ref[...])[None]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def axpydot(a, x, y, w, block_n: int = 8 * TILE,
            interpret: Optional[bool] = None):
    n = x.shape[0]
    block_n = min(block_n, n)
    if block_n % TILE != 0 or n % block_n != 0:
        # pad to tile multiple; zeros are exact under +
        import numpy as np
        padded = int(np.ceil(n / TILE) * TILE)
        block_n = min(block_n - block_n % TILE or TILE, padded)
        while padded % block_n != 0:
            block_n -= TILE
        pad = padded - n
        x = jnp.pad(x, (0, pad))
        y = jnp.pad(y, (0, pad))
        w = jnp.pad(w, (0, pad))
        n = padded
    grid = (n // block_n,)
    a_arr = jnp.asarray(a, jnp.float32).reshape(1)
    return pl.pallas_call(
        _axpydot_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((1,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a_arr, x, y, w)
