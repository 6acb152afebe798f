"""DOT Pallas kernel with partial-sum interleaving (paper §3.3.1).

The streaming phase accumulates into an (8,128) fp32 tile (the TPU reshaping
of the paper's 'buffer larger than the add latency'); the reduce phase
collapses the tile. Used by the Dot Library Node's ``pallas`` expansion.
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
TILE = SUBLANES * LANES


def _dot_kernel(x_ref, w_ref, o_ref, acc_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    prod = x_ref[...].astype(jnp.float32) * w_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.sum(prod.reshape(-1, SUBLANES, LANES), axis=0)

    @pl.when(step == pl.num_programs(0) - 1)
    def _reduce():
        o_ref[...] = jnp.sum(acc_ref[...])[None]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def dot(x, w, block_n: int = 8 * TILE, interpret: Optional[bool] = None):
    n = x.shape[0]
    block_n = min(block_n, max(n, TILE))
    if block_n % TILE != 0 or n % block_n != 0:
        import numpy as np
        padded = int(np.ceil(n / TILE) * TILE)
        block_n = min(block_n - block_n % TILE or TILE, padded)
        while padded % block_n != 0:
            block_n -= TILE
        pad = padded - n
        x = jnp.pad(x, (0, pad))
        w = jnp.pad(w, (0, pad))
        n = padded
    return pl.pallas_call(
        _dot_kernel,
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((block_n,), lambda i: (i,)),
                  pl.BlockSpec((block_n,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w)
