"""Decode-attention Pallas kernel over a gathered paged-KV context.

Serving decodes one token per sequence per step: q is (B, H, Dh) and the
context K/V — gathered from the paged KV pool through the block table —
is (B, C, H, Dh) where C is the *context bucket* (a small multiple of the
page size), not the model's max sequence length. The kernel fuses
score -> mask -> softmax -> PV per (batch, head) grid cell so the (C,)
score vector never leaves VMEM; per-sequence lengths arrive as a
scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``) and mask the
context tail, so one compiled kernel serves every occupancy of the
bucket.

This is the hand-written "flash" expansion level of the
``PagedAttnDecode`` library node; the "pallas" level generates the
equivalent grid kernel from the SDFG (memlets -> BlockSpecs) and is the
serving default.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...codegen.device import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, scale, window,
                   ctx):
    b = pl.program_id(0)
    pos = pos_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)            # (Dh,)
    k = k_ref[0, :, 0].astype(jnp.float32)         # (C, Dh)
    v = v_ref[0, :, 0].astype(jnp.float32)
    s = jnp.sum(k * q[None, :], axis=-1) * scale   # (C,)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (ctx, 1), 0)[:, 0]
    mask = k_pos <= pos
    if window is not None:
        mask &= k_pos > pos - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_ref[0, 0] = (p @ v).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def decode_attention(q, k, v, pos, *, window: int = None,
                     interpret: Optional[bool] = None):
    """q: (B, H, Dh); k/v: (B, C, H, Dh) gathered context; pos: (B,) int32
    absolute position of the current token -> (B, H, Dh).

    Causal over absolute context positions: key j attends iff
    ``j <= pos[b]`` (and ``j > pos[b] - window`` for sliding-window
    layers). Entries past ``pos`` — unwritten pages, the null page of
    evicted slots — are masked structurally, so pool garbage never
    reaches the softmax.
    """
    b, h, dh = q.shape
    _, c, _, _ = k.shape
    scale = 1.0 / np.sqrt(dh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, dh), lambda i, j, pos: (i, j, 0)),
            pl.BlockSpec((1, c, 1, dh), lambda i, j, pos: (i, 0, j, 0)),
            pl.BlockSpec((1, c, 1, dh), lambda i, j, pos: (i, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, dh), lambda i, j, pos: (i, j, 0)),
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, window=window,
                          ctx=c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(pos.astype(jnp.int32), q, k, v)
