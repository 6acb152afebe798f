"""Tiled GEMM Pallas kernel — the MXU realization of the paper's Fig.-6
systolic array (DESIGN.md §2).

The paper instantiates P processing elements, each buffering part of A and
streaming B through a FIFO chain. On TPU, the 128x128 MXU *is* the systolic
array; the kernel's job is the paper's 'memory reader PE' role: tile
(bm, bk, bn) blocks through VMEM with the K grid dimension innermost so the
fp32 VMEM scratch accumulator carries partial C tiles across K steps
(= the PE-chain accumulation), and the Pallas pipeline double-buffers the
HBM->VMEM streams (= the FIFOs). An optional fused epilogue (bias +
activation) plays the role of a downstream streaming-composed PE.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...codegen.device import resolve_interpret

MXU = 128


def _act(name, x):
    if name is None:
        return x
    if name == "relu":
        return jnp.maximum(x, 0.0)
    if name == "silu":
        return x / (1.0 + jnp.exp(-x))
    if name == "gelu":
        return 0.5 * x * (1.0 + jnp.tanh(
            0.7978845608028654 * (x + 0.044715 * x ** 3)))
    raise ValueError(name)


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, activation, k_steps):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        o_ref[...] = _act(activation, acc_ref[...]).astype(o_ref.dtype)


def _matmul_bias_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *,
                        activation, k_steps):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        out = acc_ref[...] + bias_ref[...].astype(jnp.float32)
        o_ref[...] = _act(activation, out).astype(o_ref.dtype)


def _pad_to(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=(
    "bm", "bk", "bn", "activation", "interpret", "out_dtype"))
def matmul(a, b, bias=None, *, bm: int = 2 * MXU, bk: int = 4 * MXU,
           bn: int = 2 * MXU, activation: str = None,
           interpret: Optional[bool] = None, out_dtype=None):
    """C = act(A @ B + bias), A:(M,K) B:(K,N), fp32 accumulation."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm_, bk_, bn_ = min(bm, M), min(bk, K), min(bn, N)
    # clamp to hw-aligned sizes when the problem allows it
    a_p = _pad_to(a, bm_, bk_)
    b_p = _pad_to(b, bk_, bn_)
    Mp, Kp = a_p.shape
    _, Np = b_p.shape
    k_steps = Kp // bk_
    grid = (Mp // bm_, Np // bn_, k_steps)
    out_dtype = out_dtype or a.dtype

    if bias is not None:
        bias_p = jnp.pad(bias, (0, Np - bias.shape[0])).reshape(1, Np)
        out = pl.pallas_call(
            functools.partial(_matmul_bias_kernel, activation=activation,
                              k_steps=k_steps),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
                pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
            interpret=resolve_interpret(interpret),
        )(a_p, b_p, bias_p)
    else:
        out = pl.pallas_call(
            functools.partial(_matmul_kernel, activation=activation,
                              k_steps=k_steps),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
            interpret=resolve_interpret(interpret),
        )(a_p, b_p)
    if (Mp, Np) != (M, N):
        out = out[:M, :N]
    return out
