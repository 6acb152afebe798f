"""Flash attention (fwd) Pallas kernel: causal, sliding-window, GQA.

This is the paper's StreamingComposition insight applied to attention
(DESIGN.md §4): QK^T -> softmax -> PV fused into one kernel so the (Sq,Sk)
score matrix never reaches HBM. Online-softmax running (max, sum) registers
play the role of the paper's §3.3.1 accumulation specialization; the KV
sequence streams block-by-block through VMEM like the FPGA reader PEs.

Grid: (batch*heads, Sq/bq, Sk/bk) with the KV dimension innermost; the
fp32 VMEM scratch carries (acc, m, l) across KV steps. Causal/window
blocks that are fully masked are skipped via jnp.where on block indices
(structural zero-work; on TPU Mosaic hoists the branch).
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, bq, bk, k_steps):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)          # (bq, dh)
    k = k_ref[0].astype(jnp.float32)          # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = None,
                    bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B, Sq, Hq, Dh); k/v: (B, Sk, Hkv, Dh) -> (B, Sq, Hq, Dh)."""
    b, sq, hq, dh = q.shape
    _, sk, hkv, _ = k.shape
    rep = hq // hkv
    scale = 1.0 / np.sqrt(dh)
    bq = min(bq, sq)
    bk = min(bk, sk)
    while sq % bq:
        bq -= 1
    while sk % bk:
        bk -= 1
    # layout: fold heads into the grid's leading dim; GQA indexes the
    # shared KV head via integer division in the index_map
    qh = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, dh)
    kh = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, dh)
    vh = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, dh)
    k_steps = sk // bk
    grid = (b * hq, sq // bq, k_steps)

    def kv_index(h, qi, ki):
        return (h // rep, ki, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, bk, dh), kv_index),
            pl.BlockSpec((1, bk, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qh, kh, vh)
    return out.reshape(b, hq, sq, dh).transpose(0, 2, 1, 3)
