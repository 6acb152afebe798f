"""Chunked WKV6 Pallas kernel.

TPU adaptation of RWKV6's data-dependent-decay linear recurrence
(DESIGN.md §4): the GLA-style chunkwise form turns the per-token recurrence
into MXU matmuls. The grid walks (batch*heads) x sequence-chunks; the
(hd, hd) fp32 state lives in VMEM scratch and carries across chunk steps —
a literal shift register of the recurrence state, with the intra-chunk
causal matmul playing the paper's 'unrolled circuit' role.

Chunk length 16 bounds exp(cumsum log w) within fp32 (|log w| <= 3.5).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...codegen.device import resolve_interpret

CHUNK = 16


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_final_ref,
                state_ref, *, n_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)       # (C, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)       # (hd,)
    C = r.shape[0]

    lw = jnp.log(jnp.maximum(w, 1e-8))
    la = jnp.cumsum(lw, axis=0)            # inclusive per-key log decay
    a_prev = jnp.exp(la - lw)              # A_{t-1}
    a_last = jnp.exp(la[-1])               # (hd,)
    r_t = r * a_prev
    k_t = k * jnp.exp(-la)
    k_rev = k * jnp.exp(la[-1:] - la)

    # intra-chunk: strictly-causal scores + diagonal bonus
    scores = jnp.dot(r_t, k_t.T, preferred_element_type=jnp.float32)
    t_pos = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j_pos = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    scores = jnp.where(j_pos < t_pos, scores, 0.0)
    out = jnp.dot(scores, v, preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u[None, :] * k, axis=1)
    out = out + diag[:, None] * v

    # inter-chunk: apply carried state, then update it
    out = out + jnp.dot(r_t, state_ref[...],
                        preferred_element_type=jnp.float32)
    state_ref[...] = a_last[:, None] * state_ref[...] + jnp.dot(
        k_rev.T, v, preferred_element_type=jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        s_final_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv_chunked(r, k, v, w, u, interpret: Optional[bool] = None):
    """r,k,v,w: (B,S,H,hd); u: (H,hd) -> (out (B,S,H,hd), state (B,H,hd,hd)).
    Zero initial state (prefill); S must be a multiple of CHUNK."""
    B, S, H, hd = r.shape
    assert S % CHUNK == 0, (S, CHUNK)
    n_chunks = S // CHUNK

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    rf, kf, vf, wf = map(fold, (r, k, v, w))
    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, hd)

    out, state = pl.pallas_call(
        functools.partial(_wkv_kernel, n_chunks=n_chunks),
        grid=(B * H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, CHUNK, hd), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, CHUNK, hd), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, CHUNK, hd), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, CHUNK, hd), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, hd), lambda h, c: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, CHUNK, hd), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, hd, hd), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hd), r.dtype),
            jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rf, kf, vf, wf, uf)
    out = out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    state = state.reshape(B, H, hd, hd)
    return out, state
