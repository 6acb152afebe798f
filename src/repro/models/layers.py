"""Composable model layers (pure-functional JAX, sharding-friendly).

Every perf-critical op routes through a dual-path selector — ``xla`` (jnp
composite, GSPMD-shardable: used by the multi-pod dry-run) or ``pallas``
(explicit-VMEM kernel, validated in interpret mode on CPU, the TPU
production path) — the LM-framework incarnation of the paper's multi-level
Library-Node expansion (DESIGN.md §3.4).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def psc(x, *roles):
    """Activation sharding constraint by role, against the ambient mesh.

    roles per dim: 'batch' (shard over pod+data axes), 'model', 'seq_model'
    (sequence over model — long-context decode), or None. Filters to axes
    present in the ambient mesh and checks divisibility, so model code is
    mesh-agnostic. Only ``Auto`` axes are constrained, so it is a no-op
    without a mesh context (CPU smoke tests) and inside ``shard_map``,
    where every axis is ``Manual``.
    """
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty:
        return x
    auto = jax.sharding.AxisType.Auto
    sizes = {a: n for (a, n), t in zip(am.shape.items(), am.axis_types)
             if t == auto}
    if not sizes:
        return x
    spec = []
    for dim, role in zip(x.shape, roles):
        if role == "batch":
            axes, prod = [], 1
            for a in ("pod", "data"):
                if a in sizes and dim % (prod * sizes[a]) == 0:
                    axes.append(a)
                    prod *= sizes[a]
            spec.append(tuple(axes) if len(axes) > 1 else
                        (axes[0] if axes else None))
        elif role in ("model", "seq_model"):
            spec.append("model" if "model" in sizes
                        and dim % sizes["model"] == 0 else None)
        else:
            spec.append(None)
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*spec))


def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale + bias).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_freqs(d_head: int, theta: float = 10000.0):
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32)
                            / d_head))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)                       # (Dh/2,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)             # (..., S, 1, Dh/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, causal, optional sliding window), dual-path
# ---------------------------------------------------------------------------
def _gqa_repeat(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def attention_xla(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                  q_offset=0):
    """q: (B, Sq, Hq, Dh); k/v: (B, Sk, Hkv, Dh). GSPMD-shardable einsum
    formulation; supports decode (Sq=1 with KV cache) via q_offset.

    Sharding: heads over 'model' when divisible; for decode with few KV
    heads the *sequence* dim of K/V shards over 'model' instead
    (sequence-parallel attention — GSPMD inserts the partial-softmax
    combine, the chip-level version of the paper's §3.3.1 partial sums)."""
    b, sq, hq, dh = q.shape
    _, sk, hkv, _ = k.shape
    n_rep = hq // hkv
    decode = sq == 1
    # decode with few KV heads: keep K/V sequence-sharded (matches the
    # cache sharding rule) so no per-step cache reshard is needed
    seq_sharded = decode and hkv % _model_size() != 0
    k = _gqa_repeat(k, n_rep)
    v = _gqa_repeat(v, n_rep)
    if seq_sharded:
        k = psc(k, "batch", "seq_model", None, None)
        v = psc(v, "batch", "seq_model", None, None)
    else:
        q = psc(q, "batch", None, "model", None)
        k = psc(k, "batch", None, "model", None)
        v = psc(v, "batch", None, "model", None)
    scale = 1.0 / np.sqrt(dh)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if seq_sharded:
        logits = psc(logits, "batch", None, None, "seq_model")
    else:
        logits = psc(logits, "batch", "model", None, None)
    q_pos = q_offset + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    out = psc(out, "batch", None, "model", None)
    return out.astype(q.dtype)


def _model_size() -> int:
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty:
        return 1
    return dict(am.shape).get("model", 1)


def attention_chunked(q, k, v, *, causal: bool = True,
                      window: Optional[int] = None, q_offset=0,
                      bk: int = 1024):
    """Online-softmax chunked attention (beyond-paper optimization,
    EXPERIMENTS §Perf): the (Sq, Sk) score matrix never materializes —
    KV streams through in bk-chunks with running (max, sum, acc) carried
    across a scan, the XLA-level realization of the flash/streaming-
    composition insight. When the head count does not divide the model
    axis (yi-34b: 56 heads on 16), queries shard over *sequence* instead
    (sequence parallelism) so compute still spreads across all chips."""
    from . import _flags
    b, sq, hq, dh = q.shape
    _, sk, hkv, _ = k.shape
    n_rep = hq // hkv
    k = _gqa_repeat(k, n_rep)
    v = _gqa_repeat(v, n_rep)
    heads_shard = hq % _model_size() == 0
    if heads_shard:
        q = psc(q, "batch", None, "model", None)
        k = psc(k, "batch", None, "model", None)
        v = psc(v, "batch", None, "model", None)
    else:
        q = psc(q, "batch", "seq_model", None, None)  # SP over queries
    scale = 1.0 / np.sqrt(dh)
    bk = min(bk, sk)
    while sk % bk:
        bk -= 1
    n_chunks = sk // bk
    q32 = q.astype(jnp.float32) * scale
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, ci):
        m, l, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k, ci * bk, bk, axis=1
                                          ).astype(jnp.float32)
        vs = jax.lax.dynamic_slice_in_dim(v, ci * bk, bk, axis=1
                                          ).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, ks)
        k_pos = ci * bk + jnp.arange(bk)
        mask = jnp.ones((sq, bk), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vs)
        return (m_new, l, acc), None

    m0 = jnp.full((b, hq, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    a0 = jnp.zeros((b, hq, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), jnp.arange(n_chunks),
        unroll=n_chunks if _flags.UNROLL_SCANS else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 2, 1, 3)  # (b, sq, hq, dh)
    if heads_shard:
        out = psc(out, "batch", None, "model", None)
    else:
        out = psc(out, "batch", "seq_model", None, None)
    return out.astype(q.dtype)


def attention(q, k, v, *, causal=True, window=None, q_offset=0,
              impl: str = "xla", interpret: Optional[bool] = None):
    if impl == "xla" or q.shape[1] == 1:
        return attention_xla(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)
    if impl == "pallas":
        from ..kernels.attention import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               interpret=interpret)
    raise ValueError(impl)


# ---------------------------------------------------------------------------
# Dense MLPs
# ---------------------------------------------------------------------------
def swiglu(x, w_gate, w_up, w_down):
    g = psc(jnp.einsum("bsd,df->bsf", x, w_gate), "batch", None, "model")
    u = psc(jnp.einsum("bsd,df->bsf", x, w_up), "batch", None, "model")
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("bsf,fd->bsd", h, w_down)


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    h = psc(jnp.einsum("bsd,df->bsf", x, w_in) + b_in, "batch", None, "model")
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", h, w_out) + b_out


# ---------------------------------------------------------------------------
# Mixture of Experts: GShard-style capacity-based dispatch (static shapes,
# EP-shardable over the 'model' axis). Top-k routing with optional shared
# expert.
# ---------------------------------------------------------------------------
def moe_block(x, router_w, w_gate, w_up, w_down, *, top_k: int,
              capacity_factor: float = 1.25,
              shared: Optional[dict] = None, dispatch: str = "onehot",
              drop_tokens: bool = True):
    """x: (B, S, D); router_w: (D, E); expert weights stacked (E, D, F) /
    (E, F, D). Returns (out, aux_loss).

    dispatch='onehot' is the paper-era GShard formulation (one-hot
    einsums: O(T^2) dispatch FLOPs — the dry-run exposes this);
    dispatch='sort' is the beyond-paper scatter/gather dispatch
    (EXPERIMENTS §Perf): O(T*k*D) data movement, no dispatch matmuls.

    drop_tokens=False is eval mode: capacity = n_tokens, so no (token,
    expert) pair can overflow its buffer (top-k experts are distinct, so
    an expert receives at most n_tokens assignments). Dropping depends on
    whole-batch whole-sequence token counts, which token-by-token decode
    cannot see — disabling it makes decode match forward bit-for-bit.
    Cost caveat: capacity grows from ~top_k*cf/E * n_tokens to n_tokens,
    an E/(top_k*cf) constant inflation of the (E, C, D) expert buffers
    (and of the already-O(T*C) one-hot dispatch tensors) — for long-
    sequence eval at scale prefer dispatch='sort' or pass
    drop_tokens=True explicitly and accept train-style dropping."""
    b, s, d = x.shape
    e = router_w.shape[1]
    n_tokens = b * s
    xt = x.reshape(n_tokens, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, experts_idx = jax.lax.top_k(probs, top_k)       # (T, k)
    gate_vals = gate_vals / jnp.clip(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    if drop_tokens:
        capacity = int(np.ceil(top_k * n_tokens * capacity_factor / e))
        capacity = max(capacity, 4)
    else:
        capacity = n_tokens

    # position of each (token, k) pair within its expert's buffer
    onehot = jax.nn.one_hot(experts_idx, e, dtype=jnp.int32)   # (T, k, E)
    flat = onehot.reshape(n_tokens * top_k, e)
    pos_in_expert = jnp.cumsum(flat, axis=0) * flat - 1        # (T*k, E)
    pos = jnp.max(pos_in_expert, axis=-1).reshape(n_tokens, top_k)
    keep = pos < capacity
    gate_vals = gate_vals * keep

    if dispatch == "sort":
        out = _moe_apply_scatter(xt, experts_idx, pos, keep, gate_vals,
                                 w_gate, w_up, w_down, e, capacity, d)
        if shared is not None:
            out = out + swiglu(xt[None], shared["w_gate"], shared["w_up"],
                               shared["w_down"])[0]
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(experts_idx[:, 0], e,
                                     dtype=jnp.float32), axis=0)
        aux = e * jnp.sum(me * ce)
        return out.reshape(b, s, d), aux

    # dispatch: (T, k, E, C) combine tensor (bool) - classic GShard einsums
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity + 1,
                            dtype=xt.dtype)[..., :capacity]    # (T,k,C)
    dispatch = jnp.einsum("tke,tkc->etc", onehot.astype(xt.dtype), pos_oh)
    dispatch = psc(dispatch, "model", "batch", None)
    expert_in = psc(jnp.einsum("etc,td->ecd", dispatch, xt),
                    "model", None, None)                       # (E, C, D)

    # expert FFNs (EP: the leading expert dim shards over 'model')
    g = psc(jnp.einsum("ecd,edf->ecf", expert_in, w_gate), "model", None, None)
    u = psc(jnp.einsum("ecd,edf->ecf", expert_in, w_up), "model", None, None)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    expert_out = psc(jnp.einsum("ecf,efd->ecd", h, w_down),
                     "model", None, None)                      # (E, C, D)

    combine = jnp.einsum("tke,tkc,tk->etc", onehot.astype(xt.dtype), pos_oh,
                         gate_vals.astype(xt.dtype))
    out = jnp.einsum("etc,ecd->td", combine, expert_out)

    if shared is not None:
        out = out + swiglu(xt[None], shared["w_gate"], shared["w_up"],
                           shared["w_down"])[0]

    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(experts_idx[:, 0], e, dtype=jnp.float32),
                  axis=0)
    aux = e * jnp.sum(me * ce)
    return out.reshape(b, s, d), aux


def _moe_apply_scatter(xt, experts_idx, pos, keep, gate_vals,
                       w_gate, w_up, w_down, e, capacity, d):
    """Scatter/gather MoE dispatch: tokens scatter into (E*C, D) expert
    buffers by (expert, slot) index; outputs gather back. Slots are unique
    by construction (pos is a per-expert running count), so scatter-set is
    exact. Data movement O(T*k*D); no quadratic one-hot matmuls."""
    n_tokens, top_k = experts_idx.shape
    slot = experts_idx * capacity + pos                  # (T, k)
    slot = jnp.where(keep, slot, e * capacity)           # dropped -> sink row
    flat_slot = slot.reshape(-1)
    src = jnp.broadcast_to(xt[:, None, :], (n_tokens, top_k, d)
                           ).reshape(n_tokens * top_k, d)
    buf = jnp.zeros((e * capacity + 1, d), xt.dtype)
    buf = buf.at[flat_slot].set(src)
    expert_in = psc(buf[:-1].reshape(e, capacity, d), "model", None, None)

    g = psc(jnp.einsum("ecd,edf->ecf", expert_in, w_gate), "model", None,
            None)
    u = psc(jnp.einsum("ecd,edf->ecf", expert_in, w_up), "model", None, None)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
    expert_out = psc(jnp.einsum("ecf,efd->ecd", h, w_down),
                     "model", None, None)

    flat_out = jnp.concatenate(
        [expert_out.reshape(e * capacity, d),
         jnp.zeros((1, d), xt.dtype)], axis=0)
    gathered = flat_out[flat_slot].reshape(n_tokens, top_k, d)
    out = jnp.sum(gathered * gate_vals[..., None].astype(xt.dtype), axis=1)
    return out


# ---------------------------------------------------------------------------
# Parameter init helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
