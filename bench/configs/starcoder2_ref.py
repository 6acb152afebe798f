"""Plain float32 reference of the starcoder2 architecture.

The forward pass as arXiv:2402.19173 and the published ``Starcoder2``
model describe it: token embedding; per layer a pre-LayerNorm block of
grouped-query self-attention with rotary position embedding (rotate-half
form, ``theta ** (-2i / head_dim)``), causal over the sequence, and a
pre-LayerNorm GELU (tanh form) MLP with biases, each added to the
residual stream; a final LayerNorm and the output head. Every product
runs in float32 at ``Precision.HIGHEST``. No kernel, cache or batching
beyond a padded block of whole sequences.

It reads the parameter tree the benchmark hands the program, in the
layout the program takes: ``embed`` (vocab, d), ``body[0]`` with the
layers stacked on axis 0, ``final_scale``/``final_bias`` and ``lm_head``
(d, vocab). A LayerNorm's weight is stored as its offset from one.
Departures from the published model, all shared with the program: no
bias on the q, k, v and o projections, and an output head of its own
in place of the embedding's transpose (see the configuration's
``program_gaps``).

``low`` gives the control, the program's precision with every product
one step lower: both operands of every product (weights, embedding,
activations, queries, keys, attention weights and values) are rounded
to that dtype with one scale per tensor and the product is accumulated
in float32, as a float8 path with float32 accumulation computes; the
residual stream and the keys and values are held in the configuration's
``torch_dtype``, as the program holds them.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _round(a, low):
    """``a`` in float32, or rounded to ``low`` with one scale per tensor."""
    a = a.astype(jnp.float32)
    if low is None:
        return a
    fmax = float(jnp.finfo(low).max)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / fmax
    return (a / scale).astype(low).astype(jnp.float32) * scale


def _layer_norm(x, weight_offset, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)
            * (1.0 + weight_offset.astype(jnp.float32))
            + bias.astype(jnp.float32))


def _rope(x, theta):
    """x: (R, S, heads, dh), positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _store(a, store):
    """``a`` as held between operations: in ``store``, or float32."""
    return a if store is None else a.astype(store).astype(jnp.float32)


def _mm(spec, a, b, low):
    return jnp.einsum(spec, _round(a, low), _round(b, low), precision=HI)


@functools.partial(jax.jit, static_argnames=("dims", "low", "store"))
def _block(x, body, li, *, dims, low, store):
    n_heads, n_kv, dh, eps, theta = dims
    at = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, li, keepdims=False), body["attn"])
    ff = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, li, keepdims=False), body["ffn"])
    r, s, _ = x.shape
    mm = functools.partial(_mm, low=low)

    h = _layer_norm(x, at["ln_scale"], at["ln_bias"], eps)
    q = mm("rsd,dh->rsh", h, at["wq"]).reshape(r, s, n_heads, dh)
    k = mm("rsd,dh->rsh", h, at["wk"]).reshape(r, s, n_kv, dh)
    v = mm("rsd,dh->rsh", h, at["wv"]).reshape(r, s, n_kv, dh)
    q, k = _rope(q, theta), _store(_rope(k, theta), store)
    v = _store(v, store)
    group = n_heads // n_kv
    q = q.reshape(r, s, n_kv, group, dh)
    scores = mm("rqkgd,rpkd->rkgqp", q, k) / np.sqrt(dh)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = mm("rkgqp,rpkd->rqkgd", probs, v).reshape(r, s, n_heads * dh)
    x = _store(x + mm("rsh,hd->rsd", attn, at["wo"]), store)

    h = _layer_norm(x, ff["ln_scale"], ff["ln_bias"], eps)
    m = mm("rsd,df->rsf", h, ff["w_in"])
    m = jax.nn.gelu(m + ff["b_in"].astype(jnp.float32), approximate=True)
    m = mm("rsf,fd->rsd", m, ff["w_out"])
    return _store(x + m + ff["b_out"].astype(jnp.float32), store)


@functools.partial(jax.jit, static_argnames=("low", "store"))
def _embed(embed, tokens, *, low, store):
    return _store(jnp.take(_round(embed, low), tokens, axis=0), store)


@jax.jit
def _rows(x, ii, pp):
    return x[ii, pp]


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "low"))
def _head(params, hidden, *, eps, vocab, low):
    h = _layer_norm(hidden, params["final_scale"], params["final_bias"], eps)
    return _mm("nd,dv->nv", h, params["lm_head"], low)[:, :vocab]


def logits(cfg: dict, params, seqs: Sequence[Sequence[int]],
           rows: Sequence[Sequence[int]], *, block: int = 8,
           pad_to: Optional[int] = None, low=None) -> List[np.ndarray]:
    """Logits at positions ``rows[i]`` of sequence ``seqs[i]``: one
    ``(len(rows[i]), vocab)`` float32 array per sequence. Sequences run
    ``block`` at a time, right-padded to ``pad_to`` (causal attention
    keeps padding out of every row asked for), one layer at a time."""
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["norm_epsilon"]),
            float(cfg["rope_theta"]))
    n_layers = cfg["num_hidden_layers"]
    s = pad_to or max(len(q) for q in seqs)
    body = params["body"][0]
    store = None if low is None else jnp.dtype(cfg["torch_dtype"])
    out: List[np.ndarray] = []
    for b0 in range(0, len(seqs), block):
        chunk = list(seqs[b0:b0 + block])
        toks = np.zeros((block, s), np.int32)
        for i, q in enumerate(chunk):
            toks[i, :len(q)] = q
        x = _embed(params["embed"], jnp.asarray(toks), low=low, store=store)
        for li in range(n_layers):
            x = _block(x, body, jnp.int32(li), dims=dims, low=low,
                       store=store)
        sel = [(i, p) for i in range(len(chunk)) for p in rows[b0 + i]]
        # row count padded to a multiple of 256, so few shapes compile
        n_pad = -(-len(sel) // 256) * 256
        ii = np.zeros(n_pad, np.int32)
        pp = np.zeros(n_pad, np.int32)
        ii[:len(sel)] = [i for i, _ in sel]
        pp[:len(sel)] = [p for _, p in sel]
        lg = np.asarray(_head(params, _rows(x, jnp.asarray(ii),
                                            jnp.asarray(pp)),
                              eps=dims[3], vocab=cfg["vocab_size"],
                              low=low))
        k = 0
        for i in range(len(chunk)):
            n = len(rows[b0 + i])
            out.append(lg[k:k + n])
            k += n
    return out
