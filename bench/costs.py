"""Operations and bytes the algorithms need, computed from shapes.

These are the numerators of every utilisation and roofline share the
benchmark reports. They count what the mathematics needs, not what an
implementation happens to do: padding lanes, padded context, repeated
KV heads and recomputation do not count, so a better implementation can
only read higher, never above 100%.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DecoderDims:
    """The widths of a decoder-only transformer with grouped-query
    attention."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated_mlp: bool = False

    @classmethod
    def from_config(cls, c: dict) -> "DecoderDims":
        heads = c["num_attention_heads"]
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=heads, n_kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim", c["hidden_size"] // heads),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   gated_mlp=c["hidden_act"] in ("silu", "swiglu"))


def matmul_params(d: DecoderDims) -> int:
    """Weights one token multiplies through: every projection of every
    layer and the output head (the embedding is a gather, not a
    product)."""
    attn = d.d_model * d.head_dim * (2 * d.n_heads + 2 * d.n_kv_heads)
    mlp = (3 if d.gated_mlp else 2) * d.d_model * d.d_ff
    return d.n_layers * (attn + mlp) + d.d_model * d.vocab


def attention_flops(d: DecoderDims, ctx: int) -> int:
    """q.k and p.v for one query over ``ctx`` keys, in every layer."""
    return d.n_layers * 4 * d.n_heads * d.head_dim * ctx


def token_flops(d: DecoderDims, ctx: int) -> int:
    """One token through the model, attending over ``ctx`` keys (itself
    included)."""
    return 2 * matmul_params(d) + attention_flops(d, ctx)


def prefill_flops(d: DecoderDims, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens, each attending causally."""
    causal = prompt_len * (prompt_len + 1) // 2
    return (prompt_len * 2 * matmul_params(d)
            + d.n_layers * 4 * d.n_heads * d.head_dim * causal)


def decode_attention_cost(d: DecoderDims, ctxs, kv_bytes: int = 2,
                          act_bytes: int = 2):
    """One decode step's attention over all layers, for live lanes whose
    contexts are ``ctxs``: ``(flops, bytes)``. Bytes are the lanes' real
    K and V at ``n_kv_heads`` (each KV head read once, nothing padded),
    plus each lane's query and output."""
    keys = sum(int(c) for c in ctxs)
    flops = d.n_layers * 4 * d.n_heads * d.head_dim * keys
    kv = 2 * keys * d.n_kv_heads * d.head_dim * kv_bytes
    qo = 2 * len(ctxs) * d.n_heads * d.head_dim * act_bytes
    return flops, d.n_layers * (kv + qo)


def axpydot_cost(n: int, itemsize: int = 4):
    """``(a*x + y) . w`` over ``n`` elements: ``(flops, bytes)``. Each of
    x, y and w is read once; the scalar in and out are negligible."""
    return 4 * n, 3 * n * itemsize


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bytes_per_s: float) -> float:
    """The least time the chip could take: the larger of the two
    bounds."""
    return max(flops / peak_flops, nbytes / peak_bytes_per_s)
