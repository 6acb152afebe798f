"""Attention Library Nodes (paper §3): decode attention over paged KV.

``PagedAttnDecode`` abstracts one serving decode step of attention for a
whole batch: q is (B, H, Dh), the context K/V — gathered from the paged
KV pool via the block table — is (B, C, H, Dh) with C the context
bucket, and ``pos`` (B,) carries each sequence's absolute position for
causal/window masking. Expansion levels, most specialized first:

  * ``flash``   -- delegate to the hand-written Pallas kernel
                   (``kernels.attention.decode_attention``), the paper's
                   'vendor library' level;
  * ``pallas``  -- a generic (b, h) mapped tasklet whose affine memlets
                   let MapTiling + GridConversion derive a batched grid
                   kernel (the serving default: the attention step shows
                   up in ``report['grid_kernels']``);
  * ``xla``     -- one jnp tasklet, the shardable reference.

All three share one masking contract: key j participates iff
``j <= pos[b]`` (and ``j > pos[b] - window`` when sliding-window), so
unwritten pages and the null page of inactive slots never reach the
softmax regardless of what garbage they hold.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.memlet import Memlet, Range, Subset
from ..core.sdfg import SDFG, LibraryNode, State
from ..core.symbolic import sym
from .util import in_edge, out_edge, replace_with_tasklet

NEG_INF = -1e30


def _operand_shape(sdfg: SDFG, state: State, node, conn: str):
    e = in_edge(state, node, conn)
    desc = sdfg.arrays[e.memlet.data]
    return tuple(int(s.evaluate(sdfg.symbol_values)) for s in desc.shape)


def _expand_xla(node: "PagedAttnDecode", sdfg: SDFG, state: State):
    _, ctx, _, dh = _operand_shape(sdfg, state, node, "k")
    scale = 1.0 / np.sqrt(dh)
    window = node.window

    def attn(q, k, v, pos):
        qf = q.astype(jnp.float32)
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)
        s = jnp.einsum("bhd,bchd->bhc", qf, kf) * scale
        j = jnp.arange(ctx)[None, None, :]
        mask = j <= pos[:, None, None]
        if window is not None:
            mask &= j > pos[:, None, None] - window
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhc,bchd->bhd", p, vf)
        return {"out": out.astype(q.dtype)}

    replace_with_tasklet(node, sdfg, state, attn, "xla")


def _expand_flash(node: "PagedAttnDecode", sdfg: SDFG, state: State):
    window = node.window
    from ..codegen.device import resolve_interpret
    interpret = resolve_interpret(sdfg.metadata.get("pallas_interpret"))

    def attn(q, k, v, pos):
        from ..kernels.attention import decode_attention
        return {"out": decode_attention(q, k, v, pos, window=window,
                                        interpret=interpret)}

    replace_with_tasklet(node, sdfg, state, attn, "flash")


def _expand_grid(node: "PagedAttnDecode", sdfg: SDFG, state: State):
    """Generic (b, h) map over per-head attention rows.

    Every memlet is affine in the map parameters (the context/head-dim
    extents move as whole dims), so GridConversion can factor them into
    BlockSpecs; the per-iteration operands are rows/matrices, which takes
    the nested-vmap kernel-body path. MapTiling tiles b into sublane
    blocks (dtype-aware when the pipeline leaves second_size unset), so
    the derived grid streams (b_tile, C, Dh) context slabs through VMEM.
    """
    eq = in_edge(state, node, "q")
    ek = in_edge(state, node, "k")
    ev = in_edge(state, node, "v")
    ep = in_edge(state, node, "pos")
    eo = out_edge(state, node, "out")
    b_n, h_n, dh = _operand_shape(sdfg, state, node, "q")
    _, ctx, _, _ = _operand_shape(sdfg, state, node, "k")
    scale = 1.0 / np.sqrt(dh)
    window = node.window

    def attn_row(q, k, v, pos):
        # one query row: multiply-and-reduce on the vector unit (a
        # matrix-vector product vmapped over heads is a batched dot the
        # TPU kernel compiler cannot lower)
        qf = q.astype(jnp.float32)
        kf = k.astype(jnp.float32)
        s = jnp.sum(kf * qf[None, :], axis=-1) * scale   # (C,)
        j = jnp.arange(ctx)
        mask = j <= pos
        if window is not None:
            mask &= j > pos - window
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.sum(p[:, None] * v.astype(jnp.float32), axis=0)
        return {"out": out.astype(q.dtype)}

    b, h = sym("b"), sym("h")
    qd, kd, vd = eq.memlet.data, ek.memlet.data, ev.memlet.data
    pd, od = ep.memlet.data, eo.memlet.data
    state.remove_node(node)
    state.add_mapped_tasklet(
        f"{node.label}_grid", {"b": (0, b_n), "h": (0, h_n)},
        inputs={
            "q": Memlet.simple(qd, Subset([Range.index(b), Range.index(h),
                                           Range.make(0, dh)])),
            "k": Memlet.simple(kd, Subset([Range.index(b),
                                           Range.make(0, ctx),
                                           Range.index(h),
                                           Range.make(0, dh)])),
            "v": Memlet.simple(vd, Subset([Range.index(b),
                                           Range.make(0, ctx),
                                           Range.index(h),
                                           Range.make(0, dh)])),
            "pos": Memlet.simple(pd, Subset([Range.index(b)])),
        },
        outputs={
            "out": Memlet.simple(od, Subset([Range.index(b), Range.index(h),
                                             Range.make(0, dh)])),
        },
        fn=attn_row,
        input_nodes={qd: eq.src, kd: ek.src, vd: ev.src, pd: ep.src},
        output_nodes={od: eo.dst},
    )


class PagedAttnDecode(LibraryNode):
    """Batched single-token decode attention over a gathered context.

    Connectors: q (B, H, Dh), k/v (B, C, H, Dh) — already GQA-repeated to
    H heads by the page gather — pos (B,) int32 -> out (B, H, Dh).
    """

    expansions = {
        "flash": _expand_flash,
        "pallas": _expand_grid,
        "xla": _expand_xla,
        "generic": _expand_grid,
    }
    default_expansion = "xla"

    def __init__(self, name: str, window: Optional[int] = None):
        super().__init__(name, inputs=["q", "k", "v", "pos"],
                         outputs=["out"])
        self.window = window
