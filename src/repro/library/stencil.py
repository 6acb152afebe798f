"""Stencil Library Node (paper §6, StencilFlow).

One abstract node per stencil operator; expansions:

  * ``xla``    -- padded-shift jnp composite (XLA auto-fuses; the 'Intel
                  shift register' analogue where the compiler manages
                  buffering),
  * ``pallas`` -- the explicit sliding-window VMEM kernel (the 'Xilinx
                  explicit buffers' analogue, §6.2).

Chains of Stencil nodes composed through streams fuse into a single
multi-stage Pallas kernel (registered below) — StencilFlow's fully
pipelined multi-stencil architecture with delay buffers as VMEM halos.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp

from ..codegen.device import resolve_interpret
from ..codegen.pipeline_fusion import FUSION_REGISTRY
from ..core.sdfg import LibraryNode, SDFG, State
from .util import replace_with_tasklet


class Stencil(LibraryNode):
    """2D stencil with static offsets and runtime scalar coefficients."""
    default_expansion = "xla"

    def __init__(self, name: str, offsets: Sequence[Tuple[int, int]],
                 coeff_names: Sequence[str]):
        super().__init__(name, inputs=["a", "c"], outputs=["b"])
        self.offsets = tuple(tuple(o) for o in offsets)
        self.coeff_names = list(coeff_names)

    @property
    def radius(self) -> int:
        return max(max(abs(di), abs(dj)) for di, dj in self.offsets)


def _stencil_xla(node: Stencil, sdfg: SDFG, state: State):
    offsets = node.offsets

    def fn(a, c):
        from ..kernels.stencil import stencil2d_ref
        return stencil2d_ref(a, [c[k] for k in range(len(offsets))], offsets)

    replace_with_tasklet(node, sdfg, state, fn, "xla")


def _stencil_pallas(node: Stencil, sdfg: SDFG, state: State):
    offsets = node.offsets
    interpret = resolve_interpret(sdfg.metadata.get("pallas_interpret"))

    def fn(a, c):
        from ..kernels.stencil import stencil2d
        return stencil2d(a, c, offsets, interpret=interpret)

    replace_with_tasklet(node, sdfg, state, fn, "pallas")


Stencil.expansions = {"xla": _stencil_xla, "generic": _stencil_xla,
                      "pallas": _stencil_pallas}


def _fuse_stencil_chain(chain, sdfg, state, interpret, in_map, out_map):
    """N consecutive stencils -> one fused multi-stage kernel."""
    offsets_per_stage = tuple(n.offsets for n in chain)
    a_c = in_map[(chain[0].label, "a")]
    c_cs = [in_map[(n.label, "c")] for n in chain]
    out_c = out_map[(chain[-1].label, "b")]

    def fn(**kw):
        from ..kernels.stencil import stencil2d_chain
        coeffs = [kw[c] for c in c_cs]
        return {out_c: stencil2d_chain(kw[a_c], coeffs, offsets_per_stage,
                                       interpret=interpret)}

    return fn


# register chains of length 2..6
for _k in range(2, 7):
    FUSION_REGISTRY[tuple(["Stencil"] * _k)] = _fuse_stencil_chain
