"""Legacy one-shot compile entry point, now a shim over the staged
pipeline (repro.pipeline): ``compile_sdfg(s, ...)`` is exactly
``pipeline.lower(s).compile(..., in_place=True)``.

The backend split (paper §2.1) lives in ``pipeline.passes
.default_pipeline``: ``jnp`` prefers (xla, generic) expansions and lets
XLA fuse (the Intel-OpenCL analogue); ``pallas`` runs pipeline-fusion
first and prefers (pallas, xla, generic) (the Vivado-HLS analogue). Both
produce the same function semantics; tests cross-validate them.

``in_place=True`` preserves the historical contract that the caller's
SDFG is expanded by compilation (callers inspect the lowered graph);
staged callers get a pristine ``Lowered`` plus a private compiled copy.
In-place compiles deliberately bypass ``pipeline.COMPILATION_CACHE`` —
the produced callable would alias the caller's live graph and a hit
would skip the in-place expansion — so only the staged path
(``Lowered.compile``) is served from the cache.
"""
from __future__ import annotations

from typing import Optional

from ..core.sdfg import SDFG
from ..pipeline.stages import BACKENDS, Compiled, Lowered

#: compat alias: the executable stage used to be defined here.
CompiledSDFG = Compiled


def compile_sdfg(sdfg: SDFG, backend: str = "jnp", jit: bool = True,
                 interpret: Optional[bool] = None,
                 expansion_level: Optional[str] = None) -> Compiled:
    return Lowered(sdfg).compile(
        backend=backend, jit=jit, interpret=interpret,
        expansion_level=expansion_level, in_place=True)
