"""Quickstart: the paper's §3 multi-level flow on AXPYDOT (Figs. 9-13),
expressed through the staged AOT pipeline (ARCHITECTURE.md):

    Wrapped --lower()--> Lowered --optimize(passes)--> Lowered
            --compile(backend)--> Compiled

Build via the Python/BLAS frontend -> offload to device -> stream memory
accesses -> compose pipelines -> compile with both 'vendor' backends
(XLA-auto and Pallas-explicit) and compare; a second compile of the same
program is served from the compilation cache.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

import repro.kernels  # noqa: F401  (register fused kernels)
from repro.codegen.device import enable_compile_cache
from repro.frontends import blas
from repro.frontends.api import dc_program
from repro.pipeline import (COMPILATION_CACHE, PassManager,
                            DeviceOffloadPass, StreamingCompositionPass,
                            StreamingMemoryPass, VectorizationPass)


@dc_program
def axpydot(p, n):
    a = p.scalar_input("a", "float32")
    x, y, w = (p.input(nm, (n,)) for nm in ("x", "y", "w"))
    p.output("result", blas.dot(blas.axpy(a, x, y), w))


def main():
    enable_compile_cache()
    n = 1 << 20
    rng = np.random.default_rng(0)
    a = np.float32(0.7)
    x, y, w = (rng.standard_normal(n).astype(np.float32) for _ in range(3))
    expected = float(np.dot((a * x + y).astype(np.float32), w))

    print("== 1. trace: Wrapped -> Lowered (generic SDFG, paper Fig. 10)")
    lowered = axpydot.lower(n)
    print("  ", lowered)

    print("== 2. DeviceOffload pass (paper Fig. 11, FPGATransformSDFG)")
    lowered.optimize([DeviceOffloadPass()])
    naive_vol = lowered.sdfg.off_chip_volume()
    print(f"   off-chip volume: {naive_vol/2**20:.1f} MiB")

    print("== 3. Vectorization + StreamingComposition + StreamingMemory "
          "(paper Fig. 12)")
    mid = PassManager([VectorizationPass(width=128),
                       StreamingCompositionPass(),
                       StreamingMemoryPass()], name="streaming_ladder")
    lowered.optimize(mid)
    stream_vol = lowered.sdfg.off_chip_volume()
    main_state = [s for s in lowered.sdfg.states if s.label == "main"][0]
    for entry in lowered.reports[-1]["passes"]:
        print(f"   pass {entry['name']:22s} applied={entry['summary']} "
              f"({entry['seconds']*1e3:.1f} ms)")
    print(f"   off-chip volume: {stream_vol/2**20:.1f} MiB "
          f"({naive_vol/stream_vol:.2f}x less; z never leaves VMEM)")
    print(f"   processing elements in kernel state: "
          f"{len(main_state.processing_elements())}")

    print("== 4. compile with both vendor backends (default pipelines)")
    for backend in ("jnp", "pallas"):
        staged = axpydot.lower(n).optimize(
            [DeviceOffloadPass(), StreamingCompositionPass()])
        c = staged.compile(backend)
        out = float(np.asarray(c(a=a, x=x, y=y, w=w)["result"]).ravel()[0])
        fused = c.report["fused_regions"]
        print(f"   backend={backend:7s} result={out:+.4f} "
              f"(expected {expected:+.4f}) fused={fused}")

    print("== 5. recompile: served from the compilation cache")
    before = COMPILATION_CACHE.stats
    axpydot.lower(n).optimize(
        [DeviceOffloadPass(), StreamingCompositionPass()]).compile("pallas")
    after = COMPILATION_CACHE.stats
    assert after["hits"] == before["hits"] + 1, (before, after)
    print(f"   cache: {after}")
    print("OK")


if __name__ == "__main__":
    main()
