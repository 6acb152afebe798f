"""Paper §6 case study: StencilFlow program through the multi-level stack.

JSON program (Fig. 17, two diffusion iterations) -> stencil Library Nodes
-> DeviceOffload + StreamingComposition -> fused multi-stage Pallas kernel
(sliding-window VMEM slabs; the intermediate field never leaves VMEM).

Run: PYTHONPATH=src python examples/stencil_pipeline.py
"""
import numpy as np

import repro.kernels  # noqa: F401
from repro.codegen.device import enable_compile_cache
from repro.frontends.stencil import build_stencil_program
from repro.kernels.stencil import stencil2d_ref
from repro.pipeline import (DeviceOffloadPass, StreamingCompositionPass,
                            lower)

PROGRAM = {
    "name": "diffusion_2it",
    "dimensions": [1024, 512],
    "outputs": ["d"],
    "inputs": {"a": {"data_type": "float32", "input_dims": ["j", "k"]}},
    "program": {
        "b": {"computation": "b = c0*a[j,k] + c1*a[j-1,k] + c2*a[j+1,k] + "
                             "c3*a[j,k-1] + c4*a[j,k+1]"},
        "d": {"computation": "d = c0*b[j,k] + c1*b[j-1,k] + c2*b[j+1,k] + "
                             "c3*b[j,k-1] + c4*b[j,k+1]"},
    },
}


def main():
    enable_compile_cache()
    print("== parse JSON program ->", len(PROGRAM["program"]),
          "stencil operators")
    staged = lower(build_stencil_program(PROGRAM))
    staged.optimize([DeviceOffloadPass()])
    v0 = staged.sdfg.off_chip_volume()
    staged.optimize([StreamingCompositionPass()])
    n_comp = staged.reports[-1]["passes"][0]["summary"]
    v1 = staged.sdfg.off_chip_volume()
    print(f"== StreamingComposition: {n_comp} intermediate(s) -> streams; "
          f"volume {v0/2**20:.1f} -> {v1/2**20:.1f} MiB")

    c = staged.compile("pallas")
    print("== fused:", c.report["fused_regions"])

    rng = np.random.default_rng(0)
    a = rng.standard_normal(tuple(PROGRAM["dimensions"])).astype(np.float32)
    co = np.array([0.2, 0.1, 0.15, 0.25, 0.3], np.float32)
    out = np.asarray(c(a=a, b_coeffs=co, d_coeffs=co)["d"])
    offs = [(0, 0), (-1, 0), (1, 0), (0, -1), (0, 1)]
    exp = np.asarray(stencil2d_ref(stencil2d_ref(a, co, offs), co, offs))
    np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-5)
    print("== matches the unfused reference. OK")


if __name__ == "__main__":
    main()
