"""Paper §5 case study: LeNet-5 inference ladder (Table 3).

naive -> InputToConstant -> StreamingComposition, driven through the
staged pipeline (Lowered.optimize with pass pipelines) and compiled with
the Pallas backend (conv+pool stages fuse into im2col systolic GEMMs).

Run: PYTHONPATH=src python examples/lenet_pipeline.py
"""
import time

import numpy as np

import repro.kernels  # noqa: F401
from repro.codegen.device import enable_compile_cache
from repro.frontends.ml import build_lenet, init_lenet_params, lenet_reference
from repro.pipeline import (DeviceOffloadPass, InputToConstantPass,
                            StreamingCompositionPass, lower)


def main():
    enable_compile_cache()
    batch = 100
    params = init_lenet_params()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, 1, 28, 28)).astype(np.float32)
    expected = np.asarray(lenet_reference(params, x))

    print("== naive (all parameters and intermediates off-chip)")
    l1 = lower(build_lenet(batch)).optimize([DeviceOffloadPass()])
    print(f"   off-chip volume: {l1.sdfg.off_chip_volume()/2**20:.2f} MiB")
    out = l1.compile("jnp")(x=x, **params)
    np.testing.assert_allclose(np.asarray(out["probs"]), expected,
                               rtol=1e-2, atol=1e-4)

    print("== InputToConstant (paper: parameters fixed in hardware)")
    l2 = lower(build_lenet(batch)).optimize(
        [InputToConstantPass(parameters=params), DeviceOffloadPass()])
    v_const = l2.sdfg.off_chip_volume()
    print(f"   off-chip volume: {v_const/2**20:.2f} MiB")

    print("== + StreamingComposition, Pallas backend")
    l2.optimize([StreamingCompositionPass()])
    v_stream = l2.sdfg.off_chip_volume()
    c = l2.compile("pallas")
    t0 = time.perf_counter()
    out = c(x=x)
    dt = time.perf_counter() - t0
    np.testing.assert_allclose(np.asarray(out["probs"]), expected,
                               rtol=1e-2, atol=1e-4)
    print(f"   off-chip volume: {v_stream/2**20:.2f} MiB")
    print(f"   fused pipelines: {c.report['fused_regions']}")
    print(f"   inference time (CPU, interpret): {dt*1e3:.1f} ms "
          f"for batch {batch}")
    print("OK")


if __name__ == "__main__":
    main()
