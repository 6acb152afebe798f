"""Paper Table 3: LeNet-5 inference ladder — naive / InputToConstant /
+StreamingComposition. Volumes analytic at the paper's batch=1000; runtime
at batch=100 on CPU (naive jnp vs streamed pallas-interpret).

The conv-stack rung compiles LeNet's first conv+relu+maxpool block to ONE
Pallas grid kernel through halo-aware MapFusion: the pool consumer reads
the conv intermediate at the four strided points ``t[2p+u, 2q+v]``, so
MapFusion replicates the conv producer per offset (4 replicas + pool = 5
tasklets) and the feature map never leaves VMEM."""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

import repro.kernels  # noqa: F401
from repro.core.memlet import Memlet, Range, Subset
from repro.core.sdfg import SDFG
from repro.core.symbolic import sym
from repro.frontends.ml import build_lenet, init_lenet_params, lenet_reference
from repro.pipeline import (DeviceOffloadPass, ExpandLibraryNodesPass,
                            GridConversionPass, InputToConstantPass,
                            MapTilingPass, PassManager, PipelineFusionPass,
                            SetExpansionPreferencePass,
                            StreamingCompositionPass, VectorizationPass,
                            lower)
from repro.transforms import (DeviceOffload, InputToConstant,
                              StreamingComposition)

PAPER_BATCH = 1000
BENCH_BATCH = 100
CONV_BATCH = 16
K, R, IH = 8, 5, 28          # channels, kernel, input H=W (LeNet conv1)
OH, PH = IH - R + 1, (IH - R + 1) // 2


def _convblock_sdfg(batch):
    """conv(5x5, K ch) + relu + 2x2 maxpool over a (batch,1,28,28) input
    as two mapped tasklets sharing the feature-map access node."""
    s = SDFG("convblock")
    s.add_array("x", (batch, 1, IH, IH), "float32")
    s.add_array("W", (K, 1, R, R), "float32")
    s.add_array("bias", (K,), "float32")
    s.add_transient("t", (batch, K, OH, OH), "float32")
    s.add_array("y", (batch, K, PH, PH), "float32")
    st = s.add_state("main", is_start=True)
    n, k, oh, ow = sym("n"), sym("k"), sym("oh"), sym("ow")
    _, _, ex = st.add_mapped_tasklet(
        "conv", {"n": (0, batch), "k": (0, K), "oh": (0, OH), "ow": (0, OH)},
        inputs={"xs": Memlet.simple("x", Subset([
                    Range.index(n), Range.index(0),
                    Range.make(oh, oh + R), Range.make(ow, ow + R)])),
                "w": Memlet.simple("W", Subset([
                    Range.index(k), Range.index(0),
                    Range.make(0, R), Range.make(0, R)])),
                "bb": Memlet.simple("bias", Subset.indices([k]))},
        outputs={"o": Memlet.simple("t", Subset.indices([n, k, oh, ow]))},
        fn=lambda xs, w, bb: jnp.maximum(jnp.sum(xs * w) + bb, 0.0))
    t_node = next(e.dst for e in st.out_edges(ex) if e.memlet.data == "t")
    ph, pw = sym("ph"), sym("pw")
    st.add_mapped_tasklet(
        "pool", {"n": (0, batch), "k": (0, K), "ph": (0, PH), "pw": (0, PH)},
        inputs={f"p{u}{v}": Memlet.simple("t", Subset.indices(
                    [n, k, 2 * ph + u, 2 * pw + v]))
                for u in (0, 1) for v in (0, 1)},
        outputs={"o": Memlet.simple("y", Subset.indices([n, k, ph, pw]))},
        fn=lambda p00, p01, p10, p11: jnp.maximum(jnp.maximum(p00, p01),
                                                  jnp.maximum(p10, p11)),
        input_nodes={"t": t_node})
    return s


def _convblock_reference(x, W, bias):
    batch = x.shape[0]
    t = np.zeros((batch, K, OH, OH), np.float32)
    for u in range(R):
        for v in range(R):
            t += np.einsum("nij,k->nkij",
                           x[:, 0, u:u + OH, v:v + OH], W[:, 0, u, v])
    t = np.maximum(t + bias[None, :, None, None], 0.0)
    return t.reshape(batch, K, PH, 2, PH, 2).max(axis=(3, 5))


def _perstage_pipeline():
    tiles = GridConversionPass.default_tiles("pallas")
    return PassManager([
        SetExpansionPreferencePass(("pallas", "xla", "generic")),
        PipelineFusionPass(),
        ExpandLibraryNodesPass(),
        VectorizationPass(),
        MapTilingPass(tile_size=tiles.get("minor"),
                      second_size=tiles.get("second")),
        GridConversionPass(),
    ], name="convblock_perstage")


def _volumes(batch, params):
    out = {}
    s = build_lenet(batch)
    s.apply(DeviceOffload)
    out["naive"] = s.off_chip_volume()
    s2 = build_lenet(batch)
    s2.apply(InputToConstant, parameters=params)
    s2.apply(DeviceOffload)
    out["const"] = s2.off_chip_volume()
    s2.apply(StreamingComposition)
    out["stream"] = s2.off_chip_volume()
    return out


def run(report, small: bool = False):
    bench_batch = 20 if small else BENCH_BATCH
    params = init_lenet_params()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((bench_batch, 1, 28, 28)).astype(np.float32)
    exp = np.asarray(lenet_reference(params, x))

    vols = _volumes(PAPER_BATCH, params)

    c1 = lower(build_lenet(bench_batch)).optimize(
        [DeviceOffloadPass()]).compile("jnp")
    c1(x=x, **params)
    t0 = time.perf_counter()
    o1 = c1(x=x, **params)
    t_naive = time.perf_counter() - t0
    np.testing.assert_allclose(np.asarray(o1["probs"]), exp, rtol=1e-2,
                               atol=1e-4)

    c2 = lower(build_lenet(bench_batch)).optimize(
        [InputToConstantPass(parameters=params), DeviceOffloadPass(),
         StreamingCompositionPass()]).compile("pallas")
    c2(x=x)
    t0 = time.perf_counter()
    o2 = c2(x=x)
    t_stream = time.perf_counter() - t0
    np.testing.assert_allclose(np.asarray(o2["probs"]), exp, rtol=1e-2,
                               atol=1e-4)

    report("lenet_naive_volume_GiB", vols["naive"] / 2**30,
           "paper 0.28 GiB @ batch 1000 (incl. per-tile weight re-streams "
           "we don't model; see EXPERIMENTS §Paper)")
    report("lenet_const_volume_GiB", vols["const"] / 2**30,
           f"ratio {vols['naive']/vols['const']:.2f}x @1000; 1.20x @32 "
           f"(paper 1.27x)")
    report("lenet_stream_volume_GiB", vols["stream"] / 2**30,
           f"ratio {vols['naive']/vols['stream']:.2f}x (paper 1.7x; we "
           f"stream every intermediate)")
    report("lenet_naive_ms", t_naive * 1e3, f"batch={bench_batch} CPU jnp")
    report("lenet_stream_pallas_ms", t_stream * 1e3,
           f"fused {c2.report['fused_regions']}")

    # conv stack through halo-aware MapFusion: ONE grid kernel for
    # conv+relu+maxpool vs one kernel per stage vs the jnp lowering
    cb = 2 if small else CONV_BATCH
    xc = rng.standard_normal((cb, 1, IH, IH)).astype(np.float32)
    Wc = (rng.standard_normal((K, 1, R, R)) * 0.1).astype(np.float32)
    bc = (rng.standard_normal((K,)) * 0.1).astype(np.float32)
    ref = _convblock_reference(xc, Wc, bc)

    cf = lower(_convblock_sdfg(cb)).compile("pallas")
    assert len(cf.report["grid_kernels"]) == 1, \
        f"conv stack must be ONE grid kernel, got {cf.report['grid_kernels']}"
    blocks = cf.report["grid_converted"][0]["block_shape"]
    cp = lower(_convblock_sdfg(cb)).compile("pallas",
                                            pipeline=_perstage_pipeline())
    assert len(cp.report["grid_kernels"]) == 2, \
        f"per-stage conv stack must be 2 kernels, " \
        f"got {cp.report['grid_kernels']}"
    cj = lower(_convblock_sdfg(cb)).compile("jnp")

    def _best(fn):
        fn(x=xc, W=Wc, bias=bc)  # compile / warm
        best, out = float("inf"), None
        for _ in range(5):
            t0 = time.perf_counter()
            out = fn(x=xc, W=Wc, bias=bc)
            np.asarray(out["y"])
            best = min(best, time.perf_counter() - t0)
        return out, best

    of, tf = _best(cf)
    op, tp = _best(cp)
    oj, tj = _best(cj)
    np.testing.assert_allclose(np.asarray(of["y"]), ref, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(op["y"]), ref, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(oj["y"]), ref, rtol=1e-4,
                               atol=1e-5)
    report("lenet_convblock_fused_ms", tf * 1e3,
           f"batch={cb}; conv+relu+pool as ONE grid kernel (4 conv "
           f"replicas + pool, blocks={blocks}); {tp/tf:.2f}x vs per-stage",
           backend="pallas", grid_kernels=1, block_shape=blocks)
    report("lenet_convblock_perstage_ms", tp * 1e3,
           f"batch={cb}; conv and pool as separate grid kernels",
           backend="pallas", grid_kernels=2)
    report("lenet_convblock_jnp_ms", tj * 1e3,
           f"batch={cb}; structural vmap lowering")
    assert tf < tp, "fused conv stack must beat the per-stage baseline"
