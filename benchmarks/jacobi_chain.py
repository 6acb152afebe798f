"""Deep stencil pipeline through halo-aware MapFusion: a 4-stage 1-D
jacobi chain compiled to ONE Pallas grid kernel.

Each stage reads its predecessor at ``i-1, i, i+1`` — the write-order =
read-order rule lets MapFusion replicate producers per offset
(content-deduplicated: 1+3+5+7 = 16 tasklets for 4 stages at radius 1)
so the three intermediates never leave VMEM.  The per-stage baseline is
the identical pipeline minus MapFusionPass: four grid kernels with the
intermediates materialized between them.  The jnp/vmap lowering
cross-validates both.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.memlet import Memlet, Subset
from repro.core.sdfg import SDFG
from repro.core.symbolic import sym
from repro.pipeline import (ExpandLibraryNodesPass, GridConversionPass,
                            MapTilingPass, PassManager, PipelineFusionPass,
                            SetExpansionPreferencePass, VectorizationPass,
                            lower)

N = 8704          # interior after 4 stages: 8192 (all extents % 64 == 0)
N_SMALL = 1088    # interior after 4 stages: 576
STAGES = 4
MARGIN = 64       # stage k computes [MARGIN*(k+1), n - MARGIN*(k+1))
REPS = 5


def _chain_sdfg(n, stages=STAGES):
    s = SDFG("jacobi_chain")
    s.add_array("a", (n,), "float32")
    s.add_array("b", (n,), "float32")
    names = ["a"]
    for k in range(1, stages):
        s.add_transient(f"t{k}", (n,), "float32")
        names.append(f"t{k}")
    names.append("b")
    st = s.add_state("main", is_start=True)
    i = sym("i")
    node_of = {}
    for k in range(stages):
        src, dst = names[k], names[k + 1]
        lo, hi = MARGIN * (k + 1), n - MARGIN * (k + 1)
        _, _, ex = st.add_mapped_tasklet(
            f"jacobi{k}", {"i": (lo, hi)},
            inputs={"w": Memlet.simple(src, Subset.indices([i - 1])),
                    "c": Memlet.simple(src, Subset.indices([i])),
                    "e": Memlet.simple(src, Subset.indices([i + 1]))},
            outputs={"o": Memlet.simple(dst, Subset.indices([i]))},
            fn=lambda w, c, e: 0.25 * w + 0.5 * c + 0.25 * e,
            input_nodes={src: node_of[src]} if src in node_of else None)
        node_of[dst] = next(e.dst for e in st.out_edges(ex)
                            if e.memlet.data == dst)
    return s


def _reference(a, stages=STAGES):
    n = a.shape[0]
    cur = a
    for k in range(stages):
        lo, hi = MARGIN * (k + 1), n - MARGIN * (k + 1)
        nxt = np.zeros_like(cur)
        nxt[lo:hi] = (0.25 * cur[lo - 1:hi - 1] + 0.5 * cur[lo:hi]
                      + 0.25 * cur[lo + 1:hi + 1])
        cur = nxt
    return cur


def _perstage_pipeline():
    """The pallas default pipeline with MapFusionPass removed: every
    stage stays its own scope and converts to its own grid kernel."""
    tiles = GridConversionPass.default_tiles("pallas")
    return PassManager([
        SetExpansionPreferencePass(("pallas", "xla", "generic")),
        PipelineFusionPass(),
        ExpandLibraryNodesPass(),
        VectorizationPass(),
        MapTilingPass(tile_size=tiles.get("minor"),
                      second_size=tiles.get("second")),
        GridConversionPass(),
    ], name="jacobi_perstage")


def _time(fn, *args, **kwargs):
    fn(*args, **kwargs)  # compile / warm
    best = float("inf")
    out = None
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        np.asarray(out["b"])
        best = min(best, time.perf_counter() - t0)
    return out, best


def run(report, small: bool = False):
    n = N_SMALL if small else N
    rng = np.random.default_rng(7)
    a = rng.standard_normal((n,)).astype(np.float32)

    cf = lower(_chain_sdfg(n)).compile("pallas")
    assert len(cf.report["grid_kernels"]) == 1, \
        f"fused chain must be ONE grid kernel, got {cf.report['grid_kernels']}"
    blocks = cf.report["grid_converted"][0]["block_shape"]
    tasklets = cf.report["grid_converted"][0].get("tasklets")

    cp = lower(_chain_sdfg(n)).compile("pallas",
                                       pipeline=_perstage_pipeline())
    assert len(cp.report["grid_kernels"]) == STAGES, \
        f"per-stage baseline must be {STAGES} kernels, " \
        f"got {cp.report['grid_kernels']}"

    cj = lower(_chain_sdfg(n)).compile("jnp")

    of, tf = _time(cf, a=a)
    op, tp = _time(cp, a=a)
    oj, tj = _time(cj, a=a)

    ref = _reference(a)
    np.testing.assert_allclose(np.asarray(of["b"]), ref,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(op["b"]), ref,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(oj["b"]), ref,
                               rtol=1e-4, atol=1e-5)

    report("jacobi_chain_fused_ms", tf * 1e3,
           f"n={n}; {STAGES} stages as ONE grid kernel "
           f"({tasklets} tasklets after halo replication, blocks={blocks}); "
           f"{tp/tf:.2f}x vs per-stage",
           backend="pallas", grid_kernels=1, block_shape=blocks)
    report("jacobi_chain_perstage_ms", tp * 1e3,
           f"n={n}; one grid kernel per stage, intermediates materialized",
           backend="pallas", grid_kernels=STAGES)
    report("jacobi_chain_jnp_ms", tj * 1e3,
           f"n={n}; structural vmap lowering")
    assert tf < tp, \
        "fused chain must beat the per-stage baseline"
