"""Paper Table 2: GEMVER composition ladder.

    B = A + u1 v1^T + u2 v2^T ; x = beta*B^T y + z ; w = alpha*B x

Variants: naive / streaming composition / manual composition (the paper's
§4.2 replication of the rank-1-update result so pipeline fusion applies
once more). Volumes analytic at the paper's N=16,384 (GiB); runtime at a
reduced N on CPU. The native grid path additionally compares the unfused
kernel ladder (2x ger + 2x gemv grid kernels, B1 round-tripping through
HBM) against MapFusion (the two rank-1 updates as ONE grid kernel with
B1 held in-kernel).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import Memlet
from repro.frontends import blas
from repro.frontends.api import Program
from repro.pipeline import (ExpandLibraryNodesPass, GridConversionPass,
                            MapFusionPass, MapTilingPass, PassManager,
                            SetExpansionPreferencePass, lower)
from repro.transforms import DeviceOffload, StreamingComposition

PAPER_N = 16_384
BENCH_N = 1024
GRID_N = 128              # grid-path comparison (interpret-mode kernels)


def build(n, manual_replication=False, replica_in_hbm=True):
    p = Program("gemver")
    A = p.input("A", (n, n))
    u1, v1 = p.input("u1", (n,)), p.input("v1", (n,))
    u2, v2 = p.input("u2", (n,)), p.input("v2", (n,))
    yv, zv = p.input("y", (n,)), p.input("z", (n,))
    B1 = blas.ger(A, u1, v1)
    B2 = blas.ger(B1, u2, v2)
    # x = beta * B^T y + z
    x = blas.gemv(B2, yv, y0=zv, trans=True, alpha=0.9, beta=1.0)
    if manual_replication:
        # fork the second GER's output: one replica streams into the
        # transposed GEMV; the other feeds the row-major GEMV
        # (paper §4.2 'manually replicate C following expansion').
        # replica_in_hbm=True keeps that replica off-chip exactly as the
        # paper does (3 GiB); False lets StreamingComposition stream BOTH
        # replicas (beyond-paper: 1 GiB kernel volume).
        st = p.state
        rep = p.temp(B2.shape, B2.dtype, name="B2_rep")
        producer_edge = st.in_edges(B2.node)[0]
        rep_node = st.add_access(rep.name)
        st.add_edge(producer_edge.src, producer_edge.src_conn, rep_node,
                    None, Memlet.simple(rep.name))
        from repro.frontends.api import TensorHandle
        B2b = TensorHandle(p, rep.name, B2.shape, B2.dtype, node=rep_node)
        w = blas.gemv(B2b, x, alpha=1.1)
        if replica_in_hbm:
            # pin the replica off-chip: composition must not stream it
            p.sdfg.metadata["pin_hbm"] = {rep.name}
    else:
        w = blas.gemv(B2, x, alpha=1.1)
    p.output("x_out", x)
    p.output("w_out", w)
    return p.finalize()


def build_chain(n):
    """The fused-DAG ladder rung: B = A + u1 v1^T + u2 v2^T ; w = alpha*B x.
    With the elementwise-exact ``accumulate`` gemv expansion the whole
    ger->ger->gemv chain is one iteration space and MapFusion collapses it
    into ONE grid kernel (B1 and B2 never leave the kernel)."""
    p = Program("gemver_chain")
    A = p.input("A", (n, n))
    u1, v1 = p.input("u1", (n,)), p.input("v1", (n,))
    u2, v2 = p.input("u2", (n,)), p.input("v2", (n,))
    xv = p.input("xw", (n,))
    B1 = blas.ger(A, u1, v1)
    B2 = blas.ger(B1, u2, v2)
    p.output("w_out", blas.gemv(B2, xv, alpha=1.1))
    return p.finalize()


def reference(n, d):
    B = d["A"] + np.outer(d["u1"], d["v1"]) + np.outer(d["u2"], d["v2"])
    x = 0.9 * B.T @ d["y"] + d["z"]
    w = 1.1 * B @ x
    return x, w


def _variants(n):
    out = {}
    s = build(n)
    s.apply(DeviceOffload)
    out["naive"] = s
    s2 = build(n)
    s2.apply(DeviceOffload)
    s2.apply(StreamingComposition)
    out["streaming"] = s2
    s3 = build(n, manual_replication=True, replica_in_hbm=True)
    s3.apply(DeviceOffload)
    s3.apply(StreamingComposition)
    out["manual"] = s3
    # beyond-paper: both replicas stream (kernel volume -> 1 matrix pass)
    s4 = build(n, manual_replication=True, replica_in_hbm=False)
    s4.apply(DeviceOffload)
    s4.apply(StreamingComposition)
    out["both_streamed"] = s4
    return out


def _kernel_volume(sdfg):
    """Kernel-state volume only (the paper's Table-2 column excludes the
    host<->device staging copies)."""
    main = [st for st in sdfg.states if st.label == "main"][0]
    return main.off_chip_volume()


def run(report, small: bool = False):
    rng = np.random.default_rng(0)
    n = 256 if small else BENCH_N
    d = {k: rng.standard_normal((n, n) if k == "A" else n
                                ).astype(np.float32)
         for k in ("A", "u1", "v1", "u2", "v2", "y", "z")}
    x_ref, w_ref = reference(n, d)

    vols = {name: _kernel_volume(s) for name, s in
            _variants(PAPER_N).items()}
    times = {}
    for name, s in _variants(n).items():
        c = lower(s).compile("jnp")
        c(**d)  # compile
        t0 = time.perf_counter()
        out = c(**d)
        times[name] = time.perf_counter() - t0
        np.testing.assert_allclose(np.asarray(out["x_out"]), x_ref,
                                   rtol=5e-2, atol=5e-1)
        np.testing.assert_allclose(np.asarray(out["w_out"]), w_ref,
                                   rtol=5e-2, atol=5e-1)

    paper = {"naive": "6.0", "streaming": "4.0", "manual": "3.0",
             "both_streamed": "(beyond-paper)"}
    for name in ("naive", "streaming", "manual", "both_streamed"):
        report(f"gemver_{name}_volume_GiB", vols[name] / 2**30,
               f"paper table2 {paper[name]} GiB; "
               f"ratio {vols['naive']/vols[name]:.2f}x")
        report(f"gemver_{name}_ms", times[name] * 1e3, f"n={n} CPU")

    # native grid path: unfused kernel ladder vs MapFusion'd rank-1 pair
    gn = 64 if small else GRID_N
    gd = {k: rng.standard_normal((gn, gn) if k == "A" else gn
                                 ).astype(np.float32)
          for k in ("A", "u1", "v1", "u2", "v2", "y", "z")}
    gx_ref, gw_ref = reference(gn, gd)

    grid_times, kernels, blocks = {}, {}, {}
    for name, fused, tiled in (("unfused", False, True),
                               ("fused", True, True),
                               ("untiled", True, False)):
        c = lower(build(gn)).compile(
            "pallas", pipeline=_grid_pipeline(fused, tiled))
        c(**gd)  # compile
        t0 = time.perf_counter()
        out = c(**gd)
        np.asarray(out["w_out"])
        grid_times[name] = time.perf_counter() - t0
        kernels[name] = c.report["grid_kernels"]
        blocks[name] = [e["block_shape"] for e in c.report["grid_converted"]]
        np.testing.assert_allclose(np.asarray(out["x_out"]), gx_ref,
                                   rtol=5e-2, atol=5e-1)
        np.testing.assert_allclose(np.asarray(out["w_out"]), gw_ref,
                                   rtol=5e-2, atol=5e-1)
    assert len(kernels["unfused"]) == 4 and len(kernels["fused"]) == 3

    report("gemver_grid_unfused_ms", grid_times["unfused"] * 1e3,
           f"n={gn}; kernels={kernels['unfused']}", backend="pallas")
    report("gemver_grid_fused_ms", grid_times["fused"] * 1e3,
           f"n={gn}; ger pair fused, B1 in-kernel, blocks="
           f"{blocks['fused'][0]}; speedup "
           f"{grid_times['unfused']/grid_times['fused']:.2f}x vs unfused",
           backend="pallas", block_shape=blocks["fused"][0])
    report("gemver_grid_untiled_ms", grid_times["untiled"] * 1e3,
           f"n={gn}; fused but 1-element blocks {blocks['untiled'][0]}; "
           f"tiled speedup "
           f"{grid_times['untiled']/grid_times['fused']:.2f}x",
           backend="pallas")
    assert grid_times["fused"] < grid_times["untiled"], \
        "tiled grid variant must beat the 1-element-block grid variant"

    # fused-DAG chain: ger->ger->gemv as ONE grid kernel (accumulate gemv)
    # vs the pairwise-fused baseline (ger pair fused, row-streaming gemv
    # as its own kernel, B2 round-tripping through HBM between them).
    # Sized where the avoided n^2 round-trip dominates: below ~256 the
    # pairwise row-gemv block is too cheap for the fusion win to show.
    cn = 384
    cd = {k: rng.standard_normal((cn, cn) if k == "A" else cn
                                 ).astype(np.float32)
          for k in ("A", "u1", "v1", "u2", "v2", "xw")}
    B = cd["A"] + np.outer(cd["u1"], cd["v1"]) + np.outer(cd["u2"], cd["v2"])
    w_ref = 1.1 * B @ cd["xw"]
    chain_times, chain_kernels = {}, {}
    reps = 5  # this pair feeds a hard CI comparison gate: average it
    for name, pref in (("dag", ("accumulate", "generic")),
                       ("pairwise", ("generic",))):
        c = lower(build_chain(cn)).compile(
            "pallas", pipeline=_chain_pipeline(name, pref))
        c(**cd)  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = c(**cd)
            np.asarray(out["w_out"])
        chain_times[name] = (time.perf_counter() - t0) / reps
        chain_kernels[name] = c.report["grid_kernels"]
        np.testing.assert_allclose(np.asarray(out["w_out"]), w_ref,
                                   rtol=5e-2, atol=5e-1)
    assert len(chain_kernels["dag"]) == 1, \
        f"chain must fuse to ONE grid kernel, got {chain_kernels['dag']}"
    assert len(chain_kernels["pairwise"]) >= 2
    report("gemver_chain_dag_ms", chain_times["dag"] * 1e3,
           f"n={cn}; ger->ger->gemv as ONE kernel "
           f"{chain_kernels['dag']}; speedup "
           f"{chain_times['pairwise']/chain_times['dag']:.2f}x vs pairwise",
           backend="pallas", grid_kernels=len(chain_kernels["dag"]))
    report("gemver_chain_pairwise_ms", chain_times["pairwise"] * 1e3,
           f"n={cn}; pairwise-fused baseline, kernels="
           f"{chain_kernels['pairwise']}", backend="pallas",
           grid_kernels=len(chain_kernels["pairwise"]))


def _grid_pipeline(fused: bool, tiled: bool = True,
                   tile_size: int = None) -> PassManager:
    passes = [SetExpansionPreferencePass(("generic",)),
              ExpandLibraryNodesPass()]
    if fused:
        passes.append(MapFusionPass())
    if tiled:
        defaults = GridConversionPass.default_tiles("pallas")
        passes.append(MapTilingPass(tile_size=tile_size)
                      if tile_size else
                      MapTilingPass(tile_size=defaults.get("minor"),
                                    second_size=defaults.get("second")))
    passes.append(GridConversionPass())
    return PassManager(passes, name=f"grid_f{int(fused)}_t{int(tiled)}"
                                    f"_{tile_size or 'auto'}")


def _chain_pipeline(name: str, pref) -> PassManager:
    defaults = GridConversionPass.default_tiles("pallas")
    return PassManager([
        SetExpansionPreferencePass(tuple(pref)),
        ExpandLibraryNodesPass(),
        MapFusionPass(),
        MapTilingPass(tile_size=defaults.get("minor"),
                      second_size=defaults.get("second")),
        GridConversionPass(),
    ], name=f"chain_{name}")


def calibrate(report, small: bool = False):
    """Sweep the minor (lane) tile size for the fused grid ladder on the
    current backend and record the measured winner — the numbers the
    GridConversion cost model's static thresholds should be tuned to."""
    rng = np.random.default_rng(1)
    gn = 64 if small else GRID_N
    gd = {k: rng.standard_normal((gn, gn) if k == "A" else gn
                                 ).astype(np.float32)
          for k in ("A", "u1", "v1", "u2", "v2", "y", "z")}
    best, times = None, {}
    for t in (8, 16, 32, 64, 128):
        if t > gn:
            continue
        c = lower(build(gn)).compile(
            "pallas", pipeline=_grid_pipeline(True, True, tile_size=t))
        c(**gd)  # compile
        t0 = time.perf_counter()
        out = c(**gd)
        np.asarray(out["w_out"])
        times[t] = time.perf_counter() - t0
        blk = c.report["grid_converted"][0]["block_shape"]
        report(f"gemver_calibrate_tile{t}_ms", times[t] * 1e3,
               f"n={gn}; fused grid, minor tile {t}, blocks {blk}",
               backend="pallas")
        if best is None or times[t] < times[best]:
            best = t
    report("gemver_calibrate_best_tile", best,
           f"n={gn}; measured crossover of the minor-tile sweep "
           f"{sorted(times)}", backend="pallas")
