"""Sliding-window stencil Pallas kernels — the paper's §6.2 Xilinx
shift-register emulation, adapted to the TPU memory hierarchy.

Intel OpenCL gives StencilFlow a shift register holding the stencil
wavefront; Vivado HLS does not, so the paper derives explicit cyclic
buffers per access offset. The TPU has neither construct: the adaptation
(DESIGN.md §2) keeps a **halo'd row slab resident in VMEM** per grid step.
Each grid step owns one row-tile of the output and reads an overlapping
(tile + 2*halo) slab of the pre-padded input, expressed with an
element-indexed BlockSpec (``pl.Element``) — the buffers between access
points become VMEM rows, and the wavefront advances tile-by-tile down the
grid, double-buffered by the Pallas pipeline exactly like the FPGA reader
PEs feed the shift register.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...codegen.device import resolve_interpret


def _pick_tile(n: int, target: int) -> int:
    t = min(target, n)
    while n % t != 0:
        t -= 1
    return t


def _element_block_spec(shape, index_map) -> pl.BlockSpec:
    """Element-indexed BlockSpec: ``index_map`` returns element offsets,
    which the overlapping halo'd slabs need (slab height is not a
    multiple of the tile stride)."""
    return pl.BlockSpec(tuple(pl.Element(s) for s in shape), index_map)


# ---------------------------------------------------------------------------
# Generic 2D stencil: static offsets, runtime coeffs (SMEM)
# ---------------------------------------------------------------------------
def _stencil2d_kernel(c_ref, a_ref, o_ref, *, offsets, radius):
    slab = a_ref[...].astype(jnp.float32)
    bh = o_ref.shape[0]
    W = o_ref.shape[1]
    out = jnp.zeros((bh, W), jnp.float32)
    r = radius
    for k, (di, dj) in enumerate(offsets):
        out += c_ref[k] * jax.lax.slice(
            slab, (r + di, r + dj), (r + di + bh, r + dj + W))
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("offsets", "bh", "interpret"))
def stencil2d(a, coeffs, offsets, bh: int = 256,
              interpret: Optional[bool] = None):
    """out[p] = sum_k c_k * a[p + offsets_k], constant-0 boundary."""
    H, W = a.shape
    bh = _pick_tile(H, bh)
    r = max(max(abs(di), abs(dj)) for di, dj in offsets)
    p = jnp.pad(a, r)
    coeffs = jnp.asarray(coeffs, jnp.float32)
    return pl.pallas_call(
        functools.partial(_stencil2d_kernel, offsets=tuple(offsets), radius=r),
        grid=(H // bh,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _element_block_spec((bh + 2 * r, W + 2 * r),
                                lambda i: (i * bh, 0)),
        ],
        out_specs=pl.BlockSpec((bh, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), a.dtype),
        interpret=resolve_interpret(interpret),
    )(coeffs, p)


# ---------------------------------------------------------------------------
# Fused multi-stage 2D stencil chain (paper §6: fully pipelined multi-stencil
# architectures). All stages execute on one VMEM-resident slab per grid step;
# intermediates never touch HBM — the delay buffers of StencilFlow become
# shrinking VMEM halos. Inter-stage boundary conditions are enforced by
# masking positions outside the global domain to the constant-0 boundary.
# ---------------------------------------------------------------------------
def _stencil2d_chain_kernel(c_ref, a_ref, o_ref, *, stages, radii, H, W, bh):
    R = sum(radii)
    i = pl.program_id(0)
    cur = a_ref[...].astype(jnp.float32)  # halo R slab of padded input
    h = R
    coeff_base = 0
    for s, (offsets, n_coeff) in enumerate(stages):
        r = radii[s]
        h_new = h - r
        size_u = bh + 2 * h_new
        size_v = W + 2 * h_new
        out = jnp.zeros((size_u, size_v), jnp.float32)
        for k, (di, dj) in enumerate(offsets):
            out += c_ref[coeff_base + k] * jax.lax.slice(
                cur, (r + di, r + dj), (r + di + size_u, r + dj + size_v))
        coeff_base += n_coeff
        if s < len(stages) - 1:
            # constant-0 boundary for the *next* stage's input: zero
            # positions outside the global domain
            row0 = i * bh - h_new
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32,
                                                   (size_u, size_v), 0)
            cols = -h_new + jax.lax.broadcasted_iota(jnp.int32,
                                                     (size_u, size_v), 1)
            inside = ((rows >= 0) & (rows < H) & (cols >= 0) & (cols < W))
            out = jnp.where(inside, out, 0.0)
        cur = out
        h = h_new
    o_ref[...] = cur.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("offsets_per_stage", "bh",
                                             "interpret"))
def stencil2d_chain(a, coeffs_per_stage, offsets_per_stage, bh: int = 256,
                    interpret: Optional[bool] = None):
    """Apply consecutive stencil stages in one fused kernel.

    offsets_per_stage: tuple of tuples of (di, dj); coeffs_per_stage: list of
    coefficient arrays, concatenated into one SMEM vector.
    """
    H, W = a.shape
    bh = _pick_tile(H, bh)
    radii = tuple(max(max(abs(di), abs(dj)) for di, dj in offs)
                  for offs in offsets_per_stage)
    R = sum(radii)
    p = jnp.pad(a, R)
    coeffs = jnp.concatenate([jnp.asarray(c, jnp.float32).reshape(-1)
                              for c in coeffs_per_stage])
    stages = tuple((tuple(offs), len(offs)) for offs in offsets_per_stage)
    return pl.pallas_call(
        functools.partial(_stencil2d_chain_kernel, stages=stages,
                          radii=radii, H=H, W=W, bh=bh),
        grid=(H // bh,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _element_block_spec((bh + 2 * R, W + 2 * R),
                                lambda i: (i * bh, 0)),
        ],
        out_specs=pl.BlockSpec((bh, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), a.dtype),
        interpret=resolve_interpret(interpret),
    )(coeffs, p)


# ---------------------------------------------------------------------------
# diffusion 2D (paper Fig. 17): 5-point stencil, constant-0 boundary
# ---------------------------------------------------------------------------
def _diffusion2d_kernel(c_ref, a_ref, o_ref):
    c0, c1, c2, c3, c4 = (c_ref[k] for k in range(5))
    slab = a_ref[...].astype(jnp.float32)
    out = (c0 * slab[1:-1, 1:-1] + c1 * slab[:-2, 1:-1]
           + c2 * slab[2:, 1:-1] + c3 * slab[1:-1, :-2]
           + c4 * slab[1:-1, 2:])
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def diffusion2d(a, coeffs, bh: int = 256, interpret: Optional[bool] = None):
    H, W = a.shape
    bh = _pick_tile(H, bh)
    p = jnp.pad(a, 1)  # constant-0 boundary
    coeffs = jnp.asarray(coeffs, jnp.float32)
    return pl.pallas_call(
        _diffusion2d_kernel,
        grid=(H // bh,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _element_block_spec((bh + 2, W + 2),
                                lambda i: (i * bh, 0)),
        ],
        out_specs=pl.BlockSpec((bh, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), a.dtype),
        interpret=resolve_interpret(interpret),
    )(coeffs, p)


# ---------------------------------------------------------------------------
# Jacobi 3D: 7-point stencil over (D, H, W); tiles over the slowest axis
# ---------------------------------------------------------------------------
def _jacobi3d_kernel(a_ref, o_ref):
    slab = a_ref[...].astype(jnp.float32)
    c = jnp.float32(1.0 / 7.0)
    out = c * (slab[1:-1, 1:-1, 1:-1]
               + slab[:-2, 1:-1, 1:-1] + slab[2:, 1:-1, 1:-1]
               + slab[1:-1, :-2, 1:-1] + slab[1:-1, 2:, 1:-1]
               + slab[1:-1, 1:-1, :-2] + slab[1:-1, 1:-1, 2:])
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def jacobi3d(a, bd: int = 16, interpret: Optional[bool] = None):
    D, H, W = a.shape
    bd = _pick_tile(D, bd)
    p = jnp.pad(a, 1)
    return pl.pallas_call(
        _jacobi3d_kernel,
        grid=(D // bd,),
        in_specs=[_element_block_spec(
            (bd + 2, H + 2, W + 2),
            lambda i: (i * bd, 0, 0))],
        out_specs=pl.BlockSpec((bd, H, W), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((D, H, W), a.dtype),
        interpret=resolve_interpret(interpret),
    )(p)


# ---------------------------------------------------------------------------
# diffusion 3D: explicit laplacian step
# ---------------------------------------------------------------------------
def _diffusion3d_kernel(alpha_ref, a_ref, o_ref):
    alpha = alpha_ref[0]
    slab = a_ref[...].astype(jnp.float32)
    center = slab[1:-1, 1:-1, 1:-1]
    lap = (slab[:-2, 1:-1, 1:-1] + slab[2:, 1:-1, 1:-1]
           + slab[1:-1, :-2, 1:-1] + slab[1:-1, 2:, 1:-1]
           + slab[1:-1, 1:-1, :-2] + slab[1:-1, 1:-1, 2:]
           - 6.0 * center)
    o_ref[...] = (center + alpha * lap).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def diffusion3d(a, alpha: float = 0.1, bd: int = 16,
                interpret: Optional[bool] = None):
    D, H, W = a.shape
    bd = _pick_tile(D, bd)
    p = jnp.pad(a, 1)
    alpha_arr = jnp.asarray([alpha], jnp.float32)
    return pl.pallas_call(
        _diffusion3d_kernel,
        grid=(D // bd,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _element_block_spec(
                (bd + 2, H + 2, W + 2),
                lambda i: (i * bd, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bd, H, W), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((D, H, W), a.dtype),
        interpret=resolve_interpret(interpret),
    )(alpha_arr, p)
