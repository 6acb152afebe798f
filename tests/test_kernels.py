"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + property tests.
All kernels run in interpret mode (CPU) per the assignment."""
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the optional 'hypothesis' "
    "package (pip install repro[test])")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax.numpy as jnp  # noqa: E402
from repro.kernels import axpydot, dot, gemm, stencil  # noqa: E402

RNG = np.random.default_rng(42)


# -- axpydot ---------------------------------------------------------------
# 1024-16384: one grid step; 3 << 20: several full (R, 128) blocks;
# 1053696: a masked last block; 1054000: padded to the tiling, then masked
@pytest.mark.parametrize("n", [1024, 4096, 5000, 16384, 3 << 20, 1053696,
                               1054000])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_axpydot_sweep(n, dtype):
    if dtype == "bfloat16":
        import ml_dtypes
        dtype = ml_dtypes.bfloat16
    a = np.float32(1.3)
    x, y, w = (RNG.standard_normal(n).astype(dtype) for _ in range(3))
    out = axpydot.axpydot(a, x, y, w, interpret=True)
    ref = axpydot.axpydot_ref(a, x, y, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-3 if dtype != np.float32 else 3e-5)


@pytest.mark.parametrize("n,itemsize,steps", [
    (209_715_200, 4, 800),   # the paper's size in float32
    (209_715_200, 2, 400),
    (3 << 20, 4, 12),
    (16384, 4, 1),
    (16384, 2, 1),
    (1024, 4, 1),
])
def test_axpydot_block_rule(n, itemsize, steps):
    from repro.kernels.axpydot import kernel
    assert kernel.sublanes(itemsize) == {4: 8, 2: 16}[itemsize]
    rows = n // kernel.LANES
    block = kernel.block_rows(rows, itemsize)
    assert block % kernel.sublanes(itemsize) == 0
    assert -(-rows // block) == steps <= 1000
    # three inputs, double-buffered, within the default scoped VMEM
    assert 3 * 2 * block * kernel.LANES * itemsize <= kernel.VMEM_LIMIT


# -- dot ---------------------------------------------------------------------
@pytest.mark.parametrize("n", [1024, 2048, 9973])
def test_dot_sweep(n):
    x, w = (RNG.standard_normal(n).astype(np.float32) for _ in range(2))
    np.testing.assert_allclose(np.asarray(dot.dot(x, w, interpret=True)),
                               np.asarray(dot.dot_ref(x, w)), rtol=3e-5)


# -- gemm ---------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 512, 128),
                                   (300, 200, 150), (64, 1000, 32)])
@pytest.mark.parametrize("act", [None, "relu", "gelu"])
def test_gemm_sweep(shape, act):
    M, K, N = shape
    A = RNG.standard_normal((M, K)).astype(np.float32)
    B = RNG.standard_normal((K, N)).astype(np.float32)
    bias = RNG.standard_normal(N).astype(np.float32)
    out = gemm.matmul(A, B, bias, activation=act, bm=128, bk=128, bn=128,
                      interpret=True)
    ref = gemm.matmul_ref(A, B, bias, activation=act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def test_gemm_bf16():
    import ml_dtypes
    A = RNG.standard_normal((128, 256)).astype(ml_dtypes.bfloat16)
    B = RNG.standard_normal((256, 128)).astype(ml_dtypes.bfloat16)
    out = gemm.matmul(A, B, interpret=True)
    ref = gemm.matmul_ref(A, B)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=3e-2,
                               atol=3e-2)


@given(m=st.integers(8, 160), k=st.integers(8, 160), n=st.integers(8, 160))
@settings(max_examples=12, deadline=None)
def test_gemm_property_shapes(m, k, n):
    A = RNG.standard_normal((m, k)).astype(np.float32)
    B = RNG.standard_normal((k, n)).astype(np.float32)
    out = gemm.matmul(A, B, bm=64, bk=64, bn=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(gemm.matmul_ref(A, B)),
                               rtol=2e-4, atol=2e-4)


# -- stencils ------------------------------------------------------------------
@pytest.mark.parametrize("hw", [(64, 48), (128, 128), (65, 33)])
def test_diffusion2d(hw):
    a = RNG.standard_normal(hw).astype(np.float32)
    co = np.array([0.2, 0.1, 0.15, 0.25, 0.3], np.float32)
    np.testing.assert_allclose(
        np.asarray(stencil.diffusion2d(a, co, bh=16, interpret=True)),
        np.asarray(stencil.diffusion2d_ref(a, co)), rtol=1e-5, atol=1e-6)


def test_jacobi3d_and_diffusion3d():
    a = RNG.standard_normal((16, 12, 10)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(stencil.jacobi3d(a, bd=4, interpret=True)),
        np.asarray(stencil.jacobi3d_ref(a)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(stencil.diffusion3d(a, 0.1, bd=4, interpret=True)),
        np.asarray(stencil.diffusion3d_ref(a, 0.1)), rtol=1e-5, atol=1e-5)


@given(di=st.integers(-2, 2), dj=st.integers(-2, 2))
@settings(max_examples=10, deadline=None)
def test_stencil2d_arbitrary_offsets(di, dj):
    offsets = ((0, 0), (di, dj))
    a = RNG.standard_normal((32, 24)).astype(np.float32)
    co = np.array([0.5, 0.25], np.float32)
    np.testing.assert_allclose(
        np.asarray(stencil.stencil2d(a, co, offsets, bh=8, interpret=True)),
        np.asarray(stencil.stencil2d_ref(a, co, offsets)),
        rtol=1e-5, atol=1e-6)


def test_stencil_chain_matches_sequential():
    offs = ((0, 0), (-1, 0), (1, 0), (0, -1), (0, 1))
    a = RNG.standard_normal((48, 40)).astype(np.float32)
    c1 = np.array([0.2, 0.1, 0.15, 0.25, 0.3], np.float32)
    c2 = np.array([0.1, 0.2, 0.3, 0.2, 0.2], np.float32)
    fused = stencil.stencil2d_chain(a, [c1, c2], (offs, offs), bh=16,
                                    interpret=True)
    seq = stencil.stencil2d_ref(stencil.stencil2d_ref(a, c1, offs), c2, offs)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(seq),
                               rtol=1e-4, atol=1e-5)
