"""The one interpret decision and the compile-cache placement."""
import jax
import numpy as np
import pytest

from repro.codegen import device
from repro.pipeline import lower


def test_interpret_follows_the_device_unless_explicit():
    assert device.default_interpret() == (jax.default_backend() != "tpu")
    assert device.resolve_interpret(None) == device.default_interpret()
    assert device.resolve_interpret(False) is False
    assert device.resolve_interpret(True) is True


def test_compile_reports_and_keys_the_resolved_interpret():
    """Interpreted and compiled builds of one SDFG never share a cache
    entry, and the report says which one a Compiled is."""
    from benchmarks.jacobi_chain import N_SMALL, _chain_sdfg, _reference
    from repro.pipeline.cache import CompilationCache
    cache = CompilationCache()
    low = lower(_chain_sdfg(N_SMALL))
    c = low.compile("pallas", cache=cache)
    assert c.report["interpret"] is device.default_interpret()
    other = low.compile("pallas", interpret=not c.report["interpret"],
                        cache=cache)
    assert other is not c and other.cache_key != c.cache_key
    assert other.report["interpret"] is not c.report["interpret"]
    a = np.random.default_rng(0).standard_normal(N_SMALL).astype(np.float32)
    np.testing.assert_allclose(np.asarray(c(a=a)["b"]), _reference(a),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, placed):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
    without it the cache goes to the checkout's ``.jax_cache``."""
    before = jax.config.jax_compilation_cache_dir
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = device.enable_compile_cache()
        if placed:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(device.CHECKOUT_CACHE_DIR)
            assert device.CHECKOUT_CACHE_DIR.parent.joinpath(
                "chip_smoke.py").exists()
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
