"""Compile the main path's kernels for a TPU v5e that is described, not
attached, with the Pallas interpreter off.

The chip's compiler (Mosaic) refuses what interpret mode accepts: blocks
not aligned to the (8, 128) tiling, dynamic slices, more VMEM than a
kernel may use. These compiles catch that without a chip. Nothing runs,
so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels  # noqa: F401  (registers the fused Axpy+Dot)
from repro.codegen import device
from repro.pipeline import DeviceOffloadPass, StreamingCompositionPass, lower

#: (B, ctx) buckets of chip_smoke.py's serving step: full batch at the
#: largest context its traffic reaches and at its max_model_len, and the
#: one-row batch whose grid has a single step
SMOKE_BUCKETS = ((8, 256), (8, 512), (1, 256))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """Shapes are placed on one described chip; every ``interpret``
    default resolves as on that chip; the persistent compilation cache is
    off (its entries could not be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(device, "default_interpret", lambda: False)
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shapes(sharding, **specs):
    return {n: jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for n, (s, d) in specs.items()}


def _compile_for_chip(compiled, sharding, **specs):
    assert compiled.report["interpret"] is False
    exe = compiled.lower(**_shapes(sharding, **specs)).compile()
    assert "tpu_custom_call" in exe.as_text()
    return exe


@pytest.mark.parametrize("B,ctx", SMOKE_BUCKETS)
def test_serving_decode_step_published_widths(chip, B, ctx):
    """starcoder2-3b at its published widths, depth cut to 2 layers: every
    attention layer is a Mosaic kernel."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.compile import (decode_pipeline, flatten_params,
                                       serving_decode_step)
    cfg = dataclasses.replace(get_config("starcoder2-3b"), n_layers=2,
                              param_dtype="bfloat16")
    model = build_model(cfg)
    ps = 16
    n_pages = 8 * 512 // ps + 4
    flat = jax.eval_shape(lambda: flatten_params(
        model, model.init(jax.random.PRNGKey(0))))
    wspecs = {n: (tuple(a.shape), str(a.dtype)) for n, a in flat.items()}
    compiled = serving_decode_step.lower(
        model=model, wspecs=wspecs, B=B, ctx=ctx, page_size=ps,
        n_pages=n_pages, cache_dtype="bfloat16").compile(
        backend="pallas", interpret=False,
        pipeline=decode_pipeline(interpret=False), cache=None)
    assert len(compiled.report["grid_kernels"]) == cfg.n_layers
    assert not compiled.report["grid_fallbacks"]
    pages = ((n_pages, ps, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    _compile_for_chip(
        compiled, chip, tokens=((B, 1), jnp.int32),
        positions=((B,), jnp.int32), block_table=((B, ctx // ps), jnp.int32),
        **wspecs, **{f"{k}{li}": pages for li in range(cfg.n_layers)
                     for k in ("kp", "vp")})


def test_jacobi_chain_one_kernel(chip):
    from benchmarks.jacobi_chain import N, _chain_sdfg
    compiled = lower(_chain_sdfg(N)).compile("pallas", interpret=False,
                                             cache=None)
    assert len(compiled.report["grid_kernels"]) == 1
    _compile_for_chip(compiled, chip, a=((N,), jnp.float32))


def test_axpydot_streamed_kernel(chip):
    from benchmarks.axpydot import build
    n = 1 << 26  # chip_smoke.py's size
    compiled = lower(build(n)).optimize(
        [DeviceOffloadPass(), StreamingCompositionPass()]).compile(
        "pallas", interpret=False, cache=None)
    assert compiled.report["fused_regions"] == ["Axpy+Dot"]
    f32 = jnp.float32
    _compile_for_chip(compiled, chip, a=((), f32), x=((n,), f32),
                      y=((n,), f32), w=((n,), f32))


def test_axpydot_paper_size_streams_without_copies(chip):
    """At the paper's n the lane-dense (n / 128, 128) view of x, y and w is
    a bitcast: one kernel, and no copy or relayout of the inputs, which
    would double the HBM traffic."""
    import math
    import re

    from benchmarks.axpydot import build
    n = 209_715_200
    compiled = lower(build(n)).optimize(
        [DeviceOffloadPass(), StreamingCompositionPass()]).compile(
        "pallas", interpret=False, cache=None)
    f32 = jnp.float32
    exe = _compile_for_chip(compiled, chip, a=((), f32), x=((n,), f32),
                            y=((n,), f32), w=((n,), f32))
    hlo = exe.as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1
    # every value as large as an input is an input or a view of one
    inst = re.compile(r"= \w+\[([\d,]+)\]\S* ([\w-]+)\(")
    for m in inst.finditer(hlo):
        if math.prod(int(d) for d in m.group(1).split(",")) >= n:
            assert m.group(2) in ("parameter", "bitcast"), m.group(0)
    assert exe.memory_analysis().temp_size_in_bytes < n


def test_gemver_chain_one_kernel(chip):
    from benchmarks.gemver import _chain_pipeline, build_chain
    n = 384
    compiled = lower(build_chain(n)).compile(
        "pallas", interpret=False, cache=None,
        pipeline=_chain_pipeline("dag", ("accumulate", "generic")))
    assert len(compiled.report["grid_kernels"]) == 1
    f32 = jnp.float32
    _compile_for_chip(compiled, chip, A=((n, n), f32), u1=((n,), f32),
                      v1=((n,), f32), u2=((n,), f32), v2=((n,), f32),
                      xw=((n,), f32))
