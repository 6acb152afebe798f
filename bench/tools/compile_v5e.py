"""Compile the serving cells' largest decode step for a described TPU v5e.

Nothing runs: the chip's compiler builds the whole 30-layer step at its
largest bucket from shapes alone, and ``memory_analysis()`` gives its
static accounting. That tells, before any chip time is spent, whether
the step fits beside the two weight copies the scheduler keeps. Run on
a machine without a chip::

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/tools/compile_v5e.py \
        --config starcoder2-3b --batch 32 --ctx 512
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="starcoder2-3b")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--ctx", type=int, default=512)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import serving
    from repro.codegen import device
    from repro.serving.compile import decode_pipeline, serving_decode_step

    jax.config.update("jax_enable_compilation_cache", False)
    device.default_interpret = lambda: False
    cfg = serving.load_config(args.config)
    model = serving.build_model(cfg)
    sv = cfg["serving"]
    ps = sv["page_size"]
    n_pages = serving.n_pages(cfg)
    from repro.serving.compile import flatten_params
    tree = jax.eval_shape(
        lambda: serving.model_file(cfg).init_params(cfg, serving.seed_key(0)))
    flat = jax.eval_shape(lambda t: flatten_params(model, t), tree)
    wspecs = {n: (tuple(a.shape), str(a.dtype)) for n, a in flat.items()}
    B, ctx = args.batch, args.ctx
    t0 = time.perf_counter()
    compiled = serving_decode_step.lower(
        model=model, wspecs=wspecs, B=B, ctx=ctx, page_size=ps,
        n_pages=n_pages, cache_dtype=sv["cache_dtype"]).compile(
        backend="pallas", interpret=False,
        pipeline=decode_pipeline(interpret=False), cache=None)
    t1 = time.perf_counter()
    print(f"SDFG passes {t1 - t0:.1f} s", file=sys.stderr, flush=True)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    hkv, dh = model.cfg.n_kv_heads, model.cfg.head_dim
    specs = {"tokens": ((B, 1), "int32"), "positions": ((B,), "int32"),
             "block_table": ((B, ctx // ps), "int32"), **wspecs}
    for li in range(model.cfg.n_layers):
        for k in ("kp", "vp"):
            specs[f"{k}{li}"] = ((n_pages, ps, hkv, dh), sv["cache_dtype"])
    shapes = {n: jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one)
              for n, (s, d) in specs.items()}
    lowered = compiled.lower(**shapes)
    print(f"lowered {time.perf_counter() - t1:.1f} s", file=sys.stderr,
          flush=True)
    exe = lowered.compile()
    t2 = time.perf_counter()
    ma = exe.memory_analysis()
    text = exe.as_text()
    out = {"config": args.config, "B": B, "ctx": ctx, "n_pages": n_pages,
           "grid_kernels": len(compiled.report["grid_kernels"]),
           "grid_fallbacks": list(compiled.report["grid_fallbacks"]),
           "tpu_custom_calls": text.count("tpu_custom_call"),
           "sdfg_passes_s": t1 - t0, "xla_compile_s": t2 - t1}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        out[k] = getattr(ma, k, None)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
