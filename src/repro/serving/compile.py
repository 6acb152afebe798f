"""The compiled serving decode step (ROADMAP: serve-heavy-traffic).

One whole decode step — embed, every layer's attention/SSM + FFN over the
paged KV cache, final norm + logits — is built as a single ``@dc_program``
SDFG and lowered through ``default_pipeline("pallas")``. The attention of
each layer enters the graph as a :class:`~repro.library.PagedAttnDecode`
Library Node whose ``pallas`` expansion is a (b, h) mapped tasklet, so
MapTiling + GridConversion turn it into a batched Pallas grid kernel
inside the compiled step (it shows up in ``Compiled.report``'s
``grid_kernels``). Everything around it — QKV projection + RoPE, the
paged KV write, the page gather, FFN/MoE, RWKV/Mamba state updates — are
jnp tasklets replicating ``models.blocks`` decode math exactly, so the
compiled step matches ``TransformerLM.decode_step`` token for token.

Shape bucketing: the step is specialized on ``(B, ctx)`` — the padded
batch bucket and the context bucket (a multiple of the page size covering
the longest live sequence). Each bucket is one SDFG whose content hash
keys the process-wide ``COMPILATION_CACHE``; re-entering a bucket is a
cache hit, no re-lowering. Padding lanes ride along: their block-table
rows are zero, so their KV writes land on the pool's null page and their
attention reads garbage that the ``j <= pos`` mask never admits.

Why this beats ``jax.jit(model.decode_step)``: the baseline attends over
the full dense ``max_model_len`` cache every step and re-threads the
whole (B, Smax, Hkv, Dh) cache through the jit boundary; the compiled
step attends over the (much smaller) live context bucket, gathers only
the pages the block table names, and donates the page/state buffers.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..codegen.device import resolve_interpret
from ..core.memlet import Memlet
from ..frontends.api import Program, TensorHandle, dc_program
from ..library import PagedAttnDecode
from ..models import blocks
from ..models.layers import apply_rope, layer_norm, rms_norm
from ..pipeline.cache import COMPILATION_CACHE, CompilationCache
from ..pipeline.passes import (ExpandLibraryNodesPass, GridConversionPass,
                               MapFusionPass, MapTilingPass, PassManager,
                               PipelineFusionPass, SetExpansionPreferencePass,
                               VectorizationPass, default_pipeline)


# ---------------------------------------------------------------------------
# Model introspection: flat layer order, weight/state naming
# ---------------------------------------------------------------------------
def flat_layer_specs(model) -> List:
    """Layer specs in execution order: period scan unrolled, then tail."""
    specs = []
    for _ in range(model.n_periods):
        specs.extend(model.period_specs)
    specs.extend(model.tail_specs)
    return specs


def attention_layer_shapes(model) -> Dict[int, Tuple[int, int]]:
    """flat layer index -> (n_kv_heads, head_dim) for every attn layer."""
    cfg = model.cfg
    return {li: (cfg.n_kv_heads, cfg.head_dim)
            for li, spec in enumerate(flat_layer_specs(model))
            if spec.kind == "attn"}


def flatten_params(model, params, place=None) -> Dict[str, jnp.ndarray]:
    """Stacked tree -> flat ``L{li}__{group}__{key}`` arrays (+ head/embed).

    Iteration order is deterministic (periods outer, positions inner,
    matching the scan's execution order), so two flattenings of the same
    model produce identical container orders and the built SDFGs
    content-hash equal. ``place`` (e.g. a ``device_put`` onto a mesh) is
    applied to each array as it is sliced out, so at most one unplaced
    layer slice is alive at a time.
    """
    place = place or (lambda a: a)
    out: Dict[str, jnp.ndarray] = {"embed": place(params["embed"])}
    li = 0
    for pp in range(model.n_periods):
        for pi in range(len(model.period_specs)):
            for gname, gdict in params["body"][pi].items():
                for k, a in gdict.items():
                    out[f"L{li}__{gname}__{k}"] = place(a[pp])
            li += 1
    for ti in range(len(model.tail_specs)):
        for gname, gdict in params["tail"][ti].items():
            for k, a in gdict.items():
                out[f"L{li}__{gname}__{k}"] = place(a)
        li += 1
    out["final_scale"] = place(params["final_scale"])
    if "final_bias" in params:
        out["final_bias"] = place(params["final_bias"])
    if not model.cfg.tie_embeddings:
        out["lm_head"] = place(params["lm_head"])
    return out


def state_specs(model) -> Dict[str, Tuple[int, Tuple[int, ...], str]]:
    """Per-slot recurrent-state rows for non-attention layers:
    ``st{li}__{key}`` -> (flat layer index, per-row shape, dtype)."""
    cfg = model.cfg
    out: Dict[str, Tuple[int, Tuple[int, ...], str]] = {}
    for li, spec in enumerate(flat_layer_specs(model)):
        if spec.kind == "rwkv":
            one = blocks.rwkv_cache_init(cfg, 1)
        elif spec.kind == "mamba":
            one = blocks.mamba_cache_init(cfg, 1)
        else:
            continue
        for key in sorted(one):
            a = one[key]
            out[f"st{li}__{key}"] = (li, tuple(a.shape[1:]), str(a.dtype))
    return out


# ---------------------------------------------------------------------------
# SDFG builder
# ---------------------------------------------------------------------------
def _tasklet(p: Program, label: str, ins: Dict[str, TensorHandle],
             outs: Dict[str, object], fn) -> Dict[str, TensorHandle]:
    """Wire one tasklet. ``outs`` values are either an existing handle (an
    in/out container — gets a fresh access-node version) or a
    ``(shape, dtype)`` tuple (a new transient)."""
    st = p.state
    t = st.add_tasklet(label, list(ins), list(outs), fn)
    for conn, h in ins.items():
        st.add_edge(h.read_node(), None, t, conn, Memlet.simple(h.name))
    res = {}
    for conn, spec in outs.items():
        if isinstance(spec, tuple):
            h = p.temp(spec[0], spec[1], name=f"{label}_{conn}")
        else:
            h = spec
        st.add_edge(t, conn, h.fresh_write_node(), None,
                    Memlet.simple(h.name))
        res[conn] = h
    return res


@dc_program
def serving_decode_step(p: Program, model=None, wspecs=None, B=None,
                        ctx=None, page_size=None, n_pages=None,
                        cache_dtype="bfloat16"):
    """One full decode step over the paged cache, specialized on (B, ctx).

    Inputs: tokens (B,1) i32, positions (B,) i32, block_table
    (B, ctx/page_size) i32, flat weights, per-attention-layer page arrays
    kp{li}/vp{li}, per-recurrent-layer state rows st{li}__*. Outputs:
    logits (B, V) plus the updated page/state containers (donated by the
    step wrapper).
    """
    cfg = model.cfg
    adt = cfg.activation_dtype
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    D = cfg.d_model
    ps = page_size
    n_bt = ctx // ps
    vocab_padded = model.vocab_padded
    specs = flat_layer_specs(model)
    sspecs = state_specs(model)

    tokens = p.input("tokens", (B, 1), "int32")
    positions = p.input("positions", (B,), "int32")
    bt = p.input("block_table", (B, n_bt), "int32")
    wh = {name: p.input(name, shape, dt)
          for name, (shape, dt) in wspecs.items()}
    kph, vph = {}, {}
    for li, spec in enumerate(specs):
        if spec.kind == "attn":
            shape = (n_pages, ps, Hkv, dh)
            kph[li] = p.input(f"kp{li}", shape, cache_dtype)
            vph[li] = p.input(f"vp{li}", shape, cache_dtype)
    sth = {name: p.input(name, (B,) + shape, dt)
           for name, (li, shape, dt) in sspecs.items()}

    def embed_fn(tokens, embed):
        return {"x": jnp.take(embed, tokens[:, 0], axis=0
                              ).astype(jnp.dtype(adt))}

    x = _tasklet(p, "embed", {"tokens": tokens, "embed": wh["embed"]},
                 {"x": ((B, D), adt)}, embed_fn)["x"]

    for li, spec in enumerate(specs):
        w = lambda g, k: wh[f"L{li}__{g}__{k}"]
        if spec.kind == "attn":
            x = _attn_layer(p, cfg, li, spec, x, positions, bt, w,
                            kph[li], vph[li], B, ctx, ps)
            x = _ffn_layer(p, cfg, li, spec, x, w, B, D)
        elif spec.kind == "mamba":
            x = _recurrent_layer(p, cfg, li, "mamba", blocks.mamba_apply,
                                 x, w, sth, sspecs, B, D)
            x = _ffn_layer(p, cfg, li, spec, x, w, B, D)
        elif spec.kind == "rwkv":
            x = _recurrent_layer(p, cfg, li, "rwkv", blocks.rwkv_apply,
                                 x, w, sth, sspecs, B, D)
        else:
            raise ValueError(f"unknown layer kind {spec.kind!r}")

    head_ins = {"x": x, "final_scale": wh["final_scale"]}
    if cfg.norm == "layernorm":
        head_ins["final_bias"] = wh["final_bias"]
    if cfg.tie_embeddings:
        head_ins["embed"] = wh["embed"]
    else:
        head_ins["lm_head"] = wh["lm_head"]

    def head_fn(x, final_scale, final_bias=None, embed=None, lm_head=None):
        xs = x[:, None, :]
        if cfg.norm == "rmsnorm":
            xs = rms_norm(xs, final_scale)
        else:
            xs = layer_norm(xs, final_scale + 1.0, final_bias)
        jadt = jnp.dtype(adt)
        head = embed.T if cfg.tie_embeddings else lm_head
        lg = jnp.einsum("bsd,dv->bsv", xs.astype(jadt), head.astype(jadt))
        if cfg.tie_embeddings:
            lg = lg * np.float32(1.0 / np.sqrt(cfg.d_model)
                                 ).astype(lg.dtype)
        if vocab_padded != cfg.vocab:
            pad = jnp.arange(vocab_padded) >= cfg.vocab
            lg = jnp.where(pad, jnp.asarray(-1e30, lg.dtype), lg)
        return {"logits": lg[:, 0]}

    lg = _tasklet(p, "head", head_ins,
                  {"logits": ((B, vocab_padded), adt)}, head_fn)["logits"]
    p.output("logits", lg)

    # Builder-declared partition hints for ShardMapPass (inert unless the
    # lowering pipeline actually shards): per-slot containers split on the
    # batch/slot dim, page arrays on the page dim (each host owns one
    # contiguous page block and the block table it receives is localized
    # to it), weights replicate. The tasklet closures above are all
    # batch-row-wise (``reshape(-1, ...)``), so they run unchanged on the
    # shard-local row blocks.
    declared = {"tokens": 0, "positions": 0, "block_table": 0, "logits": 0}
    declared.update({name: None for name in wspecs})
    for li in kph:
        declared[f"kp{li}"] = 0
        declared[f"vp{li}"] = 0
    declared.update({name: 0 for name in sspecs})
    p.sdfg.metadata["shard_declared"] = declared


def _attn_layer(p, cfg, li, spec, x, positions, bt, w, kp, vp, B, ctx, ps):
    """QKV -> paged KV write -> page gather -> PagedAttnDecode -> proj.

    The tasklet math mirrors ``blocks.attn_apply``'s decode branch
    exactly (same casts, same op order) so the compiled step reproduces
    ``decode_step`` bit-for-bit on the positions the mask admits.
    """
    adt = cfg.activation_dtype
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    D = cfg.d_model
    cache_dtype = p.sdfg.arrays[kp.name].dtype.name

    qkv_ins = {"x": x, "positions": positions, "wq": w("attn", "wq"),
               "wk": w("attn", "wk"), "wv": w("attn", "wv"),
               "ln_scale": w("attn", "ln_scale")}
    if cfg.norm == "layernorm":
        qkv_ins["ln_bias"] = w("attn", "ln_bias")

    def qkv_fn(x, positions, wq, wk, wv, ln_scale, ln_bias=None):
        jadt = jnp.dtype(adt)
        pn = {"ln_scale": ln_scale}
        if ln_bias is not None:
            pn["ln_bias"] = ln_bias
        xs = x[:, None, :]
        h = blocks._norm(cfg, xs, pn, "ln").astype(jadt)
        q = jnp.einsum("bsd,dh->bsh", h, wq.astype(jadt)
                       ).reshape(-1, 1, H, dh)
        k = jnp.einsum("bsd,dh->bsh", h, wk.astype(jadt)
                       ).reshape(-1, 1, Hkv, dh)
        v = jnp.einsum("bsd,dh->bsh", h, wv.astype(jadt)
                       ).reshape(-1, 1, Hkv, dh)
        pos2 = positions[:, None]
        q = apply_rope(q, pos2, cfg.rope_theta)
        k = apply_rope(k, pos2, cfg.rope_theta)
        cdt = jnp.dtype(cache_dtype)
        return {"q": q[:, 0], "k_new": k[:, 0].astype(cdt),
                "v_new": v[:, 0].astype(cdt)}

    qkv = _tasklet(p, f"qkv{li}", qkv_ins,
                   {"q": ((B, H, dh), adt),
                    "k_new": ((B, Hkv, dh), cache_dtype),
                    "v_new": ((B, Hkv, dh), cache_dtype)}, qkv_fn)

    def kvw_fn(kp, vp, k_new, v_new, bt, positions):
        page = jnp.take_along_axis(bt, positions[:, None] // ps,
                                   axis=1)[:, 0]
        off = positions % ps
        return {"kp_out": kp.at[page, off].set(k_new),
                "vp_out": vp.at[page, off].set(v_new)}

    _tasklet(p, f"kvw{li}",
             {"kp": kp, "vp": vp, "k_new": qkv["k_new"],
              "v_new": qkv["v_new"], "bt": bt, "positions": positions},
             {"kp_out": kp, "vp_out": vp}, kvw_fn)

    def gather_fn(kp, vp, bt):
        jadt = jnp.dtype(adt)
        rep = H // Hkv

        def expand(pages):
            c = pages[bt].reshape(-1, ctx, Hkv, dh)
            if rep > 1:
                b = c.shape[0]
                c = jnp.broadcast_to(c[:, :, :, None, :],
                                     (b, ctx, Hkv, rep, dh)
                                     ).reshape(b, ctx, H, dh)
            return c.astype(jadt)

        return {"ck": expand(kp), "cv": expand(vp)}

    g = _tasklet(p, f"gather{li}", {"kp": kp, "vp": vp, "bt": bt},
                 {"ck": ((B, ctx, H, dh), adt),
                  "cv": ((B, ctx, H, dh), adt)}, gather_fn)

    node = PagedAttnDecode(f"attn{li}", window=spec.window)
    attn = p.add_op(node, {"q": qkv["q"], "k": g["ck"], "v": g["cv"],
                           "pos": positions},
                    out_shapes={"out": (B, H, dh)},
                    out_dtypes={"out": adt})

    def proj_fn(x, attn, wo):
        jadt = jnp.dtype(adt)
        out = jnp.einsum("bsh,hd->bsd", attn.reshape(-1, 1, H * dh),
                         wo.astype(jadt))
        return {"x": (x[:, None, :] + out.astype(x.dtype))[:, 0]}

    return _tasklet(p, f"proj{li}",
                    {"x": x, "attn": attn, "wo": w("attn", "wo")},
                    {"x": ((B, D), adt)}, proj_fn)["x"]


def _ffn_layer(p, cfg, li, spec, x, w, B, D):
    adt = cfg.activation_dtype
    is_moe = spec.is_moe
    keys = sorted(k for k in p.sdfg.arrays
                  if k.startswith(f"L{li}__ffn__"))
    short = [k.split("__", 2)[2] for k in keys]

    def ffn_fn(x, **pw):
        y, _ = blocks.ffn_apply(cfg, pw, x[:, None, :], is_moe)
        return {"x": y[:, 0]}

    ins = {"x": x}
    ins.update({s: w("ffn", s) for s in short})
    return _tasklet(p, f"ffn{li}", ins, {"x": ((B, D), adt)}, ffn_fn)["x"]


def _recurrent_layer(p, cfg, li, kind, apply_fn, x, w, sth, sspecs, B, D):
    """RWKV / Mamba layer: one tasklet threading per-slot state rows.

    Rows are independent under both blocks (per-position norms, einsums
    over feature dims only), so padding lanes evolve garbage state in
    their own rows without touching live slots.
    """
    adt = cfg.activation_dtype
    skeys = [name for name, (sli, _, _) in sspecs.items() if sli == li]
    short = {name: name.split("__", 1)[1] for name in skeys}
    pkeys = sorted(k for k in p.sdfg.arrays
                   if k.startswith(f"L{li}__{kind}__"))
    pshort = [k.split("__", 2)[2] for k in pkeys]
    cache_keys = sorted(short.values())

    def rec_fn(x, **kw):
        cache = {ck: kw.pop(ck) for ck in cache_keys}
        y, nc = apply_fn(cfg, kw, x[:, None, :], cache=cache)
        out = {"x": y[:, 0]}
        for ck in cache_keys:
            out[f"{ck}_out"] = nc[ck]
        return out

    ins = {"x": x}
    ins.update({s: w(kind, s) for s in pshort})
    ins.update({short[name]: sth[name] for name in skeys})
    outs = {"x": ((B, D), adt)}
    outs.update({f"{short[name]}_out": sth[name] for name in skeys})
    return _tasklet(p, f"{kind}{li}", ins, outs, rec_fn)["x"]


# ---------------------------------------------------------------------------
# Pipelines + bucketed compile wrapper
# ---------------------------------------------------------------------------
def decode_pipeline(interpret: Optional[bool] = None,
                    dtype_aware_sublanes: bool = False,
                    n_shards: int = 1, shard_axis: str = "shard",
                    mesh_sig: Optional[str] = None) -> PassManager:
    """The serving lowering pipeline.

    Default: ``default_pipeline("pallas")`` (calibrated CPU-interpret
    tiles). With ``dtype_aware_sublanes`` the second-minor tile falls back
    to MapTiling's per-scope dtype-aware sublane packing (bf16 -> 16-row
    blocks, fp32 -> 8), exercising the per-dtype block shapes instead of
    the calibrated crossover table. ``n_shards > 1`` inserts
    ``ShardMapPass`` (after MapFusion, before tiling) so the step's slot
    and page containers partition across a 1-D mesh — tiles and grids
    then derive from the shard-local shapes.
    """
    if not dtype_aware_sublanes:
        return default_pipeline("pallas", interpret=interpret,
                                n_shards=n_shards, shard_axis=shard_axis,
                                mesh_sig=mesh_sig)
    from ..pipeline.passes import ShardMapPass
    shard = [ShardMapPass(n_shards=n_shards, axis=shard_axis,
                          mesh_sig=mesh_sig)] if n_shards > 1 else []
    tiles = GridConversionPass.default_tiles("pallas", interpret)
    return PassManager([
        SetExpansionPreferencePass(("pallas", "xla", "generic")),
        PipelineFusionPass(interpret=interpret),
        ExpandLibraryNodesPass(),
        MapFusionPass(),
        *shard,
        VectorizationPass(),
        MapTilingPass(tile_size=tiles.get("minor"), second_size=None),
        GridConversionPass(),
    ], name="pallas_serve_dtype" if not shard
        else "pallas_serve_dtype_sharded")


class CompiledDecodeStep:
    """One (B, ctx) bucket: positional jit wrapper with buffer donation.

    ``Compiled.fn`` is kwargs-only; jax donation is positional, so the
    wrapper pins the argument order (``Compiled.argument_names()``) and
    donates the page/state containers — the step consumes last step's
    pages and returns this step's without a copy.

    ``donate=False`` keeps the inputs alive (the fault-tolerant mode: a
    failed step can be re-run from the same inputs), and ``rung`` names
    the degradation-ladder level this step was compiled at (``"grid"``
    for the Pallas pipeline, ``"jit"`` for the jnp fallback).
    """

    def __init__(self, compiled, donate_names, donate: bool = True,
                 rung: str = "grid"):
        from ..codegen.jnp_backend import classify_arguments
        self.compiled = compiled
        self.report = compiled.report
        self.donate = donate
        self.rung = rung
        self.arg_names, self.output_names = classify_arguments(compiled.sdfg)
        names = self.arg_names
        fn = compiled.fn
        donate = tuple(i for i, n in enumerate(names) if n in donate_names) \
            if donate else ()

        def positional(*args):
            return fn(**dict(zip(names, args)))

        self._jit = jax.jit(positional, donate_argnums=donate)

    def __call__(self, kwargs: Dict[str, jnp.ndarray]) -> Dict:
        return self._jit(*(kwargs[n] for n in self.arg_names))


class DecodeStepCompiler:
    """Shape-bucketed compiles of the serving decode step.

    Owns the flattened weights and hands back a :class:`CompiledDecodeStep`
    per (B, ctx) bucket. Lowered SDFGs are served by the (shared, LRU)
    ``CompilationCache``: identical buckets — across scheduler restarts or
    separate compiler instances sharing a cache — hit without re-lowering.

    Graceful degradation: a bucket whose Pallas grid compile raises is
    served by the jnp-jit fallback (same SDFG, ``backend="jnp"`` — token
    for token the same step) instead of killing the server. Every
    degradation is a typed entry in ``events`` (``compile_fallback`` /
    ``compile_retry_failed`` / ``compile_recovered``), and subsequent
    hits on the bucket retry the grid compile with capped exponential
    backoff (1, 2, 4, ... ``max_compile_backoff`` bucket hits between
    attempts). ``compile_fault`` is the injection seam: a callable
    ``(B, ctx) -> None`` invoked before each grid compile (the
    fault-injection harness installs one that raises).
    """

    def __init__(self, model, params, *, page_size: int, n_pages: int,
                 cache_dtype="bfloat16", interpret: Optional[bool] = None,
                 dtype_aware_sublanes: bool = False,
                 cache: Optional[CompilationCache] = None,
                 donate: bool = True, max_compile_backoff: int = 32,
                 n_shards: int = 1, shard_axis: str = "shard",
                 mesh_sig: Optional[str] = None):
        self.model = model
        self.page_size = page_size
        self.n_pages = n_pages
        self.cache_dtype = str(jnp.dtype(cache_dtype))
        self.interpret = resolve_interpret(interpret)
        self.dtype_aware_sublanes = dtype_aware_sublanes
        self.cache = COMPILATION_CACHE if cache is None else cache
        self.donate = donate
        self.max_compile_backoff = max_compile_backoff
        if n_shards > 1 and n_pages % n_shards:
            raise ValueError(f"n_pages {n_pages} not divisible by "
                             f"n_shards {n_shards}")
        self.n_shards = int(n_shards)
        self.shard_axis = shard_axis
        self.mesh_sig = mesh_sig
        self.compile_fault = None  # optional fn(B, ctx) raising to inject
        self.events: List[dict] = []
        self.flat_weights = flatten_params(model, params,
                                           place=self._replicate())
        self._wspecs = {n: (tuple(int(s) for s in a.shape), str(a.dtype))
                        for n, a in self.flat_weights.items()}
        self._steps: Dict[Tuple[int, int], CompiledDecodeStep] = {}
        self._fallbacks: Dict[Tuple[int, int], CompiledDecodeStep] = {}
        #: per-bucket grid-compile failure state for the backoff retry
        self._fail: Dict[Tuple[int, int], dict] = {}
        self._donate = (
            {f"kp{li}" for li in attention_layer_shapes(model)} |
            {f"vp{li}" for li in attention_layer_shapes(model)} |
            set(state_specs(model)))

    def _replicate(self):
        """Weight placement for a sharded step: one replica per mesh
        device, put there once. Left to the step call, every step would
        copy the weights from the default device again."""
        if self.n_shards <= 1:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        from ..codegen.shard import make_shard_mesh
        replicated = NamedSharding(make_shard_mesh(self.n_shards,
                                                   self.shard_axis),
                                   PartitionSpec())
        return lambda a: jax.device_put(a, replicated)

    @property
    def steps(self) -> Dict[Tuple[int, int], CompiledDecodeStep]:
        """The step serving each (B, ctx) bucket compiled so far."""
        return dict(self._steps)

    def _lowered(self, B: int, ctx: int):
        low = serving_decode_step.lower(
            model=self.model, wspecs=self._wspecs, B=B, ctx=ctx,
            page_size=self.page_size, n_pages=self.n_pages,
            cache_dtype=self.cache_dtype)
        # record the donation intent on the SDFG so the static verifier
        # (analysis.bounds, DON001/DON002) can prove every donated buffer
        # is genuinely consumed-and-rewritten rather than aliased
        low.sdfg.metadata["donated"] = sorted(self._donate)
        return low

    def _check_sharded(self, compiled, B: int, ctx: int):
        """A sharded compiler must never silently serve an unsharded
        step: a ShardMapPass refusal here is a hard, typed error."""
        if self.n_shards <= 1:
            return compiled
        info = compiled.report.get("shard_map") or {}
        if not info.get("sharded"):
            reasons = [d for d in compiled.report.get("grid_decisions", ())
                       if d.get("decision") in ("unsharded", "shard_refused")]
            raise RuntimeError(
                f"decode step bucket (B={B}, ctx={ctx}) did not shard "
                f"across {self.n_shards} hosts: {reasons}")
        return compiled

    def _compile_grid(self, B: int, ctx: int) -> CompiledDecodeStep:
        if self.compile_fault is not None:
            self.compile_fault(B, ctx)
        compiled = self._check_sharded(self._lowered(B, ctx).compile(
            backend="pallas", interpret=self.interpret,
            pipeline=decode_pipeline(self.interpret,
                                     self.dtype_aware_sublanes,
                                     n_shards=self.n_shards,
                                     shard_axis=self.shard_axis,
                                     mesh_sig=self.mesh_sig),
            cache=self.cache), B, ctx)
        return CompiledDecodeStep(compiled, self._donate,
                                  donate=self.donate, rung="grid")

    def _compile_jit(self, B: int, ctx: int,
                     donate: bool) -> CompiledDecodeStep:
        compiled = self._check_sharded(self._lowered(B, ctx).compile(
            backend="jnp", cache=self.cache,
            pipeline=default_pipeline("jnp", n_shards=self.n_shards,
                                      shard_axis=self.shard_axis,
                                      mesh_sig=self.mesh_sig)), B, ctx)
        return CompiledDecodeStep(compiled, self._donate, donate=donate,
                                  rung="jit")

    def fallback_for(self, B: int, ctx: int) -> CompiledDecodeStep:
        """The jnp-jit rung for a bucket, never donating — a failed grid
        step is re-run through it from the still-live inputs."""
        fb = self._fallbacks.get((B, ctx))
        if fb is None:
            fb = self._compile_jit(B, ctx, donate=False)
            self._fallbacks[(B, ctx)] = fb
        return fb

    def step_for(self, B: int, ctx: int) -> CompiledDecodeStep:
        if ctx % self.page_size:
            raise ValueError(f"ctx bucket {ctx} not a multiple of the "
                             f"page size {self.page_size}")
        key = (B, ctx)
        step = self._steps.get(key)
        fail = self._fail.get(key)
        if step is not None and fail is not None:
            # degraded bucket: retry the grid compile with capped backoff
            fail["hits_since"] += 1
            if fail["hits_since"] >= fail["backoff"]:
                try:
                    step = self._compile_grid(B, ctx)
                    self._steps[key] = step
                    self.events.append({
                        "kind": "compile_recovered", "bucket": key,
                        "after_failures": fail["failures"]})
                    del self._fail[key]
                except Exception as e:  # noqa: BLE001 - stays degraded
                    fail["failures"] += 1
                    fail["hits_since"] = 0
                    fail["backoff"] = min(fail["backoff"] * 2,
                                          self.max_compile_backoff)
                    self.events.append({
                        "kind": "compile_retry_failed", "bucket": key,
                        "error": repr(e),
                        "next_retry_after": fail["backoff"]})
            return self._steps[key]
        if step is None:
            try:
                step = self._compile_grid(B, ctx)
            except Exception as e:  # noqa: BLE001 - degrade, don't die
                self.events.append({"kind": "compile_fallback",
                                    "bucket": key, "error": repr(e),
                                    "rung": "jit"})
                self._fail[key] = {"failures": 1, "hits_since": 0,
                                   "backoff": 1}
                step = self._compile_jit(B, ctx, donate=self.donate)
            self._steps[key] = step
        return step
