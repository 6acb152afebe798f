"""The explicit Pallas backend: native grid codegen for SDFG map scopes.

Where the XLA-auto backend (jnp_backend) structurally *interprets* map
scopes — vmap for mapped tasklets, trace-time Python loops otherwise,
capped at ``SEQUENTIAL_TRIP_LIMIT`` — this backend lowers eligible
DEVICE/PIPELINED map scopes directly to a single ``pl.pallas_call`` grid
kernel, the way the paper's code generator emits complete platform
kernels from the dataflow IR:

  * the ``grid`` comes from the map ranges (tile-counter parameters after
    MapTiling; every parameter of an untiled map);
  * each memlet's affine subset is factored by
    :func:`core.memlet.factor_subset` into ``block_shape`` + an
    ``index_map`` over grid coordinates — exactly a Pallas ``BlockSpec``.
    Intra-tile parameters (MapTiling annotations) widen index dimensions
    into VMEM-resident blocks — multi-dimensional after multi-parameter
    tiling, e.g. an (8, 128) sublane×lane tile. Block-misaligned affine
    accesses (stencil halo offsets) degrade to element-addressed
    *windows*: the whole container dimension rides in VMEM and the kernel
    body slices the window per grid step. Operands whose blocks coincide
    are deduplicated into one VMEM buffer;
  * write-conflict-resolution ``add``/``max``/``min`` memlets whose index
    map ignores some grid dimensions become VMEM scratch accumulators
    (zeros / running extrema) with ``@pl.when(k == 0)`` init and a flush
    on the last reduction step — the pattern hand-written in
    ``kernels/gemm/kernel.py``. Reduction dimensions are ordered
    innermost so the output block stays resident across the accumulation;
  * scopes may hold a *chain* of tasklets (the result of MapFusion):
    tasklet->tasklet edges carry per-iteration transients that never
    materialize — they thread through the kernel body as local values,
    so a fused producer->consumer map pair is one launch with zero HBM
    intermediates;
  * tasklet bodies whose operands are all scalar-per-iteration apply
    **once to the whole block** (array-level ops on the (8, 128) tile) —
    an abstract-shape trace (``jax.eval_shape``) verifies the body is
    elementwise (results broadcast to the tile shape) before the fast
    path is taken; genuinely scalar-indexed or slice-consuming bodies
    keep the nested per-element ``vmap`` over the intra-tile parameters;
  * partial final tiles (ceil-division MapTiling of non-divisible
    extents) are masked: Pallas itself drops the out-of-bounds region of
    boundary blocks, and reduced lanes are masked to the wcr identity
    in-kernel before accumulation.

Maps whose memlets are non-affine, dynamic, strided, or misaligned beyond
what windows express are left un-annotated by ``GridConversionPass`` and
fall back to the shared structural-interpreter lowering — mirroring the
paper's fallback to generic expansions.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.dtypes import ScheduleType
from ..core.memlet import (BlockFactorError, SubsetFactorization,
                           eval_affine, factor_subset)
from ..core.sdfg import (MapEntry, MapExit, Scalar, SDFG, State, Stream,
                         Tasklet)
from ..transforms.map_tiling import normalize_tiling
from .device import resolve_interpret
from .common import (WCR_MODES, _apply_wcr, wcr_combine, wcr_identity,
                     wcr_reduce)
from .jnp_backend import StateLowering, build_callable as _build_callable

#: annotation key GridConversionPass writes and this backend consumes.
GRID_ANNOTATION = "pallas_grid"


@dataclass(frozen=True)
class EdgeSpec:
    """One tasklet edge lowered to a Pallas operand."""
    conn: str
    data: str
    fact: SubsetFactorization
    scalar: bool = False                       # 0-d container, carried as (1,)
    wcr: Optional[str] = None                  # outputs only
    reduction: Tuple[str, ...] = ()            # grid params absent from index
    box: Tuple[Tuple[int, int], ...] = ()      # written element range per dim
    node: int = 0                              # owning tasklet (chain index)


@dataclass(frozen=True)
class WcrValueSpec:
    """One in-kernel reduction value (MapFusion's wcr mode): a
    tasklet->tasklet edge carrying ``wcr`` accumulates into a VMEM scratch
    across the ``reduction`` grid steps; the consumer side of the chain
    runs once, on the last step, with the finished value."""
    key: Tuple[int, str]            # (producer chain index, src connector)
    wcr: str
    dtype: str                      # numpy dtype name for the scratch
    reduction: Tuple[str, ...]      # grid params accumulated across steps
    kept_intra: Tuple[str, ...]     # intra-tile params addressing the value


@dataclass(frozen=True)
class GridSpec:
    """Complete derived grid-kernel description for one map scope."""
    kernel_name: str
    grid: Tuple[Tuple[str, int], ...]          # (param, size) in grid order
    block_params: Tuple[Tuple[str, int], ...]  # intra-tile params + extents
    inputs: Tuple[EdgeSpec, ...]
    outputs: Tuple[EdgeSpec, ...]
    tasklet_labels: Tuple[str, ...] = ()       # topo-ordered chain labels
    #: (intra param, counter param, tile, extent) for non-divisible tiles
    partial_tiles: Tuple[Tuple[str, str, int, int], ...] = ()
    #: tasklet->tasklet edges inside the scope (fused-DAG intermediates
    #: threaded as in-kernel values; the cost model charges VMEM for them)
    internal_edges: int = 0
    #: in-kernel wcr edges (two-phase accumulate+consume kernels)
    internal_wcr: Tuple[WcrValueSpec, ...] = ()
    #: chain indices of the consumer phase (run on the last reduction step)
    phase2_nodes: Tuple[int, ...] = ()


def _scalar_fact() -> SubsetFactorization:
    from ..core.symbolic import Expr
    return SubsetFactorization((1,), (Expr.const(0),), (0,))


def operand_key(es: EdgeSpec) -> Tuple:
    """Dedup key for input operands: everything BlockSpec-relevant.
    Windows are per-edge (sliced in-kernel) and deliberately excluded, so
    a stencil's five halo reads of one container share one VMEM buffer
    when their blocks coincide."""
    return (es.data, es.scalar, es.fact.block_shape,
            tuple(repr(e) for e in es.fact.index_exprs),
            es.fact.squeeze_dims, es.fact.param_dims)


def unique_operands(spec: GridSpec) -> List[EdgeSpec]:
    """Representative EdgeSpec per deduplicated input operand."""
    seen, reps = {}, []
    for es in spec.inputs:
        k = operand_key(es)
        if k not in seen:
            seen[k] = len(reps)
            reps.append(es)
    return reps


def _tasklet_chain(state: State, entry: MapEntry, scopes) -> List[Tasklet]:
    """Topologically-ordered tasklets of the scope; raises when the scope
    holds anything else (nested maps, access nodes, ...)."""
    inner = [n for n in scopes.get(entry, []) if not isinstance(n, MapExit)]
    if not inner or not all(isinstance(n, Tasklet) for n in inner):
        raise BlockFactorError(
            f"map {entry.map.label!r}: grid codegen requires a tasklet-only "
            f"scope, got {[type(n).__name__ for n in inner]}")
    inner_set = set(inner)
    return [n for n in state.topological_nodes() if n in inner_set]


def _output_box(fact: SubsetFactorization, grid: Dict[str, Tuple[int, int]],
                label: str, dim_sizes: Tuple[int, ...],
                valid_extents: Dict[str, int]) -> Tuple[Tuple[int, int], ...]:
    """Element-range box written by an output across the whole grid,
    clamped to the container and to the *valid* extent of partial tiles;
    also verifies full coverage inside the box (each dim's block index
    must be a constant or ``param + const`` with a param used by no other
    dim; a window must step by exactly its length)."""
    box = []
    seen_params = set()
    win = {d: (e, ln) for d, e, ln in fact.windows}
    pd_inv = {d: q for q, d in fact.param_dims}
    for d, bs in enumerate(fact.block_shape):
        dim_sz = dim_sizes[d] if d < len(dim_sizes) else bs
        if d in win:
            e, ln = win[d]
            c0, syms = 0, {}
            for mono, c in e.terms.items():
                if mono == ():
                    c0 = int(c)
                else:
                    syms[mono[0][0]] = int(c)
            if not syms:
                box.append((c0, min(c0 + ln, dim_sz)))
                continue
            if len(syms) > 1 or set(syms) & seen_params:
                raise BlockFactorError(
                    f"output of {label!r}: window dim {d} start {e} not "
                    f"contiguously covered across the grid")
            (g, cg), = syms.items()
            if cg != ln:
                raise BlockFactorError(
                    f"output of {label!r}: window dim {d} steps by {cg} "
                    f"but spans {ln} elements")
            seen_params.add(g)
            n = grid[g][1]
            hi = c0 + (n - 1) * ln + ln
            if pd_inv.get(d) in valid_extents:
                hi = min(hi, c0 + valid_extents[pd_inv[d]])
            box.append((c0, min(hi, dim_sz)))
            continue
        e = fact.index_exprs[d]
        c0 = 0
        syms = {}
        for mono, c in e.terms.items():
            if mono == ():
                c0 = int(c)
            else:
                syms[mono[0][0]] = c
        if not syms:
            span = valid_extents.get(pd_inv.get(d), bs)
            box.append((c0 * bs, min(c0 * bs + span, dim_sz)))
            continue
        if len(syms) > 1 or set(syms) & seen_params:
            raise BlockFactorError(
                f"output of {label!r}: dim {d} index {e} not contiguously "
                f"covered across the grid")
        (g, cg), = syms.items()
        if cg != 1:
            raise BlockFactorError(
                f"output of {label!r}: dim {d} strides blocks by {cg}")
        seen_params.add(g)
        n = grid[g][1]
        hi = (c0 + n - 1) * bs + bs
        if pd_inv.get(d) in valid_extents:
            hi = min(hi, c0 * bs + valid_extents[pd_inv[d]])
        box.append((c0 * bs, min(hi, dim_sz)))
    return tuple(box)


def analyze_map_scope(sdfg: SDFG, state: State, entry: MapEntry,
                      scopes=None, env: Optional[Dict[str, int]] = None
                      ) -> GridSpec:
    """Derive a :class:`GridSpec` for a map scope, or raise
    :class:`BlockFactorError` when the scope must fall back to the
    structural interpreter."""
    m = entry.map
    if m.schedule not in (ScheduleType.PIPELINED, ScheduleType.DEVICE):
        raise BlockFactorError(
            f"map {m.label!r}: schedule {m.schedule.value} is not a grid")
    scopes = scopes if scopes is not None else state.scope_children()
    chain = _tasklet_chain(state, entry, scopes)
    chain_index = {t: i for i, t in enumerate(chain)}
    env = dict(sdfg.symbol_values) if env is None else dict(env)

    tiling = normalize_tiling(m.annotations.get("tiling", {}))
    grid_params: Dict[str, Tuple[int, int]] = {}
    block_params: Dict[str, int] = {}
    partials: List[Tuple[str, str, int, int]] = []
    valid_extents: Dict[str, int] = {}
    for p, r in zip(m.params, m.ranges):
        try:
            start, size = r.start.subs(env).as_int(), r.size.subs(env).as_int()
        except Exception as exc:
            raise BlockFactorError(
                f"map {m.label!r}: dynamic range for {p}") from exc
        if size < 1:
            raise BlockFactorError(f"map {m.label!r}: empty range for {p}")
        if p in tiling and size > 1:
            info = tiling[p]
            if start != 0 or size != int(info["tile"]):
                raise BlockFactorError(
                    f"map {m.label!r}: tile param {p} range [{start}, "
                    f"+{size}) disagrees with tiling annotation "
                    f"{info['tile']}")
            block_params[p] = size
            ext = info.get("extent")
            if ext is not None:
                valid_extents[p] = int(ext)
                if int(ext) % size:
                    ctr = info.get("counter")
                    if ctr is None or ctr not in m.params:
                        raise BlockFactorError(
                            f"map {m.label!r}: partial tile {p} has no "
                            f"counter to mask against")
                    partials.append((p, ctr, size, int(ext)))
        else:
            grid_params[p] = (start, size)
    if not grid_params:
        raise BlockFactorError(f"map {m.label!r}: no grid parameters")
    partial_qs = {q for q, _, _, _ in partials}
    partial_counters = {c for _, c, _, _ in partials}

    def _factor(memlet):
        if memlet.dynamic:
            raise BlockFactorError(f"dynamic memlet {memlet}")
        if memlet.data not in sdfg.arrays:
            raise BlockFactorError(f"no descriptor for {memlet.data!r}")
        desc = sdfg.arrays[memlet.data]
        if isinstance(desc, Stream):
            raise BlockFactorError(f"stream operand {memlet.data!r}")
        if isinstance(desc, Scalar) or not getattr(desc, "shape", ()):
            return _scalar_fact(), True, (1,)
        fact = factor_subset(memlet.subset, desc.shape, grid_params,
                             block_params, env, allow_windows=True)
        from ..core.symbolic import Expr
        dim_sizes = tuple(int(Expr.wrap(s).evaluate(env))
                          for s in desc.shape)
        # a window whose start depends on a partial tile's counter would
        # clamp-shift at the boundary block: fall back instead
        for d, expr, ln in fact.windows:
            if expr.free_symbols & partial_counters:
                raise BlockFactorError(
                    f"window on {memlet.data!r} dim {d} rides the partial "
                    f"tile counter {sorted(expr.free_symbols & partial_counters)}")
            pdq = {dd: q for q, dd in fact.param_dims}.get(d)
            if pdq in partial_qs:
                raise BlockFactorError(
                    f"window on {memlet.data!r} dim {d} spans partial "
                    f"tile param {pdq}")
        return fact, False, tuple(dim_sizes)

    inputs = []
    out_edge_list = []  # (chain index, edge)
    internal_vals = set()  # distinct in-kernel values: a fan-out producer
    wcr_edge_list = []  # (producer chain index, edge) for in-kernel wcr
    for ti, t in enumerate(chain):    # value is stored once, not per reader
        for e in state.in_edges(t):
            if e.dst_conn is None or e.memlet.data is None:
                continue
            if e.src in chain_index:
                # per-iteration intermediate, threaded as a local value;
                # wcr edges additionally accumulate across the reduction
                # steps (two-phase kernel, analyzed below)
                if e.memlet.wcr is not None:
                    wcr_edge_list.append((chain_index[e.src], e))
                internal_vals.add((chain_index[e.src], e.src_conn))
                continue
            fact, scalar, _ = _factor(e.memlet)
            inputs.append(EdgeSpec(e.dst_conn, e.memlet.data, fact, scalar,
                                   node=ti))
        for e in state.out_edges(t):
            if e.dst in chain_index:
                continue
            if e.memlet.data is None:
                continue
            out_edge_list.append((ti, e))

    if not out_edge_list:
        raise BlockFactorError(f"map {m.label!r}: no kernel outputs")
    used_any: List[str] = []
    outs_raw = []
    for ti, e in out_edge_list:
        if e.memlet.wcr is not None and e.memlet.wcr not in WCR_MODES:
            raise BlockFactorError(
                f"map {m.label!r}: wcr {e.memlet.wcr!r} unsupported")
        fact, scalar, dim_sizes = _factor(e.memlet)
        box = _output_box(fact, grid_params, m.label, dim_sizes,
                          valid_extents)
        used = set()
        for ex in fact.index_exprs:
            used |= ex.free_symbols
        for _, wexpr, _ in fact.windows:
            used |= wexpr.free_symbols
        if e.memlet.wcr is None:
            # a partial tile lane absent from a plain output would make the
            # garbage lane the "last write": fall back
            pd = dict(fact.param_dims)
            for q in partial_qs:
                if q not in pd:
                    raise BlockFactorError(
                        f"map {m.label!r}: partial tile param {q} absent "
                        f"from plain output {e.memlet.data!r}")
        for p in m.params:
            if p in used and p in grid_params and p not in used_any:
                used_any.append(p)
        outs_raw.append((ti, e, fact, scalar, box, used))

    # grid order: output-indexing params first (original order), reduction
    # params innermost so scratch accumulators stay block-resident.
    order = [p for p in m.params if p in grid_params and p in used_any]
    order += [p for p in m.params if p in grid_params and p not in used_any]
    outputs = []
    for ti, e, fact, scalar, box, used in outs_raw:
        reduction = tuple(p for p in order if p not in used)
        if reduction and fact.windows:
            raise BlockFactorError(
                f"map {m.label!r}: windowed output {e.memlet.data!r} "
                f"cannot host a scratch reduction")
        # every reduction dim must iterate inside every used dim
        max_used = max((order.index(p) for p in order if p in used),
                       default=-1)
        if any(order.index(p) < max_used for p in reduction):
            raise BlockFactorError(
                f"map {m.label!r}: reduction params {reduction} cannot be "
                f"ordered innermost for output {e.memlet.data!r}")
        if e.memlet.wcr is None and reduction and not getattr(
                chain[ti], "side_effect_free", True):
            raise BlockFactorError(f"map {m.label!r}: side-effecting tasklet")
        outputs.append(EdgeSpec(e.src_conn, e.memlet.data, fact, scalar,
                                e.memlet.wcr, reduction, box, node=ti))

    internal_wcr: Tuple[WcrValueSpec, ...] = ()
    phase2_nodes: Tuple[int, ...] = ()
    if wcr_edge_list:
        internal_wcr, phase2_nodes = _analyze_internal_wcr(
            sdfg, state, m, chain, chain_index, wcr_edge_list, grid_params,
            block_params, order, used_any, inputs, outputs, out_edge_list)

    return GridSpec(
        kernel_name=m.label,
        grid=tuple((p, grid_params[p][1]) for p in order),
        block_params=tuple((p, block_params[p]) for p in m.params
                           if p in block_params),
        inputs=tuple(inputs), outputs=tuple(outputs),
        tasklet_labels=tuple(t.label for t in chain),
        partial_tiles=tuple(partials),
        internal_edges=len(internal_vals),
        internal_wcr=internal_wcr, phase2_nodes=phase2_nodes)


def _analyze_internal_wcr(sdfg, state, m, chain, chain_index, wcr_edge_list,
                          grid_params, block_params, order, used_any,
                          inputs, outputs, out_edge_list
                          ) -> Tuple[Tuple[WcrValueSpec, ...],
                                     Tuple[int, ...]]:
    """Legality analysis for in-kernel wcr edges (MapFusion's reduction
    mode) and derivation of the two-phase kernel structure; raises
    :class:`BlockFactorError` when the shape cannot be expressed, falling
    back to the structural interpreter (whose sequential/phased-vmap
    lowerings are always correct for these scopes)."""
    pset = set(m.params)
    used_sets = []
    for src_ti, e in wcr_edge_list:
        if e.memlet.wcr not in WCR_MODES:
            raise BlockFactorError(
                f"map {m.label!r}: in-kernel wcr {e.memlet.wcr!r} "
                f"unsupported")
        if e.memlet.subset is None:
            raise BlockFactorError(
                f"map {m.label!r}: in-kernel wcr edge without a subset")
        used = set()
        for r in e.memlet.subset:
            used |= ((r.start.free_symbols | r.stop.free_symbols) & pset)
        used_sets.append(used)
    kept = used_sets[0]
    if any(u != kept for u in used_sets):
        raise BlockFactorError(
            f"map {m.label!r}: in-kernel wcr edges disagree on reduction "
            f"parameters")
    kept_grid = kept & set(grid_params)
    kept_intra = kept & set(block_params)
    reduction = tuple(p for p in order if p not in kept)
    red_intra = {q for q in block_params if q not in kept_intra}
    if not reduction:
        raise BlockFactorError(
            f"map {m.label!r}: in-kernel wcr with no grid reduction step")
    if kept_grid - set(used_any):
        raise BlockFactorError(
            f"map {m.label!r}: reduction-addressing params "
            f"{sorted(kept_grid - set(used_any))} absent from every output")

    # consumer phase: everything downstream of a wcr edge
    phase2 = set()
    work = [chain_index[e.dst] for _, e in wcr_edge_list]
    while work:
        ti = work.pop()
        if ti in phase2:
            continue
        phase2.add(ti)
        for e in state.out_edges(chain[ti]):
            if e.dst in chain_index:
                work.append(chain_index[e.dst])
    for ti, t in enumerate(chain):
        if ti in phase2:
            continue
        for e in state.out_edges(t):
            if (e.dst in chain_index and chain_index[e.dst] in phase2
                    and e.memlet.wcr is None):
                raise BlockFactorError(
                    f"map {m.label!r}: plain producer->consumer edge "
                    f"alongside an in-kernel wcr edge")
    for ti, e in out_edge_list:
        if ti not in phase2:
            raise BlockFactorError(
                f"map {m.label!r}: reduction producer also writes through "
                f"the exit")
    red_syms = set(reduction) | red_intra
    for es in outputs:
        if es.wcr is not None:
            raise BlockFactorError(
                f"map {m.label!r}: wcr output downstream of an in-kernel "
                f"reduction")
        _check_phase_free(m, es, red_syms, red_intra, "output")
    for es in inputs:
        if es.node in phase2:
            _check_phase_free(m, es, red_syms, red_intra, "consumer input")

    specs, seen = [], set()
    for src_ti, e in wcr_edge_list:
        key = (src_ti, e.src_conn)
        if key in seen:
            continue
        seen.add(key)
        desc = sdfg.arrays.get(e.memlet.data)
        if desc is None:
            raise BlockFactorError(
                f"map {m.label!r}: no descriptor for in-kernel wcr "
                f"intermediate {e.memlet.data!r}")
        specs.append(WcrValueSpec(
            key=key, wcr=e.memlet.wcr,
            dtype=str(desc.dtype.np_dtype.__name__
                      if hasattr(desc.dtype.np_dtype, "__name__")
                      else desc.dtype.np_dtype),
            reduction=reduction,
            kept_intra=tuple(q for q in block_params if q in kept_intra)))
    return tuple(specs), tuple(sorted(phase2))


def _check_phase_free(m, es: EdgeSpec, red_syms, red_intra, what: str):
    """A consumer-phase memlet must not address a reduction parameter —
    the consumer runs only on the last reduction step."""
    syms = set()
    for ex in es.fact.index_exprs:
        syms |= ex.free_symbols
    for _, wexpr, _ in es.fact.windows:
        syms |= wexpr.free_symbols
    if syms & red_syms or {q for q, _ in es.fact.param_dims} & red_intra:
        raise BlockFactorError(
            f"map {m.label!r}: {what} {es.data!r} addresses a reduction "
            f"parameter")


# ---------------------------------------------------------------------------
# Kernel emission
# ---------------------------------------------------------------------------


def _squeeze_adjusted_axis(fact: SubsetFactorization, dim: int) -> int:
    """Axis of ``dim`` in the loaded value after squeezing."""
    return dim - sum(1 for s in fact.squeeze_dims if s < dim)


def _conds(ids, positions, sizes, at_end: bool):
    conds = [ids[k] == (sizes[k] - 1 if at_end else 0) for k in positions]
    return functools.reduce(jnp.logical_and, conds)


#: TPU lane width: a dynamic slice start on a ref's minor dimension must
#: be a provable multiple of it.
LANES = 128
#: largest 32-bit operand placed whole in SMEM (scalar memory is small)
SMEM_MAX_BYTES = 16 * 1024


@dataclass(frozen=True)
class OperandPlacement:
    """How one deduplicated input operand reaches the kernel.

    ``smem``: a one-element-per-step 32-bit operand rides whole in SMEM
    and the body reads its scalar by grid index (a ``(1,)`` VMEM block of
    a longer vector is refused by the TPU compiler). ``view``: the 2-D
    shape a 1-D operand is passed as (see :func:`_vector_view`).
    ``pad``: trailing elements appended per dimension so lane-aligned
    window loads stay in bounds."""
    smem: bool = False
    view: Optional[str] = None
    pad: Tuple[int, ...] = ()


def _vector_view(block: int, n: int, windowed: bool) -> str:
    """The 2-D view a 1-D operand takes on the TPU, which lays 1-D arrays
    out in 1024-element tiles that kernel blocks do not match: ``"row"``
    ``(1, n)`` for lane-sized blocks and windows, ``"col"`` ``(n, 1)`` for
    blocks that only fill sublanes (the row index of an (8, 128) tile)."""
    if windowed or block % LANES == 0 or block == n:
        return "row"
    return "col"


def _view_block(view: Optional[str], block: Tuple[int, ...], index_map):
    """BlockSpec shape and index map of an operand seen through ``view``."""
    if view == "row":
        return (1,) + block, lambda *ids: (0,) + tuple(index_map(*ids))
    if view == "col":
        return block + (1,), lambda *ids: tuple(index_map(*ids)) + (0,)
    return block, index_map


def _view_shape(view: Optional[str], shape: Tuple[int, ...]):
    return {"row": (1,) + shape, "col": shape + (1,)}.get(view, shape)


def _lane_window(expr, ln: int):
    """``(aligned_const, offset, extent)`` when every grid coefficient of
    a window start is a lane multiple: the body loads ``extent`` lanes at
    a provably aligned start and slices ``[offset, offset + ln)`` out of
    them statically. ``None`` when the start cannot be aligned."""
    c0 = 0
    for mono, c in expr.terms.items():
        if mono == ():
            c0 = int(c)
        elif int(c) % LANES or int(c) < 0:
            return None
    if c0 < 0:
        return None
    base = c0 // LANES * LANES
    off = c0 - base
    return base, off, -(-(off + ln) // LANES) * LANES


def _max_affine(expr, grid_sizes: Dict[str, int]) -> int:
    """Largest value of a non-negative-coefficient affine expression over
    the grid."""
    hi = 0
    for mono, c in expr.terms.items():
        hi += int(c) if mono == () else int(c) * (grid_sizes[mono[0][0]] - 1)
    return hi


def _place_operand(es: EdgeSpec, edges: List[EdgeSpec], value,
                   grid_sizes: Dict[str, int]) -> OperandPlacement:
    import numpy as np
    squeezed = es.scalar or (
        len(es.fact.squeeze_dims) == len(es.fact.block_shape)
        and not es.fact.param_dims)
    if (squeezed and not es.fact.windows
            and int(np.prod(es.fact.block_shape)) == 1
            and value.dtype.itemsize == 4
            and value.size * 4 <= SMEM_MAX_BYTES):
        return OperandPlacement(smem=True)
    rank = value.ndim
    pad = [0] * rank
    for e in edges:
        for d, expr, ln in e.fact.windows:
            lw = _lane_window(expr, ln) if d == rank - 1 else None
            if lw is not None:
                _, off, ext = lw
                # the last aligned load ends at (largest start - off) + ext
                need = _max_affine(expr, grid_sizes) - off + ext
                pad[d] = max(pad[d], need - value.shape[d])
    pad = tuple(max(0, p) for p in pad)
    view = None
    if rank == 1:
        view = _vector_view(es.fact.block_shape[0] + pad[0],
                            value.shape[0] + pad[0],
                            any(e.fact.windows for e in edges))
    return OperandPlacement(view=view, pad=pad)


class PallasStateLowering(StateLowering):
    """State lowering that emits ``pl.pallas_call`` grid kernels for map
    scopes annotated by ``GridConversionPass`` and shares the structural
    interpreter for everything else."""

    def _lower_map_custom(self, entry: MapEntry, exit_: MapExit,
                          inner: List) -> bool:
        spec: Optional[GridSpec] = entry.map.annotations.get(GRID_ANNOTATION)
        if spec is None:
            return False
        if not inner or not all(isinstance(n, Tasklet) for n in inner):
            return False
        inner_set = set(inner)
        chain = [n for n in self.state.topological_nodes() if n in inner_set]
        labels = tuple(t.label for t in chain)
        if spec.tasklet_labels and labels != spec.tasklet_labels:
            return False  # stale annotation: graph changed under the spec
        if spec.internal_wcr:
            self._emit_two_phase(entry, chain, spec)
        else:
            self._emit_grid_kernel(entry, chain, spec)
        return True

    # ------------------------------------------------------------------
    def _chain_runner(self, chain: List[Tasklet], spec: GridSpec):
        """Build ``chain_call(opvals) -> results`` running the topo-ordered
        tasklet chain with container operands from ``opvals`` (keyed by
        input-edge index) and tasklet->tasklet values as locals."""
        chain_index = {t: i for i, t in enumerate(chain)}
        int_in: List[List[Tuple[str, Tuple[int, str]]]] = []
        out_binds: List[List[Tuple[str, str, object]]] = []
        for ti, t in enumerate(chain):
            ints = []
            for e in self.state.in_edges(t):
                if e.src in chain_index:
                    ints.append((e.dst_conn,
                                 (chain_index[e.src], e.src_conn)))
            int_in.append(ints)
            out_binds.append([])
        for oi, es in enumerate(spec.outputs):
            out_binds[es.node].append((es.conn, "result", oi))
        for ti, t in enumerate(chain):
            for e in self.state.out_edges(t):
                if e.dst in chain_index:
                    out_binds[ti].append((e.src_conn, "local",
                                          (ti, e.src_conn)))
        fns = [t.fn for t in chain]
        decl_outputs = [list(getattr(t, "outputs", ())) for t in chain]
        n_out = len(spec.outputs)

        def chain_call(opvals):
            local = {}
            results = [None] * n_out
            for ti in range(len(chain)):
                kwargs = {}
                for i, es in enumerate(spec.inputs):
                    if es.node == ti:
                        kwargs[es.conn] = opvals[i]
                for conn, key in int_in[ti]:
                    kwargs[conn] = local[key]
                r = fns[ti](**kwargs)
                conns = [c for c, _, _ in out_binds[ti]]
                if not isinstance(r, dict):
                    if isinstance(r, tuple):
                        r = dict(zip(decl_outputs[ti] or conns, r))
                    else:
                        r = {conns[0]: r}
                for conn, kind, ref in out_binds[ti]:
                    if kind == "local":
                        local[ref] = r[conn]
                    else:
                        results[ref] = r[conn]
            return tuple(results)

        return chain_call

    def _whole_block_eligible(self, spec: GridSpec, chain_call,
                              chain: List[Tasklet]) -> bool:
        """True when every operand is scalar-per-iteration (all non-tile
        effective dims are size 1) AND the chain is verifiably
        elementwise: an abstract-shape trace confirms every result
        broadcasts to the tile shape, and a concrete probe on random
        block data checks the whole-block application against the
        per-element (nested vmap) semantics — a shape trace alone cannot
        reject bodies like ``lambda a: jnp.sum(a)`` whose scalar result
        still broadcasts. Slice-consuming, shape-changing, or
        value-diverging bodies keep the per-element nested vmap."""
        import numpy as np
        if not spec.block_params:
            return False
        if not all(getattr(t, "side_effect_free", True) for t in chain):
            return False
        block_order = [q for q, _ in spec.block_params]
        bp = dict(spec.block_params)
        tile_shape = tuple(n for _, n in spec.block_params)
        for es in list(spec.inputs) + list(spec.outputs):
            pdims = set(dict(es.fact.param_dims).values())
            for d, n in enumerate(es.fact.effective_shape()):
                if n != 1 and d not in pdims:
                    return False
        rng = np.random.default_rng(2025)
        padded, unpadded = {}, {}
        for i, es in enumerate(spec.inputs):
            pd = dict(es.fact.param_dims)
            present = tuple(bp[q] for q in block_order if q in pd)
            desc = self.sdfg.arrays.get(es.data)
            dt = np.dtype(desc.dtype.np_dtype if desc is not None
                          else np.float32)
            if np.issubdtype(dt, np.inexact):
                base = rng.standard_normal(present).astype(dt)
            elif dt == np.bool_:
                base = rng.integers(0, 2, present).astype(dt)
            else:
                base = rng.integers(1, 8, present).astype(dt)
            unpadded[i] = jnp.asarray(base)
            padded[i] = jnp.reshape(
                unpadded[i],
                tuple(bp[q] if q in pd else 1 for q in block_order))
        try:
            results = jax.eval_shape(chain_call, padded)
            for r in results:
                if jnp.broadcast_shapes(tuple(r.shape),
                                        tile_shape) != tile_shape:
                    return False
            # the emit may be running under an outer jit trace, where ops
            # on concrete arrays are staged as tracers; the probe needs
            # real values at trace time
            with jax.ensure_compile_time_eval():
                whole = [jnp.broadcast_to(jnp.asarray(r), tile_shape)
                         for r in chain_call(padded)]
                f = chain_call
                for q in reversed(block_order):
                    axes = {i: (0 if q in dict(es.fact.param_dims)
                                else None)
                            for i, es in enumerate(spec.inputs)}
                    f = jax.vmap(f, in_axes=(axes,), out_axes=0)
                ref = [jnp.broadcast_to(jnp.asarray(r), tile_shape)
                       for r in f(unpadded)]
                return all(
                    np.allclose(np.asarray(w), np.asarray(r), rtol=1e-5,
                                atol=1e-6, equal_nan=True)
                    for w, r in zip(whole, ref))
        except Exception:
            return False

    # ------------------------------------------------------------------
    def _emit_grid_kernel(self, entry: MapEntry, chain: List[Tasklet],
                          spec: GridSpec):
        interpret = resolve_interpret(self.sdfg.metadata.get("pallas_interpret"))
        grid_names = [p for p, _ in spec.grid]
        grid_sizes = tuple(n for _, n in spec.grid)
        block_order = [q for q, _ in spec.block_params]
        bp = dict(spec.block_params)
        tile_shape = tuple(n for _, n in spec.block_params)

        op_reps, op_of_edge, in_vals, in_specs, places = \
            self._input_operands(spec)

        out_specs, out_shapes, out_views = self._output_operands(spec)
        scratch_shapes, scratch_index = [], {}
        for oi, es in enumerate(spec.outputs):
            if es.wcr in WCR_MODES and es.reduction:
                scratch_index[oi] = len(scratch_shapes)
                scratch_shapes.append(pltpu.VMEM(out_specs[oi].block_shape,
                                                 out_shapes[oi].dtype))

        chain_call = self._chain_runner(chain, spec)
        whole_block = self._whole_block_eligible(spec, chain_call, chain)
        n_ops, n_out = len(op_reps), len(spec.outputs)

        def kernel(*refs):
            ins = refs[:n_ops]
            outs = refs[n_ops:n_ops + n_out]
            scratch = refs[n_ops + n_out:]
            ids = [pl.program_id(k) for k in range(len(grid_names))]
            id_env = dict(zip(grid_names, ids))
            opvals = self._load_operands(spec, ins, op_of_edge, places,
                                         block_order, id_env)

            if whole_block:
                # one array-level application over the whole tile: pad
                # every operand to rank len(block_order) (size-1 axes for
                # absent tile params) and let broadcasting do the rest
                bvals = {}
                for i, es in enumerate(spec.inputs):
                    pd = dict(es.fact.param_dims)
                    shape = tuple(bp[q] if q in pd else 1
                                  for q in block_order)
                    bvals[i] = jnp.reshape(opvals[i], shape)
                results = chain_call(bvals)
            elif block_order:
                f = chain_call
                for q in reversed(block_order):
                    axes = {i: (0 if q in dict(es.fact.param_dims) else None)
                            for i, es in enumerate(spec.inputs)}
                    f = jax.vmap(f, in_axes=(axes,), out_axes=0)
                results = f(opvals)
            else:
                results = chain_call(opvals)

            for oi, (es, oref) in enumerate(zip(spec.outputs, outs)):
                val = jnp.asarray(results[oi])
                if whole_block:
                    val = jnp.broadcast_to(val, tile_shape)
                if es.wcr in WCR_MODES and spec.partial_tiles:
                    # mask reduced padding lanes to the identity; lanes
                    # present in the output land in the block's OOB region
                    # and are dropped by Pallas itself
                    pd = dict(es.fact.param_dims)
                    for q, counter, ts, ext in spec.partial_tiles:
                        if q in pd:
                            continue
                        ax = block_order.index(q)
                        lane = jax.lax.broadcasted_iota(
                            jnp.int32, jnp.shape(val), ax)
                        gidx = ids[grid_names.index(counter)] * ts + lane
                        val = jnp.where(
                            gidx < ext, val,
                            wcr_identity(es.wcr, jnp.asarray(val).dtype))
                val = self._assemble_block(val, es, block_order)
                if es.fact.windows:
                    self._store(oref, val, es, id_env, out_views[oi])
                elif es.wcr in WCR_MODES and es.reduction:
                    acc = scratch[scratch_index[oi]]
                    red_pos = [grid_names.index(p) for p in es.reduction]
                    first = _conds(ids, red_pos, grid_sizes, at_end=False)
                    last = _conds(ids, red_pos, grid_sizes, at_end=True)

                    @pl.when(first)
                    def _init(acc=acc, es=es):
                        acc[...] = jnp.full(
                            acc.shape, wcr_identity(es.wcr, acc.dtype))

                    acc[...] = wcr_combine(
                        es.wcr, acc[...],
                        jnp.reshape(val, acc.shape).astype(acc.dtype))

                    @pl.when(last)
                    def _flush(acc=acc, oref=oref):
                        oref[...] = acc[...].astype(oref.dtype)
                else:
                    self._store(oref, val, es, id_env, out_views[oi])

        results = pl.pallas_call(
            kernel, grid=grid_sizes, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shapes, scratch_shapes=scratch_shapes,
            interpret=interpret)(*in_vals)
        self._stitch_results(spec, results)

    def _output_operands(self, spec: GridSpec):
        """Output BlockSpecs, shapes and 1-D views (a 1-D output is
        produced through its :func:`_vector_view`)."""
        grid_names = [p for p, _ in spec.grid]
        out_specs, out_shapes, views = [], [], []
        for es in spec.outputs:
            pv = jnp.asarray(self.ensure_value(es.data))
            shape = (1,) if es.scalar else tuple(pv.shape)
            view = _vector_view(es.fact.block_shape[0], shape[0],
                                bool(es.fact.windows)) \
                if len(shape) == 1 else None
            block, index_map = _view_block(view, tuple(es.fact.block_shape),
                                           es.fact.index_map(grid_names))
            out_specs.append(pl.BlockSpec(block, index_map))
            out_shapes.append(jax.ShapeDtypeStruct(_view_shape(view, shape),
                                                   pv.dtype))
            views.append(view)
        return out_specs, out_shapes, views

    @staticmethod
    def _store(oref, val, es: EdgeSpec, id_env, view: Optional[str]):
        """Write one output block (or its window) into the output ref."""
        lead = 1 if view == "row" else 0
        idx = [slice(None)] * len(oref.shape)
        for d, expr, ln in es.fact.windows:
            idx[d + lead] = pl.ds(eval_affine(expr, id_env), ln)
        val = jnp.reshape(val, _view_shape(view, jnp.shape(val)))
        oref[tuple(idx)] = val.astype(oref.dtype)

    def _input_operands(self, spec: GridSpec):
        """Deduplicated input operands with their placements, the values
        handed to ``pallas_call`` and their BlockSpecs."""
        grid_names = [p for p, _ in spec.grid]
        grid_sizes = dict(spec.grid)
        op_reps = unique_operands(spec)
        op_index = {operand_key(es): i for i, es in enumerate(op_reps)}
        op_of_edge = [op_index[operand_key(es)] for es in spec.inputs]
        in_vals, in_specs, places = [], [], []
        for oi, es in enumerate(op_reps):
            v = jnp.asarray(self.ensure_value(es.data))
            if es.scalar:
                v = jnp.reshape(v, (1,))
            edges = [e for e, o in zip(spec.inputs, op_of_edge) if o == oi]
            place = _place_operand(es, edges, v, grid_sizes)
            block = list(es.fact.block_shape)
            if any(place.pad):
                v = jnp.pad(v, [(0, p) for p in place.pad])
                block = [b + p for b, p in zip(block, place.pad)]
            if place.smem:
                bspec = pl.BlockSpec(memory_space=pltpu.SMEM)
            else:
                v = jnp.reshape(v, _view_shape(place.view, v.shape))
                bspec = pl.BlockSpec(*_view_block(
                    place.view, tuple(block), es.fact.index_map(grid_names)))
            in_vals.append(v)
            in_specs.append(bspec)
            places.append(place)
        return op_reps, op_of_edge, in_vals, in_specs, places

    @staticmethod
    def _load_edge(ref, es: EdgeSpec, place: OperandPlacement, id_env):
        """One input edge's value out of its operand's ref: a scalar read
        from SMEM, or the block with each window sliced at load time (lane
        windows load an aligned span and slice it statically)."""
        if place.smem:
            idx = tuple(eval_affine(e, id_env) for e in es.fact.index_exprs)
            return ref[idx if not es.scalar else (0,)]
        lead = (slice(None),) if place.view == "row" else ()
        trail = (slice(None),) if place.view == "col" else ()
        rank = len(es.fact.block_shape)
        idx = [slice(None)] * rank
        post = [slice(None)] * rank
        for d, expr, ln in es.fact.windows:
            start = eval_affine(expr, id_env)
            lw = _lane_window(expr, ln) if d == rank - 1 else None
            if lw is None:
                idx[d] = pl.ds(start, ln)
                continue
            base, off, ext = lw
            idx[d] = pl.ds(pl.multiple_of(start - off, LANES), ext)
            post[d] = slice(off, off + ln)
        v = ref[lead + tuple(idx) + trail]
        if es.fact.windows:
            v = v[lead + tuple(post) + trail]
        if place.view is not None:
            v = jnp.reshape(v, v.shape[1:] if lead else v.shape[:-1])
        # a padded operand's plain (window-free) dims load whole: trim
        if any(place.pad):
            v = v[tuple(slice(0, n) if p and not any(
                w[0] == d for w in es.fact.windows) else slice(None)
                for d, (n, p) in enumerate(zip(es.fact.block_shape,
                                               place.pad)))]
        return v

    def _load_operands(self, spec: GridSpec, ins, op_of_edge, places,
                       block_order, id_env):
        """Per-input-edge kernel values: dedup'd block (or SMEM scalar),
        window slice, squeeze, tile axes moved to the front in
        block-param order."""
        opvals = {}
        for i, es in enumerate(spec.inputs):
            o = op_of_edge[i]
            v = self._load_edge(ins[o], es, places[o], id_env)
            if places[o].smem:
                opvals[i] = v  # already the scalar the squeeze would give
                continue
            if es.fact.squeeze_dims:
                v = jnp.squeeze(v, axis=es.fact.squeeze_dims)
            pd = dict(es.fact.param_dims)
            present = [q for q in block_order if q in pd]
            if present:  # tile axes to the front, in block-param order
                src = [_squeeze_adjusted_axis(es.fact, pd[q])
                       for q in present]
                v = jnp.moveaxis(v, src, list(range(len(src))))
            opvals[i] = v
        return opvals

    # ------------------------------------------------------------------
    def _phased_runners(self, chain: List[Tasklet], spec: GridSpec):
        """Split :meth:`_chain_runner` for two-phase kernels: phase 1
        (producer side) returns the per-iteration wcr contributions keyed
        by ``spec.internal_wcr`` order; phase 2 (consumer side) takes the
        finished accumulator values and returns the kernel outputs."""
        chain_index = {t: i for i, t in enumerate(chain)}
        p2 = set(spec.phase2_nodes)
        wcr_keys = [w.key for w in spec.internal_wcr]
        int_in: List[List[Tuple[str, Tuple[int, str]]]] = []
        int_out: List[List[Tuple[str, Tuple[int, str]]]] = []
        for ti, t in enumerate(chain):
            int_in.append([(e.dst_conn, (chain_index[e.src], e.src_conn))
                           for e in self.state.in_edges(t)
                           if e.src in chain_index])
            int_out.append([(e.src_conn, (ti, e.src_conn))
                            for e in self.state.out_edges(t)
                            if e.dst in chain_index])
        res_of = {}
        for oi, es in enumerate(spec.outputs):
            res_of.setdefault(es.node, []).append((es.conn, oi))
        fns = [t.fn for t in chain]
        decl_outputs = [list(getattr(t, "outputs", ())) for t in chain]
        n_out = len(spec.outputs)

        def _normalize(ti, r):
            if isinstance(r, dict):
                return r
            conns = [c for c, _ in int_out[ti]]
            conns += [c for c, _ in res_of.get(ti, ())]
            if isinstance(r, tuple):
                return dict(zip(decl_outputs[ti] or conns, r))
            return {conns[0]: r}

        def _run_phase(tis, opvals, local):
            results = [None] * n_out
            for ti in tis:
                kwargs = {}
                for i, es in enumerate(spec.inputs):
                    if es.node == ti:
                        kwargs[es.conn] = opvals[i]
                for conn, key in int_in[ti]:
                    kwargs[conn] = local[key]
                r = _normalize(ti, fns[ti](**kwargs))
                for conn, key in int_out[ti]:
                    if key not in local:  # an acc value stays accumulated
                        local[key] = r[conn]
                for conn, oi in res_of.get(ti, ()):
                    results[oi] = r[conn]
            return results

        p1_tis = [ti for ti in range(len(chain)) if ti not in p2]
        p2_tis = [ti for ti in range(len(chain)) if ti in p2]

        def chain1_call(opvals):
            local = {}
            _run_phase(p1_tis, opvals, local)
            return tuple(local[k] for k in wcr_keys)

        def chain2_call(opvals, accs):
            local = dict(zip(wcr_keys, accs))
            return tuple(_run_phase(p2_tis, opvals, local))

        return chain1_call, chain2_call

    def _emit_two_phase(self, entry: MapEntry, chain: List[Tasklet],
                        spec: GridSpec):
        """Two-phase grid kernel for scopes with in-kernel wcr edges: each
        grid step runs the producer phase over the whole tile, reduces the
        contribution over the intra-tile reduction axes, and accumulates it
        in a VMEM scratch; on the last reduction step the consumer phase
        runs once over the kept lattice with the finished values (the
        ``@pl.when`` phase flip of the hand-written reduction kernels)."""
        import numpy as np
        interpret = resolve_interpret(self.sdfg.metadata.get("pallas_interpret"))
        grid_names = [p for p, _ in spec.grid]
        grid_sizes = tuple(n for _, n in spec.grid)
        block_order = [q for q, _ in spec.block_params]
        bp = dict(spec.block_params)
        tile_shape = tuple(n for _, n in spec.block_params)

        op_reps, op_of_edge, in_vals, in_specs, places = \
            self._input_operands(spec)

        out_specs, out_shapes, out_views = self._output_operands(spec)

        kept_intra = set(spec.internal_wcr[0].kept_intra)
        kept_order = [q for q in block_order if q in kept_intra]
        kept_shape = tuple(bp[q] for q in kept_order)
        red_axes = tuple(i for i, q in enumerate(block_order)
                         if q not in kept_intra)
        reduction = spec.internal_wcr[0].reduction
        scratch_shapes = [pltpu.VMEM(kept_shape or (1,), np.dtype(w.dtype))
                          for w in spec.internal_wcr]

        chain1_call, chain2_call = self._phased_runners(chain, spec)
        n_ops, n_out = len(op_reps), len(spec.outputs)

        def kernel(*refs):
            ins = refs[:n_ops]
            outs = refs[n_ops:n_ops + n_out]
            accs = refs[n_ops + n_out:]
            ids = [pl.program_id(k) for k in range(len(grid_names))]
            id_env = dict(zip(grid_names, ids))
            opvals = self._load_operands(spec, ins, op_of_edge, places,
                                         block_order, id_env)

            if block_order:
                f1 = chain1_call
                for q in reversed(block_order):
                    axes = {i: (0 if q in dict(es.fact.param_dims) else None)
                            for i, es in enumerate(spec.inputs)}
                    f1 = jax.vmap(f1, in_axes=(axes,), out_axes=0)
                vals1 = f1(opvals)
            else:
                vals1 = chain1_call(opvals)

            red_pos = [grid_names.index(p) for p in reduction]
            first = _conds(ids, red_pos, grid_sizes, at_end=False)
            last = _conds(ids, red_pos, grid_sizes, at_end=True)
            for w, acc, v in zip(spec.internal_wcr, accs, vals1):
                part = wcr_reduce(w.wcr, v, red_axes) if red_axes else v
                part = jnp.reshape(part, acc.shape)

                @pl.when(first)
                def _init(acc=acc, w=w):
                    acc[...] = jnp.full(acc.shape,
                                        wcr_identity(w.wcr, acc.dtype))

                acc[...] = wcr_combine(w.wcr, acc[...],
                                       part.astype(acc.dtype))

            @pl.when(last)
            def _consume():
                acc_vals = tuple(jnp.reshape(acc[...], kept_shape)
                                 for acc in accs)
                if kept_order:
                    f2 = chain2_call
                    for q in reversed(kept_order):
                        axes = {i: (0 if q in dict(es.fact.param_dims)
                                    else None)
                                for i, es in enumerate(spec.inputs)}
                        f2 = jax.vmap(f2, in_axes=(axes, 0), out_axes=0)
                    results = f2(opvals, acc_vals)
                else:
                    results = chain2_call(opvals, acc_vals)
                for oi, (es, oref) in enumerate(zip(spec.outputs, outs)):
                    val = jnp.asarray(results[oi])
                    if block_order:
                        # kept-lattice result -> full tile lattice (the
                        # broadcast lanes collapse again in assembly)
                        trail = val.shape[len(kept_order):]
                        val = jnp.reshape(
                            val, tuple(bp[q] if q in kept_intra else 1
                                       for q in block_order) + trail)
                        val = jnp.broadcast_to(val, tile_shape + trail)
                    val = self._assemble_block(val, es, block_order)
                    self._store(oref, val, es, id_env, out_views[oi])

        results = pl.pallas_call(
            kernel, grid=grid_sizes, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shapes, scratch_shapes=scratch_shapes,
            interpret=interpret)(*in_vals)
        self._stitch_results(spec, results)

    def _stitch_results(self, spec: GridSpec, results):
        """Stitch each written box into the prior container contents:
        grid kernels only define the blocks their index maps touch.
        Re-fetch per output: two edges may target the same container."""
        if not isinstance(results, (list, tuple)):
            results = (results,)
        for es, new in zip(spec.outputs, results):
            prev = jnp.asarray(self.ensure_value(es.data))
            if es.scalar:
                prev = jnp.reshape(prev, (1,))
            new = jnp.reshape(new, prev.shape)  # 1-D outputs come as rows
            sl = tuple(slice(lo, hi) for lo, hi in es.box)
            if es.wcr in WCR_MODES:
                cur = _apply_wcr(prev.at[sl], es.wcr, new[sl])
            elif all((lo, hi) == (0, s) for (lo, hi), s
                     in zip(es.box, prev.shape)):
                cur = new
            else:
                cur = prev.at[sl].set(new[sl])
            if es.scalar:
                cur = jnp.reshape(cur, ())
            self.env[es.data] = cur

    @staticmethod
    def _assemble_block(val, es: EdgeSpec, block_order: List[str]):
        """Rearrange a whole-block or (vmapped) tasklet result — leading
        axes one per intra-tile param, trailing axes the tasklet's own
        result dims — into the output's effective block shape."""
        pd = dict(es.fact.param_dims)
        eff = es.fact.effective_shape()
        absent = tuple(i for i, q in enumerate(block_order) if q not in pd)
        if absent:
            if es.wcr in WCR_MODES:  # intra-block reduction
                val = wcr_reduce(es.wcr, val, absent)
            else:  # revisited location: last write wins, as sequentially
                idx = tuple(-1 if i in absent else slice(None)
                            for i in range(len(block_order)))
                val = val[idx]
        present = [q for q in block_order if q in pd]
        nlead = len(present)
        trailing = list(range(nlead, jnp.ndim(val)))
        slice_dims = [d for d in range(len(eff))
                      if d not in pd.values() and eff[d] > 1]
        if len(trailing) == len(slice_dims) and (present or trailing):
            src_of = {pd[q]: i for i, q in enumerate(present)}
            src_of.update({d: t for d, t in zip(slice_dims, trailing)})
            perm = [src_of[d] for d in sorted(src_of)]
            val = jnp.transpose(val, perm)
        return jnp.reshape(val, eff)


def build_callable(sdfg: SDFG):
    """Build fn(**arrays) using the Pallas grid lowering strategy."""
    return _build_callable(sdfg, lowering=PallasStateLowering)
