"""Vectorized twin of the faithful engine.

The paper's DSE sweeps hardware parameters (#PEs, NoC bandwidth, buffer
sizes) holding (layer × dataflow) fixed.  Because the analysis in
``model.py`` is written against the backend facade, the *same code* runs
with hardware parameters as traced jnp scalars: layer dims, directive
sizes, temporal trip counts and the iteration-case structure stay static
Python ints (hybrid backend), while everything touched by ``num_pes`` /
``noc_bw`` becomes part of one small jit graph.  ``vmap`` then evaluates
the whole design grid in a single fused XLA computation — this is the
beyond-paper optimization that lifts the DSE rate orders of magnitude above
the paper's 0.17M designs/s (see EXPERIMENTS.md §Perf-A).

Output is a flat, fixed-shape feature vector per design point so the DSE
can stack millions of them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .cluster_analysis import build_dense_level, hybrid_backend
from .directives import Cluster, Dataflow
from .model import analyze, analyze_dense_level, assemble_stats, \
    blend_level_results
from .performance import HWConfig
from .tensor_analysis import LayerOp

# Feature vector layout produced by the traced evaluator.
FEATURES = ("runtime", "energy_pj", "macs", "l1_kb", "l2_kb", "util",
            "bw_req", "throughput", "edp")


def _features(s) -> jnp.ndarray:
    """Pack a Stats object into the fixed FEATURES vector (traceable)."""
    runtime = jnp.asarray(s.runtime, jnp.float32)
    energy = jnp.asarray(s.energy_pj, jnp.float32)
    macs = jnp.asarray(s.total_macs, jnp.float32)
    return jnp.stack([
        runtime,
        energy,
        macs,
        jnp.asarray(s.l1_req_kb, jnp.float32),
        jnp.asarray(s.l2_req_kb, jnp.float32),
        jnp.asarray(s.utilization, jnp.float32),
        jnp.asarray(s.peak_bw.get(0, 0), jnp.float32),
        macs / runtime,
        energy * runtime,
    ])


def stats_vector(op: LayerOp, df: Dataflow, hw: HWConfig) -> jnp.ndarray:
    """One design point -> fixed-shape feature vector (traceable)."""
    xp = hybrid_backend()
    return _features(analyze(op, df, hw, xp=xp))


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under a stable ``__name__``: ``jax.jit``/``jax.pmap`` name
    the XLA module after it (``jit_<name>``), so a device trace tells the
    executable families apart."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _family(kind: str, spec: "UniversalSpec") -> str:
    """``universal_<kind>_l<levels>``, with ``_ext`` for the
    layer-shape-as-operand (netspace) variant."""
    return (f"universal_{kind}_l{spec.n_levels}"
            + ("_ext" if spec.ext_operand else ""))


@functools.lru_cache(maxsize=512)
def _build_eval(op_key, df_key, multicast: bool, reduction: bool,
                latency: float, macs_per_pe: int) -> Callable:
    op, df = _OP_REG[op_key], _DF_REG[df_key]

    def eval_one(num_pes, noc_bw):
        hw = HWConfig(num_pes=num_pes, noc_bw=noc_bw,
                      noc_latency=latency, multicast=multicast,
                      spatial_reduction=reduction,
                      macs_per_pe=macs_per_pe)
        return stats_vector(op, df, hw)

    return jax.jit(_named(jax.vmap(eval_one), "dse_grid"))


# jit-cache registries keyed by object identity (LayerOp/Dataflow are
# frozen-ish dataclasses holding dicts — not hashable — so we key by repr).
_OP_REG: dict[str, LayerOp] = {}
_DF_REG: dict[str, Dataflow] = {}


def _reg(op: LayerOp, df: Dataflow) -> tuple[str, str]:
    ok = f"{op.name}|{sorted(op.dims.items())}|{op.op_type}"
    dk = f"{df.name}|{df.directives}"
    _OP_REG[ok] = op
    _DF_REG[dk] = df
    return ok, dk


def batched_evaluator(op: LayerOp, df: Dataflow, *, multicast: bool = True,
                      spatial_reduction: bool = True,
                      noc_latency: float = 2.0,
                      macs_per_pe: int = 1) -> Callable:
    """Returns ``f(num_pes[i], noc_bw[i]) -> features[i, F]``, jit+vmap'd.

    The returned callable evaluates the full MAESTRO analysis for every
    design point in one XLA executable."""
    ok, dk = _reg(op, df)
    return _build_eval(ok, dk, multicast, spatial_reduction, noc_latency,
                       macs_per_pe)


@dataclasses.dataclass
class BatchStats:
    """Columnar stats for a batch of design points."""
    runtime: Any
    energy_pj: Any
    macs: Any
    l1_kb: Any
    l2_kb: Any
    util: Any
    bw_req: Any
    throughput: Any
    edp: Any

    @classmethod
    def from_features(cls, feats) -> "BatchStats":
        cols = {name: feats[..., i] for i, name in enumerate(FEATURES)}
        return cls(**{
            "runtime": cols["runtime"], "energy_pj": cols["energy_pj"],
            "macs": cols["macs"], "l1_kb": cols["l1_kb"],
            "l2_kb": cols["l2_kb"], "util": cols["util"],
            "bw_req": cols["bw_req"], "throughput": cols["throughput"],
            "edp": cols["edp"]})


def evaluate_grid(op: LayerOp, df: Dataflow, num_pes, noc_bw,
                  **kw) -> BatchStats:
    """Evaluate (layer × dataflow) over arrays of hardware design points."""
    f = batched_evaluator(op, df, **kw)
    feats = f(jnp.asarray(num_pes), jnp.asarray(noc_bw))
    return BatchStats.from_features(feats)


# ----------------------------------------------------------------------
# Tile-size-traced twin: the mapping-space axis (repro.mapspace)
# ----------------------------------------------------------------------
#
# The hardware DSE above holds the dataflow fixed and traces (num_pes,
# noc_bw).  The mapping search needs the dual: hardware fixed, *tile sizes*
# traced, so thousands of candidate mappings that share one directive
# structure (same dims, order, spatial choice, cluster nesting) run through
# a single jit+vmap executable.  Trip counts, iteration-case occurrences and
# tile volumes all become traced values; the case *structure* (number of
# cases, loop order) stays static per template, which is exactly what the
# mapspace engine groups candidates by.
#
# Sizes are traced as float32: volume products reach ~1e10 on real layers,
# which would overflow int32 (JAX's default int width).  Small-integer phase
# arithmetic (trip counts, equality tests) stays exact in float32 far beyond
# any realistic dim extent (< 2^24).

@functools.lru_cache(maxsize=512)
def _build_tile_eval(op_key, df_key, var_slots: tuple[int, ...],
                     num_pes: int, noc_bw: float, multicast: bool,
                     reduction: bool, latency: float,
                     macs_per_pe: int) -> Callable:
    op, template = _OP_REG[op_key], _DF_REG[df_key]
    hw = HWConfig(num_pes=num_pes, noc_bw=noc_bw, noc_latency=latency,
                  multicast=multicast, spatial_reduction=reduction,
                  macs_per_pe=macs_per_pe)

    def eval_one(sizes, offsets):
        sizes = sizes.astype(jnp.float32)
        offsets = offsets.astype(jnp.float32)
        dirs = list(template.directives)
        for j, slot in enumerate(var_slots):
            d = dirs[slot]
            if isinstance(d, Cluster):
                dirs[slot] = Cluster(sizes[j])
            else:
                dirs[slot] = type(d)(sizes[j], offsets[j], d.dim)
        df = Dataflow(template.name, tuple(dirs))
        return stats_vector(op, df, hw)

    return jax.jit(_named(jax.vmap(eval_one), "tile_features"))


def batched_tile_evaluator(op: LayerOp, template: Dataflow,
                           var_slots: tuple[int, ...], *,
                           num_pes: int, noc_bw: float,
                           multicast: bool = True,
                           spatial_reduction: bool = True,
                           noc_latency: float = 2.0,
                           macs_per_pe: int = 1) -> Callable:
    """Returns ``f(sizes[i, S], offsets[i, S]) -> features[i, F]``.

    ``template`` is a structurally-complete directive program whose
    directives at positions ``var_slots`` have placeholder size/offset; the
    evaluator substitutes row ``i`` of the operand arrays for them (a
    ``Cluster`` slot consumes only its size column).  Hardware parameters
    are static per executable — the mapping search runs at a fixed reference
    design, and the co-DSE re-enters :func:`batched_evaluator` with the
    winning concrete mappings."""
    ok, dk = _reg(op, template)
    return _build_tile_eval(ok, dk, tuple(var_slots), int(num_pes),
                            float(noc_bw), multicast, spatial_reduction,
                            noc_latency, macs_per_pe)


# ----------------------------------------------------------------------
# Universal structure-as-operand evaluator: one XLA compile per
# (op × level-count) for the WHOLE mapping space
# ----------------------------------------------------------------------
#
# The tile-traced twin above still compiles once per (spatial × perm ×
# cluster) structure group, because loop order and spatial choice are
# Python-level structure of the directive program.  The universal evaluator
# moves that structure into operands too:
#
#   * the loop permutation is a *rank vector* (per searched axis, its
#     position in the data-movement order) — "innermost coupled loop" and
#     "advancing loop" become one-hot gathers over ranks;
#   * the spatial-dim choice is a *one-hot selector* blending each axis's
#     temporal and spatial phase quantities;
#   * the cluster option is a traced cluster size plus a one-hot over the
#     space's (inner dim, inner map) candidates;
#   * hardware (#PEs, NoC bandwidth) are traced per row, so a joint
#     mapping × hardware frontier runs through the same executable.
#
# Per-dim quantities are computed densely over the op's full dim universe
# (unused dims are trip-count-1 loops, exactly like ``complete()``), so a
# single jit+vmap executable per (op, level-count) evaluates every mapping
# in the space — the per-group compile cost becomes O(1).

@dataclasses.dataclass(frozen=True)
class UniversalSpec:
    """Static structure of one universal executable: everything that is
    *not* an operand.  ``cluster`` lists the (inner_dim, inner_size,
    inner_offset) candidates of the 2-level family; empty = 1 level."""
    dim_names: tuple[str, ...]
    axis_dims: tuple[str, ...]
    pinned: tuple[str, ...]
    cluster: tuple[tuple[str, int, int], ...] = ()
    # divisor-tiled spaces: only the spatial axis can produce a non-empty
    # edge phase, so case enumeration shrinks from 2^A to A+1
    single_edge: bool = False
    # layer shape as operand (repro.netspace): dim extents come from an
    # ``ext`` (i, D) operand row instead of ``op.dims``, and the cluster
    # candidates' inner size/offset from ``cin_size``/``cin_off`` (i, K)
    # rows — so ONE executable per op-class covers every layer shape of a
    # network (the ``cluster`` entries then carry only the inner-dim
    # identity; their static size/offset fields are ignored)
    ext_operand: bool = False

    @property
    def n_levels(self) -> int:
        return 2 if self.cluster else 1


def _universal_eval_one(op: LayerOp, spec: UniversalSpec, hw_static: dict):
    """Build the single-row evaluator closed over static structure."""
    axis_dims = spec.axis_dims
    a = len(axis_dims)
    missing = [d for d in spec.dim_names
               if d not in axis_dims and d not in spec.pinned]

    def eval_one(ops):
        xp = hybrid_backend()
        hw = HWConfig(num_pes=ops["pes"], noc_bw=ops["bw"], **hw_static)
        if spec.ext_operand:
            ext0 = {d: ops["ext"][j]
                    for j, d in enumerate(spec.dim_names)}
        else:
            ext0 = {d: op.dims[d] for d in spec.dim_names}
        sizes: dict = dict(ext0)   # non-searched dims: fully unrolled
        offsets: dict = dict(ext0)
        rank: dict = {}
        sp: dict = {d: 0 for d in spec.dim_names}
        for j, d in enumerate(axis_dims):
            sizes[d] = ops["sizes"][j]
            offsets[d] = ops["offsets"][j]
            rank[d] = ops["rank"][j]
            sp[d] = ops["sp"][j]
        # loop order mirrors the grouped templates: implicit (missing) dims
        # outermost, searched axes in permutation order, pinned window dims
        # innermost.  Trip-count-1 loops only need order-consistent ranks.
        for i, d in enumerate(missing):
            rank[d] = -1 - i
        for j, d in enumerate(spec.pinned):
            rank[d] = a + j

        pes = xp.maximum(ops["pes"], 1)
        if spec.cluster:
            c_eff = xp.maximum(xp.minimum(ops["csize"], pes), 1)
            top_units = xp.maximum(xp.floordiv(pes, c_eff), 1)
        else:
            c_eff = None
            top_units = pes

        level0 = build_dense_level(
            xp, op, index=0, ext=ext0, sizes=sizes, offsets=offsets,
            rank=rank, sp=sp, loop_dims=spec.dim_names,
            edge_dims=axis_dims, n_units=top_units,
            innermost=not spec.cluster, single_edge=spec.single_edge)

        if spec.cluster:
            def child_fn(m_unit):
                results = []
                for ki, (cd, csz, coff) in enumerate(spec.cluster):
                    if spec.ext_operand:
                        csz = ops["cin_size"][ki]
                        coff = ops["cin_off"][ki]
                    lvl1 = build_dense_level(
                        xp, op, index=1, ext=m_unit, sizes={cd: csz},
                        offsets={cd: coff}, rank={cd: 0}, sp={cd: 1},
                        loop_dims=(cd,), edge_dims=(cd,), n_units=c_eff,
                        innermost=True)
                    results.append(
                        analyze_dense_level(op, lvl1, xp, hw))
                if len(results) == 1:
                    return results[0]
                return blend_level_results(xp, ops["csel"], results)
            top = analyze_dense_level(op, level0, xp, hw,
                                      child_fn=child_fn)
        else:
            top = analyze_dense_level(op, level0, xp, hw)
        return _features(
            assemble_stats(op, top, spec.n_levels, hw, xp))

    return eval_one


# ----------------------------------------------------------------------
# Fused on-device reduction tail: top-k + Pareto inside the executable
# ----------------------------------------------------------------------
#
# The universal evaluator above returns the full (n, F) feature matrix,
# which makes the *host* the bottleneck of a large DSE: every chunk copies
# n x F floats back and the objective/top-k/Pareto reduction runs in numpy.
# The reduced evaluator fuses that reduction into the same XLA program:
# each chunk returns the scalar objective column (optional), the k winner
# rows, and a within-chunk Pareto-candidate mask over (energy, throughput)
# — a few scalars per design instead of the feature matrix.  An optional
# hardware tail folds the co-DSE's area/power/leakage accounting
# (``core.dse.run_dse`` semantics) into the jit so a joint mapping x
# hardware sweep needs no host post-processing either.  Chunks can stripe
# across local devices via ``jax.pmap`` (``n_devices > 1``).

@dataclasses.dataclass(frozen=True)
class HWTail:
    """Static hardware-accounting tail (mirrors ``core.dse.run_dse``):
    SRAM = l1*pes + l2, area/power from the RTL-regression model, leakage
    energy added to the energy/EDP columns, budget-invalid designs masked
    out of the objective and the frontier."""
    area_power: Any               # energy.AreaPowerModel (frozen, hashable)
    area_budget_mm2: float
    power_budget_mw: float


@dataclasses.dataclass(frozen=True)
class ReduceSpec:
    """Static reduction structure: objective column (canonical minimize),
    top-k width, and optional extras."""
    objective: str                # FEATURES name
    maximize: bool = False
    k: int = 8
    return_vals: bool = True      # per-row objective column (search needs
    #                               it; the paper-scale sweep does not)
    pareto: bool = True           # (energy, throughput) candidate mask
    hw: HWTail | None = None
    cols: tuple[str, ...] = ()    # extra per-row FEATURES columns to ship
    #                               back (netspace's DP composer needs the
    #                               (runtime, energy, l1, l2) of EVERY
    #                               candidate, not just the top-k rows)


def _reduce_tail(reduce: ReduceSpec, feats, ops):
    """The traced reduction: runs on (block, F) features of one shard."""
    live = ops["live"] > 0                       # padding rows never win
    obj_i = FEATURES.index(reduce.objective)
    runtime = feats[:, FEATURES.index("runtime")]
    valid = live
    if reduce.hw is not None:
        ap = reduce.hw.area_power
        pes, bw = ops["pes"], ops["bw"]
        l1 = feats[:, FEATURES.index("l1_kb")]
        l2 = feats[:, FEATURES.index("l2_kb")]
        sram_kb = l1 * pes + l2
        area = ap.area(pes, sram_kb, bw)
        power = ap.power(pes, sram_kb, bw)
        valid = live & (area <= reduce.hw.area_budget_mm2) \
            & (power <= reduce.hw.power_budget_mw)
        energy = feats[:, FEATURES.index("energy_pj")] \
            + ap.static_energy_pj(area, runtime)
        feats = feats.at[:, FEATURES.index("energy_pj")].set(energy)
        feats = feats.at[:, FEATURES.index("edp")].set(energy * runtime)
    obj = feats[:, obj_i]
    if reduce.maximize:
        obj = -obj
    obj = jnp.where(jnp.isfinite(obj) & valid, obj, jnp.inf)
    k = min(reduce.k, feats.shape[0])
    # the cross-shard merge needs ties broken low-index-first for
    # 1-vs-N-device determinism: sort on (value, index) rather than rely
    # on how a backend's top_k orders equal values
    idx = jnp.arange(obj.shape[0], dtype=jnp.int32)
    top_v, top_idx = jax.lax.sort((obj, idx), num_keys=2)
    top_v, top_idx = top_v[:k], top_idx[:k]
    out = {
        "top_vals": top_v,
        "top_idx": top_idx,
        "top_feats": feats[top_idx],
        "n_valid": jnp.sum(valid),
    }
    if reduce.return_vals:
        out["vals"] = obj
    if reduce.cols:
        out["cols"] = feats[:, [FEATURES.index(c) for c in reduce.cols]]
    if reduce.pareto:
        e = feats[:, FEATURES.index("energy_pj")]
        t = feats[:, FEATURES.index("throughput")]
        e = jnp.where(valid & jnp.isfinite(e), e, jnp.inf)
        t = jnp.where(valid & jnp.isfinite(t), t, -jnp.inf)
        # sort-based frontier: O(n log n), not O(n^2) pairwise
        order = jnp.argsort(e)
        ts = t[order]
        prev = jnp.concatenate(
            [jnp.full((1,), -jnp.inf, ts.dtype),
             jax.lax.cummax(ts)[:-1]])
        mask = jnp.zeros(e.shape, bool).at[order].set(ts > prev)
        out["pareto_mask"] = mask & valid
        out["pareto_energy"] = e
        out["pareto_thr"] = t
    return out


@functools.lru_cache(maxsize=256)
def _build_reduced(op_key: str, spec: UniversalSpec, reduce: ReduceSpec,
                   multicast: bool, reduction: bool, latency: float,
                   macs_per_pe: int, n_devices: int) -> Callable:
    op = _OP_REG[op_key]
    hw_static = dict(noc_latency=latency, multicast=multicast,
                     spatial_reduction=reduction, macs_per_pe=macs_per_pe)
    eval_one = _universal_eval_one(op, spec, hw_static)

    def chunk_fn(ops):
        feats = jax.vmap(eval_one)(
            {k: v for k, v in ops.items() if k != "live"})
        return _reduce_tail(reduce, feats, ops)

    _named(chunk_fn, _family("reduced", spec))
    if n_devices > 1:
        return jax.pmap(chunk_fn)
    return jax.jit(chunk_fn)


def universal_reduced_evaluator(op: LayerOp, spec: UniversalSpec,
                                reduce: ReduceSpec, *,
                                multicast: bool = True,
                                spatial_reduction: bool = True,
                                noc_latency: float = 2.0,
                                macs_per_pe: int = 1,
                                n_devices: int = 1) -> Callable:
    """Returns the fused evaluate-and-reduce executable.

    Input is the universal operand dict plus a ``live`` (i,) float mask
    (0 = padding row).  With ``n_devices > 1`` every array carries a
    leading device axis ``(D, block, ...)`` and the executable is a pmap —
    each device reduces its shard; the caller merges the per-shard top-k /
    frontier candidates (by (value, global index), which is deterministic
    for any device count).  Output per shard:

    ``top_vals``/``top_idx``/``top_feats``
        the k best rows by the canonicalized (minimized) objective;
    ``vals`` (optional)
        the full objective column — one scalar per design, NOT the
        (n, F) feature matrix;
    ``pareto_mask``/``pareto_energy``/``pareto_thr`` (optional)
        within-shard Pareto-candidate mask over (energy min, throughput
        max) plus the two columns for host-side frontier refinement;
    ``n_valid``
        count of live (and, with a hardware tail, budget-valid) rows."""
    ok = f"{op.name}|{sorted(op.dims.items())}|{op.op_type}"
    _OP_REG[ok] = op
    return _build_reduced(ok, spec, reduce, multicast, spatial_reduction,
                          noc_latency, macs_per_pe, n_devices)


@functools.lru_cache(maxsize=256)
def _build_universal(op_key: str, spec: UniversalSpec, multicast: bool,
                     reduction: bool, latency: float,
                     macs_per_pe: int) -> Callable:
    op = _OP_REG[op_key]
    hw_static = dict(noc_latency=latency, multicast=multicast,
                     spatial_reduction=reduction, macs_per_pe=macs_per_pe)
    return jax.jit(_named(jax.vmap(_universal_eval_one(op, spec, hw_static)),
                          _family("features", spec)))


def universal_evaluator(op: LayerOp, spec: UniversalSpec, *,
                        multicast: bool = True,
                        spatial_reduction: bool = True,
                        noc_latency: float = 2.0,
                        macs_per_pe: int = 1) -> Callable:
    """Returns ``f(ops) -> features[i, F]`` where ``ops`` is a dict of
    per-row operand arrays encoding the ENTIRE mapping plus the hardware
    point:

    ``sizes``/``offsets`` (i, A)
        tile sizes / offsets per searched axis, canonical axis order;
    ``rank`` (i, A)
        each axis's position in the loop order (0 = outermost searched);
    ``sp`` (i, A)
        one-hot spatial-axis selector;
    ``csize`` (i,), ``csel`` (i, K)
        cluster size and one-hot over ``spec.cluster`` candidates
        (2-level specs only);
    ``pes``/``bw`` (i,)
        hardware design point per row (joint mapping × hardware search).

    One XLA executable per (op, level-count): every structure group of the
    mapping space is an operand pattern of the same compiled computation.
    See ``repro.mapspace.universal`` for the MapSpace-point encoder."""
    ok = f"{op.name}|{sorted(op.dims.items())}|{op.op_type}"
    _OP_REG[ok] = op
    return _build_universal(ok, spec, multicast, spatial_reduction,
                            noc_latency, macs_per_pe)
