"""Static audit of every universal executable family the engine builds.

The engine's whole performance story rests on a handful of jitted
"universal" executables (one per (op-class, level-count) family —
``mapspace.universal``, the netspace shape-as-operand variant, the
co-DSE hardware tail).  This auditor traces each of them with
``jax.make_jaxpr`` — tracing only, no XLA compile — and asserts the
invariants the engine's numerics and compile budget depend on:

``JAX-F64``
    no 64-bit array appears anywhere in the trace (the evaluator is
    float32 end-to-end; one stray Python float in the wrong place turns
    the whole pipeline f64 under x64 mode);
``JAX-WIDEN``
    no silent ``convert_element_type`` widening within a kind (f32→f64,
    i32→i64) — the classic source of accidental precision/cost creep;
``JAX-CALLBACK``
    no host callback primitive on the hot path (a ``pure_callback``
    would serialize every chunk through Python);
``JAX-WEAKTYPE``
    no weakly-typed output aval (a weak-type leak means some retrace
    will specialize differently on the next Python scalar and recompile);
``JAX-CONSTFOLD``
    every operand array is actually *used* by the traced computation —
    an ignored operand means a value that should be vmapped got baked in
    as a static constant, i.e. a recompile per value;
``JAX-SHRINK``
    the fused evaluate-and-reduce tail shrinks: total output bytes stay
    under half the input bytes, so each chunk ships back a fraction of
    what it was sent;
``JAX-PRIMBUDGET``
    the traced primitive count per family stays under a checked-in
    budget (``PRIMITIVE_BUDGET``), the compile-time analog of the
    BENCH_mapspace compile-seconds budget;
``JAX-TRACE``
    the family traces at all (a trace error is itself a finding, not a
    crash).

The audit corpus mirrors what CI actually compiles: a small conv2d and
a gemm, 1-level and 2-level specs, in plain / reduced / co-DSE /
netspace(ext-operand) variants, at 1 and ``jax.local_device_count()``
devices (the pmap path).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

from .findings import Finding

# Traced-primitive budget per audit family, measured over every variant
# in the corpus (max: conv:L1 3883, conv:L2 13299, gemm:L1 1201,
# gemm:L2 2695) with ~50% headroom.  A budget miss means an engine
# change materially grew the program XLA must optimize — raise the
# budget consciously, in review, like the compile-seconds budget in
# BENCH_mapspace.
PRIMITIVE_BUDGET = {
    "audit-conv:L1": 5800,
    "audit-conv:L2": 20000,
    "audit-gemm:L1": 1800,
    "audit-gemm:L2": 4100,
}
# Fallback for families outside the checked-in corpus (custom audits).
_DEFAULT_BUDGET = {"L1": 6000, "L2": 20000}

_WIDTHS = {"float64", "int64", "uint64", "complex128"}


def _budget_for(family: str) -> int:
    return PRIMITIVE_BUDGET.get(
        family, _DEFAULT_BUDGET["L2" if family.endswith(":L2") else "L1"])


@dataclasses.dataclass(frozen=True)
class FamilyCase:
    """One traced executable: the wrapped (jit/pmap) callable, its
    operand pytree, and — when the unused-operand check applies — the
    unwrapped vmap composition the jit would hide."""
    name: str                     # e.g. "audit-conv:L2/codse"
    family: str                   # family label, e.g. "audit-conv:L2"
    fn: Callable
    ops: dict[str, np.ndarray]
    kind: str                     # plain | reduced | codse | netspace
    unwrapped: Callable | None = None
    unwrapped_ops: dict[str, np.ndarray] | None = None
    # operands the unused-operand check tolerates: a one-hot over ONE
    # cluster candidate carries no information, so the evaluator
    # rightly drops it at trace time — that is not a recompile hazard
    allow_unused: tuple[str, ...] = ()


# ----------------------------------------------------------------------
# Corpus: the families CI compiles, at trace-only cost
# ----------------------------------------------------------------------

def _audit_ops():
    from ..core.tensor_analysis import conv2d, gemm
    return [conv2d("audit-conv", k=8, c=6, y=10, x=10, r=3, s=3),
            gemm("audit-gemm", m=32, n=64, k=64)]


def _points(space, *, cluster: bool, n: int) -> list[tuple]:
    """n valid points of one level-count family (minimum tiles)."""
    cs = [i for i, c in enumerate(space.cluster_options)
          if (c is not None) == cluster]
    base = (0,) * len(space.axes)
    pts = [(s, p, c) + base
           for s in range(len(space.spatial_choices))
           for p in range(len(space.perms))
           for c in cs]
    return (pts * (n // len(pts) + 1))[:n]


def _with_live(ops: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    n = len(ops["pes"])
    return dict(ops, live=np.ones((n,), np.float32))


def _ext_ops(op, spec, ops: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Extend base operands with the netspace shape-as-operand columns."""
    n = len(ops["pes"])
    ext = np.asarray([op.dims[d] for d in spec.dim_names], np.float32)
    out = dict(ops, ext=np.tile(ext, (n, 1)))
    if spec.cluster:
        out["cin_size"] = np.tile(np.asarray(
            [c[1] for c in spec.cluster], np.float32), (n, 1))
        out["cin_off"] = np.tile(np.asarray(
            [c[2] for c in spec.cluster], np.float32), (n, 1))
    return out


def _shard(ops: dict[str, np.ndarray], nd: int) -> dict[str, np.ndarray]:
    """Add the leading device axis the pmap executable expects (the
    1-device executable is a jit and takes the flat batch as-is)."""
    if nd <= 1:
        return ops
    return {k: v.reshape((nd, len(v) // nd) + v.shape[1:])
            for k, v in ops.items()}


def _unwrapped_reduced(op, spec, reduce):
    """The exact composition ``_build_reduced`` jits — traced bare so the
    jaxpr's invars line up 1:1 with the operand dict and an ignored
    operand is visible (jit would still thread it through the pjit eqn)."""
    import jax
    from ..core.vectorized import _reduce_tail, _universal_eval_one
    hw_static = dict(noc_latency=2.0, multicast=True,
                     spatial_reduction=True, macs_per_pe=1)
    eval_one = _universal_eval_one(op, spec, hw_static)

    def chunk_fn(ops):
        feats = jax.vmap(eval_one)(
            {k: v for k, v in ops.items() if k != "live"})
        return _reduce_tail(reduce, feats, ops)

    return chunk_fn


def build_cases(n_devices: int = 1) -> list[FamilyCase]:
    """The audit corpus at one device count.  ``n_devices > 1`` builds
    the pmap variants of the reduced executables (the plain/unwrapped
    single-shard cases are device-count independent)."""
    from ..core.dse import DSEConfig
    from ..core.vectorized import (HWTail, ReduceSpec,
                                   universal_evaluator,
                                   universal_reduced_evaluator)
    from ..mapspace.space import build_space
    from ..mapspace.universal import encode_points, universal_specs

    # large enough that the O(n) terms of the shrink check
    # dominate the O(k) top-k constants, as they do at real block sizes
    n = 256
    n -= n % n_devices
    cfg = DSEConfig()
    reduce = ReduceSpec(objective="edp", k=4)
    codse = dataclasses.replace(reduce, hw=HWTail(
        area_power=cfg.area_power, area_budget_mm2=cfg.area_budget_mm2,
        power_budget_mw=cfg.power_budget_mw))
    net_reduce = ReduceSpec(objective="runtime", k=1, pareto=False,
                            cols=("runtime", "energy_pj", "l1_kb", "l2_kb"))
    cases: list[FamilyCase] = []
    for op in _audit_ops():
        space = build_space(op)
        for spec in universal_specs(op, space):
            if spec is None:
                continue
            fam = f"{op.name}:L{2 if spec.cluster else 1}"
            pts = _points(space, cluster=bool(spec.cluster), n=n)
            base = encode_points(op, space, pts, spec,
                                 num_pes=64, noc_bw=32.0)
            live = _with_live(base)
            sharded = _shard(live, n_devices)
            nspec = dataclasses.replace(spec, ext_operand=True)
            nops = _shard(_with_live(_ext_ops(op, nspec, base)), n_devices)
            tolerate = ("csel",) if len(spec.cluster) == 1 else ()

            if n_devices == 1:
                cases.append(FamilyCase(
                    name=f"{fam}/plain", family=fam, kind="plain",
                    fn=universal_evaluator(op, spec), ops=base))
            for kind, rspec, fops in (("reduced", reduce, sharded),
                                      ("codse", codse, sharded)):
                cases.append(FamilyCase(
                    name=f"{fam}/{kind}" + (f"@{n_devices}dev"
                                            if n_devices > 1 else ""),
                    family=fam, kind=kind,
                    fn=universal_reduced_evaluator(
                        op, spec, rspec, n_devices=n_devices),
                    ops=fops,
                    unwrapped=_unwrapped_reduced(op, spec, rspec),
                    unwrapped_ops=live, allow_unused=tolerate))
            cases.append(FamilyCase(
                name=f"{fam}/netspace" + (f"@{n_devices}dev"
                                          if n_devices > 1 else ""),
                family=fam, kind="netspace",
                fn=universal_reduced_evaluator(
                    op, nspec, net_reduce, n_devices=n_devices),
                ops=nops,
                unwrapped=_unwrapped_reduced(op, nspec, net_reduce),
                unwrapped_ops=_with_live(_ext_ops(op, nspec, base)),
                allow_unused=tolerate))
    return cases


# ----------------------------------------------------------------------
# Jaxpr checks
# ----------------------------------------------------------------------

def _sub_jaxprs(params: dict):
    from jax.extend import core
    for v in params.values():
        if isinstance(v, core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for w in v:
                if isinstance(w, core.ClosedJaxpr):
                    yield w.jaxpr
                elif isinstance(w, core.Jaxpr):
                    yield w


def _walk_eqns(jaxpr):
    """Every eqn of a jaxpr and its nested sub-jaxprs (pjit bodies, pmap
    call_jaxprs, scan/cond branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk_eqns(sub)


def _dtype_of(v) -> Any:
    aval = getattr(v, "aval", None)
    return getattr(aval, "dtype", None)


def audit_case(case: FamilyCase) -> tuple[list[Finding], int]:
    """Findings + traced primitive count for one executable family
    variant."""
    import jax
    site = f"jaxpr::{case.name}"
    findings: list[Finding] = []

    def f(code: str, msg: str, severity: str = "error") -> None:
        findings.append(Finding(code=code, site=site, analyzer="jaxpr",
                                message=msg, severity=severity))

    try:
        closed = jax.make_jaxpr(case.fn)(case.ops)
    except Exception as e:                        # noqa: BLE001
        f("JAX-TRACE", f"{type(e).__name__}: {e}")
        return findings, 0

    n_prims = 0
    seen_f64: set[str] = set()
    seen_cb: set[str] = set()
    seen_widen: set[str] = set()
    for eqn in _walk_eqns(closed.jaxpr):
        n_prims += 1
        pname = eqn.primitive.name
        if "callback" in pname or "outside_call" in pname:
            seen_cb.add(pname)
        for v in eqn.outvars:
            dt = _dtype_of(v)
            if dt is not None and dt.name in _WIDTHS:
                seen_f64.add(f"{pname} -> {dt.name}")
        if pname == "convert_element_type":
            src = _dtype_of(eqn.invars[0])
            dst = eqn.params.get("new_dtype")
            if src is not None and dst is not None \
                    and np.dtype(dst).kind == np.dtype(src).kind \
                    and np.dtype(dst).itemsize > np.dtype(src).itemsize:
                seen_widen.add(f"{np.dtype(src).name} -> "
                               f"{np.dtype(dst).name}")
    for what in sorted(seen_f64):
        f("JAX-F64", f"64-bit value in the traced program: {what}")
    for what in sorted(seen_widen):
        f("JAX-WIDEN", f"silent convert_element_type widening: {what}")
    for what in sorted(seen_cb):
        f("JAX-CALLBACK", f"host callback on the hot path: {what}")
    for aval in closed.out_avals:
        leaves = aval if isinstance(aval, (tuple, list)) else [aval]
        for a in leaves:
            if getattr(a, "weak_type", False):
                f("JAX-WEAKTYPE",
                  f"weakly-typed output aval {a}: the next Python "
                  f"scalar retrace will recompile")

    budget = _budget_for(case.family)
    if n_prims > budget:
        f("JAX-PRIMBUDGET",
          f"{n_prims} traced primitives exceeds the "
          f"{case.family.split(':')[-1]} budget of {budget}")

    if case.unwrapped is not None:
        findings += _audit_unwrapped(case)
    if case.kind in ("reduced", "codse", "netspace"):
        findings += _audit_shrink(case, closed, site)
    return findings, n_prims


def _audit_unwrapped(case: FamilyCase) -> list[Finding]:
    """JAX-CONSTFOLD: trace the bare vmap composition and demand every
    operand leaf is consumed.  Dict pytrees flatten in sorted-key order,
    so jaxpr.invars line up with sorted(ops)."""
    import jax
    from jax.extend.core import Literal
    site = f"jaxpr::{case.name}"
    ops = case.unwrapped_ops or case.ops
    try:
        closed = jax.make_jaxpr(case.unwrapped)(ops)
    except Exception as e:                        # noqa: BLE001
        return [Finding(code="JAX-TRACE", site=site, analyzer="jaxpr",
                        message=f"unwrapped trace failed: "
                                f"{type(e).__name__}: {e}")]
    used: set[int] = set()

    def mark(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    used.add(id(v))
            for sub in _sub_jaxprs(eqn.params):
                mark(sub)
        for v in jaxpr.outvars:
            if not isinstance(v, Literal):
                used.add(id(v))

    mark(closed.jaxpr)
    findings = []
    keys = sorted(ops)
    for key, var in zip(keys, closed.jaxpr.invars):
        if key in case.allow_unused:
            continue
        if id(var) not in used:
            findings.append(Finding(
                code="JAX-CONSTFOLD", site=site, analyzer="jaxpr",
                message=f"operand {key!r} is ignored by the traced "
                        f"computation — its value must be baked in "
                        f"statically, a recompile per distinct value"))
    return findings


def _aval_bytes(avals) -> int:
    total = 0
    for a in avals:
        shape = getattr(a, "shape", None)
        dt = getattr(a, "dtype", None)
        if shape is None or dt is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
    return total


def _audit_shrink(case: FamilyCase, closed, site: str) -> list[Finding]:
    """JAX-SHRINK: the fused reduce must shrink its input, otherwise the
    device-to-host copy per chunk grows with the chunk."""
    in_b = _aval_bytes(closed.in_avals)
    out_b = _aval_bytes(closed.out_avals)
    if out_b * 2 > in_b:
        return [Finding(
            code="JAX-SHRINK", site=site, analyzer="jaxpr",
            message=f"reduce tail returns {out_b} B for {in_b} B of "
                    f"operands (> 1/2)")]
    return []


# ----------------------------------------------------------------------
# Entry point
# ----------------------------------------------------------------------

def audit(device_counts: tuple[int, ...] = (1,)
          ) -> tuple[list[Finding], dict[str, Any]]:
    """Run the full audit.  Returns ``(findings, report)`` where the
    report carries per-case traced primitive counts and the budget —
    the exact payload BENCH_mapspace embeds next to the compile
    budget."""
    findings: list[Finding] = []
    prim_counts: dict[str, int] = {}
    for nd in device_counts:
        for case in build_cases(nd):
            fs, n = audit_case(case)
            findings += fs
            prim_counts[case.name] = n
    report = {
        "primitive_counts": prim_counts,
        "primitive_budget": dict(PRIMITIVE_BUDGET),
        "device_counts": list(device_counts),
        "n_cases": len(prim_counts),
    }
    return findings, report
