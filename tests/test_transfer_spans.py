"""Every host second of a co-DSE query sits in a named leaf span.

Load-bearing properties:

  * on the query's thread no two leaf spans (the names
    ``obs.PHASE_OF_SPAN`` maps) overlap, so the ``timing`` phases stay
    disjoint and, with ``transfer`` among them, still sum to wall;
  * every ``h2d`` span carries the bytes of the operands the executable
    received, and every ``d2h`` span the bytes of the outputs it
    returned;
  * the co-DSE steps outside the gene pipeline have spans of their own
    (``hw-sweep``, ``point-encode``, ``frontier-merge``,
    ``design-gather``);
  * tracing changes no answer;
  * each executable's XLA module is named by its family.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import Hardware, Query, SearchSpec, Session, Workload
from repro.core import tensor_analysis as ta
from repro.core.vectorized import (ReduceSpec, universal_evaluator,
                                   universal_reduced_evaluator)
from repro.mapspace import universal
from repro.mapspace.space import build_space

OP = ta.conv2d("spans-conv", k=8, c=6, y=12, x=12, r=3, s=3)


def _query() -> Query:
    return Query(Workload.of_layer(OP),
                 Hardware(num_pes=48, noc_bw=12.0, pe_range=(16, 32, 64),
                          bw_range=(4.0, 8.0, 16.0)),
                 SearchSpec(objective="edp", budget=60, block=96, top_k=4,
                            codse_top_k=2, joint_genes=6))


def _nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


@pytest.fixture(scope="module")
def traced():
    """One warm co-DSE query run with tracing on, every executable call
    recorded with the bytes it took in and gave back."""
    session = Session(jax_cache=False)
    session.run(_query())                 # compiles: the traced run is warm
    calls: list[tuple[int, int]] = []

    def recording(build):
        def make(*a, **kw):
            f = build(*a, **kw)

            def call(ops):
                out = f(ops)
                calls.append((_nbytes(ops), _nbytes(out)))
                return out
            return call
        return make

    mp = pytest.MonkeyPatch()
    for name in ("universal_evaluator", "universal_reduced_evaluator"):
        mp.setattr(universal, name, recording(getattr(universal, name)))
    obs.disable_tracing()
    tracer = obs.enable_tracing()
    try:
        rep = session.run(_query())
    finally:
        obs.disable_tracing()
        mp.undo()
    return rep, tracer.spans(), calls


def test_leaf_spans_never_overlap_on_the_query_thread(traced):
    _, spans, _ = traced
    (q,) = [e for e in spans if e["name"] == "query"]
    leaves = sorted((e["ts"], e["ts"] + e["dur"], e["name"])
                    for e in spans if e["name"] in obs.PHASE_OF_SPAN
                    and e["tid"] == q["tid"])
    assert leaves
    for (_, end, a), (start, _, b) in zip(leaves, leaves[1:]):
        # ts/dur are rounded to the nanosecond
        assert start >= end - 2e-3, (a, b, end - start)
    assert leaves[0][0] >= q["ts"]
    assert leaves[-1][1] <= q["ts"] + q["dur"] + 2e-3


@pytest.mark.parametrize("span,side", [("h2d", 0), ("d2h", 1)])
def test_transfer_spans_carry_the_arrays_bytes(traced, span, side):
    _, spans, calls = traced
    got = [e["args"]["bytes"] for e in sorted(spans, key=lambda e: e["ts"])
           if e["name"] == span]
    # one executable call per block, in the order the blocks were sent
    # and collected (no compile in the traced run, so no call repeats)
    assert got == [c[side] for c in calls]
    assert all(b > 0 for b in got)


@pytest.mark.parametrize("name", ["hw-sweep", "point-encode",
                                  "frontier-merge", "design-gather",
                                  "design-chunk", "encode", "dispatch",
                                  "device-pass", "topk-merge"])
def test_codse_steps_have_spans(traced, name):
    rep, spans, _ = traced
    found = [e for e in spans if e["name"] == name]
    assert found
    if name == "hw-sweep":          # one per swept search winner
        assert [e["args"]["mapping"] for e in found] == \
            [label for label, _ in rep.raw.dse]


def test_timing_has_transfer_and_sums_to_wall(traced):
    rep, _, _ = traced
    timing = rep.extras["timing"]
    phases = timing["phases"]
    assert phases.get("transfer", 0.0) > 0.0
    assert set(phases) <= set(obs.PHASE_NAMES)
    assert sum(phases.values()) == pytest.approx(timing["wall_s"],
                                                 abs=1e-5)
    assert list(obs.PHASE_NAMES).index("transfer") == \
        list(obs.PHASE_NAMES).index("encode") + 1


def test_tracing_changes_no_answer(traced):
    rep, _, _ = traced
    obs.disable_tracing()
    plain = Session(jax_cache=False).run(_query())
    assert plain.results_json() == rep.results_json()
    assert plain.extras["joint"]["top"] == rep.extras["joint"]["top"]


def _block(spec, rows: int) -> dict[str, jax.ShapeDtypeStruct]:
    space = build_space(OP)
    cs = [i for i, c in enumerate(space.cluster_options)
          if (c is not None) == bool(spec.cluster)]
    pts = [(0, 0, cs[0]) + (0,) * len(space.axes)] * rows
    ops = universal.encode_points(OP, space, pts, spec, num_pes=48,
                                  noc_bw=12.0)
    ops["live"] = np.ones((rows,), np.float32)
    if spec.ext_operand:
        ops["ext"] = np.ones((rows, len(spec.dim_names)), np.float32)
        if spec.cluster:
            k = len(spec.cluster)
            ops["cin_size"] = np.ones((rows, k), np.float32)
            ops["cin_off"] = np.ones((rows, k), np.float32)
    return {k: jax.ShapeDtypeStruct(v.shape, jnp.dtype(v.dtype))
            for k, v in ops.items()}


@pytest.mark.parametrize("kind,levels,ext", [
    ("reduced", 1, False), ("reduced", 2, False), ("features", 1, False),
    ("features", 2, False), ("reduced", 1, True)])
def test_executables_are_named_by_family(kind, levels, ext):
    spec = universal.universal_specs(OP, build_space(OP))[levels - 1]
    spec = dataclasses.replace(spec, ext_operand=ext)
    shapes = _block(spec, 8)
    if kind == "reduced":
        f = universal_reduced_evaluator(OP, spec, ReduceSpec("edp", k=2))
    else:
        shapes.pop("live")
        f = universal_evaluator(OP, spec)
    text = f.lower(shapes).as_text()
    want = f"jit_universal_{kind}_l{levels}" + ("_ext" if ext else "")
    assert f"module @{want} " in text, text[:200]
    assert "jit_chunk_fn" not in text and "jit_eval_one" not in text
