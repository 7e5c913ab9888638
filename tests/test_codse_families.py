"""The co-DSE's host time and designs split by operator family.

Load-bearing properties:

  * every ``hw-sweep`` span names the executable family of its mapping
    and every ``design-chunk`` span the families of its designs
    (``universal.family_label``);
  * the ``codse.designs`` counter counts each joint design once, under
    its family, so a process that sweeps two operators splits its
    designs by operator.
"""
import numpy as np
import pytest

from repro import obs
from repro.api import Hardware, Query, SearchSpec, Session, Workload
from repro.core import tensor_analysis as ta
from repro.core.dse import DSEConfig
from repro.mapspace.codse import joint_sweep
from repro.mapspace.space import build_space, gene_tables, sample_genes
from repro.mapspace.universal import family_label, gene_families

CONV = ta.conv2d("fam-conv", k=8, c=6, y=12, x=12, r=3, s=3)
MLA = ta.mla_decode_score("fam-mla", heads=8, context=256, qk_dim=72)
PES, BWS = (16, 32, 64), (4.0, 8.0, 16.0)


def _query(op) -> Query:
    return Query(Workload.of_layer(op),
                 Hardware(num_pes=48, noc_bw=12.0, pe_range=PES,
                          bw_range=BWS),
                 SearchSpec(objective="edp", budget=60, block=96, top_k=4,
                            codse_top_k=2, joint_genes=6))


def _designs() -> dict[str, float]:
    return obs.metrics().counters("codse.designs")


def _family(key: str) -> str:
    """The family label of a ``codse.designs[family=...]`` key."""
    return key[key.index("family=") + len("family="):-1]


@pytest.fixture(scope="module")
def traced():
    """Co-DSE queries on two operators, warm, traced, with the
    ``codse.designs`` counter read around each."""
    session = Session(jax_cache=False)
    for op in (CONV, MLA):
        session.run(_query(op))           # compiles outside the trace
    obs.disable_tracing()
    tracer = obs.enable_tracing()
    out = {}
    try:
        for op in (CONV, MLA):
            before = _designs()
            rep = session.run(_query(op))
            after = _designs()
            out[op.name] = (rep, {k: v - before.get(k, 0.0)
                                  for k, v in after.items()
                                  if v != before.get(k, 0.0)})
    finally:
        obs.disable_tracing()
    return out, tracer.spans()


@pytest.mark.parametrize("op", [CONV, MLA], ids=lambda o: o.name)
def test_hw_sweep_spans_name_their_mapping_family(traced, op):
    out, spans = traced
    sweeps = [e for e in spans if e["name"] == "hw-sweep"
              and e["args"]["family"].startswith(op.name + ":")]
    co = out[op.name][0].raw
    assert len(sweeps) == len(co.dse)
    space = co.search.space
    for e in sweeps:
        point = next(p["point"] for p in co.search.top_k
                     if e["args"]["mapping"] == f"ms:{op.name}:"
                     + "-".join(str(g) for g in p["point"]))
        levels = 1 if space.cluster_options[point[2]] is None else 2
        assert e["args"]["family"] == f"{op.name}:L{levels}"


@pytest.mark.parametrize("op", [CONV, MLA], ids=lambda o: o.name)
def test_design_chunk_spans_name_their_families(traced, op):
    _, spans = traced
    chunks = [e for e in spans if e["name"] == "design-chunk"
              and e["args"]["family"].startswith(op.name + ":")]
    assert chunks
    for e in chunks:
        fams = e["args"]["family"].split("+")
        assert set(fams) <= {f"{op.name}:L1", f"{op.name}:L2"}


@pytest.mark.parametrize("op", [CONV, MLA], ids=lambda o: o.name)
def test_designs_counter_splits_by_operator(traced, op):
    out, _ = traced
    rep, moved = out[op.name]
    co = rep.raw
    n_hw = len(PES) * len(BWS)
    # only this operator's families moved, and they count every joint
    # design: the per-mapping hardware sweeps plus the paper-scale sweep
    assert moved and all(_family(k).startswith(op.name + ":")
                         for k in moved)
    assert sum(moved.values()) == len(co.dse) * n_hw + co.joint.n_designs


def test_chunk_designs_are_counted_under_each_rows_family():
    space = build_space(MLA)
    tb = gene_tables(MLA, space)
    genes = sample_genes(space, np.random.default_rng(5), 64)
    is2 = ~tb.cluster_is_none[genes[:, 2]]
    assert is2.any() and (~is2).any()
    cfg = DSEConfig(pe_range=PES, bw_range=BWS)
    h = len(PES) * len(BWS)
    assert family_label(MLA, None) == f"{MLA.name}:L1"
    assert set(gene_families(MLA, space, genes)) == \
        {f"{MLA.name}:L1", f"{MLA.name}:L2"}
    before = _designs()
    obs.disable_tracing()
    tracer = obs.enable_tracing()
    try:
        # 7 designs a chunk: chunks straddle mappings (h = 9)
        res = joint_sweep(MLA, space, genes, cfg, block=64,
                          chunk_designs=7)
    finally:
        obs.disable_tracing()
    after = _designs()
    moved = {_family(k): v - before.get(k, 0.0) for k, v in after.items()}
    assert moved[f"{MLA.name}:L1"] == int((~is2).sum()) * h
    assert moved[f"{MLA.name}:L2"] == int(is2.sum()) * h
    assert res.n_designs == len(genes) * h
    chunks = tracer.spans("design-chunk")
    assert sum(e["args"]["rows"] for e in chunks) == res.n_designs
    mixed = [e for e in chunks if "+" in e["args"]["family"]]
    assert mixed                   # some chunk holds both families
