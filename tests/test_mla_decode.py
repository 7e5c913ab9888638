"""DeepSeek-V3 decode operators in the DSE engine.

Load-bearing properties:

  * the rows dim ``N`` (heads of an MLA score, tokens of a projection)
    is a searched axis wherever its extent is above 1, and the universal
    engine agrees with the faithful engine on spaces that map it
    spatially and tile it;
  * every CNN zoo layer (``N = 1``) keeps its mapping space byte for
    byte, so CNN answers do not move;
  * the zoo's ``deepseek_v3_decode`` holds the published widths, and a
    network search over it fuses exactly the boundaries it declares;
  * ``OP_BUILDERS``, speclint and the jaxpr audit know the MLA ops.
"""
import hashlib

import numpy as np
import pytest

from repro.analysis import speclint
from repro.analysis.jaxpr_audit import PRIMITIVE_BUDGET, _audit_ops
from repro.api import Hardware, Query, SearchSpec, Session, Workload
from repro.api.spec import OP_BUILDERS, op_from_json
from repro.core import dnn_models as zoo
from repro.core import tensor_analysis as ta
from repro.core.model import analyze
from repro.core.performance import HWConfig
from repro.core.reuse_analysis import MULTICAST, reuse_opportunity_table
from repro.core.vectorized import FEATURES
from repro.mapspace import (build_space, evaluate_points, point_dataflow,
                            sample_points)
from repro.netspace.space import build_netspace

HW = HWConfig(num_pes=48, noc_bw=12.0, noc_latency=2.0)

# The universal engine carries every statistic in float32 (24-bit
# mantissa, about 6e-8 relative per rounding) over a few dozen operations;
# the faithful engine works in exact Python numbers.  1e-5 leaves room for
# that accumulation and is 100x tighter than a wrong term would read.
_REL = 1e-5


def _mla_ops():
    return [ta.mla_decode_score("mla-t-score", heads=8, context=256,
                                qk_dim=72),
            ta.mla_decode_av("mla-t-av", heads=8, context=256, latent=64),
            ta.fc("mla-t-fc", n=12, k=40, c=24)]


def test_mla_constructors_couple_the_cache_to_the_heads_only():
    score, av, _ = _mla_ops()
    assert score.dims == {"N": 8, "K": 256, "C": 72}
    assert av.dims == {"N": 8, "K": 64, "C": 256}
    for op in (score, av):
        # the cache (filter) is shared along the heads: not coupled to N
        assert not op.filter.coupled_to("N")
        assert op.input.coupled_to("N") and op.output.coupled_to("N")
        table = reuse_opportunity_table(op)
        assert table[("N", "K")]["spatial"]["F"] == MULTICAST
    assert score.total_macs == 8 * 256 * 72
    assert av.total_macs == 8 * 256 * 64


@pytest.mark.parametrize("which", [0, 1, 2],
                         ids=["mla_decode_score", "mla_decode_av", "fc"])
def test_universal_matches_faithful_with_rows_spatial_and_tiled(which):
    op = _mla_ops()[which]
    space = build_space(op)
    dims = [ax.dim for ax in space.axes]
    assert "N" in dims
    n_ax = dims.index("N")
    n_tiles = space.axes[n_ax].n
    rng = np.random.default_rng(which)
    pts = [p for p in sample_points(space, rng, 600)
           if space.spatial_choices[p[0]] == n_ax
           and 0 < p[3 + n_ax] < n_tiles - 1][:32]
    assert len(pts) == 32
    assert {space.cluster_options[p[2]] is None for p in pts} == \
        {True, False}
    feats, _ = evaluate_points(op, space, pts, num_pes=HW.num_pes,
                               noc_bw=HW.noc_bw, block=64)
    for i, pt in enumerate(pts):
        df = point_dataflow(space, pt)
        assert f"SpatialMap({space.axes[n_ax].sizes[pt[3 + n_ax]]}" in \
            str(df)
        s = analyze(op, df, HW)
        ref = {"runtime": s.runtime, "energy_pj": s.energy_pj,
               "macs": s.total_macs, "l1_kb": s.l1_req_kb,
               "l2_kb": s.l2_req_kb, "util": s.utilization,
               "bw_req": s.peak_bw.get(0, 0), "edp": s.edp}
        got = dict(zip(FEATURES, feats[i]))
        for k, v in ref.items():
            assert got[k] == pytest.approx(float(v), rel=_REL), (pt, k)


# Digests of ``build_space(op).fingerprint()`` over each CNN zoo network's
# layers, and of its network space, computed before N became searchable.
_SPACE_DIGEST = {
    "alexnet": "f6f8e9a0c9ec0937", "mobilenet_v2": "1ab8d44dd9b02be3",
    "resnet50": "6ef28743e7998484", "resnext50": "e176ef89011c6697",
    "unet": "867f54cffabfeced", "vgg16": "db3c2c2bcdfd6c8b"}
_NETSPACE_DIGEST = {
    "alexnet": "6b9ffd0edc57aaca", "mobilenet_v2": "6ac134e476865bf4",
    "resnet50": "c6d343cd6f5fbae5", "resnext50": "1d3150ee79390579",
    "unet": "b2ce133a52fc2747", "vgg16": "aaf9ce382449850e"}


def _digest(parts) -> str:
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(_SPACE_DIGEST))
def test_cnn_zoo_spaces_are_unchanged(name):
    layers = zoo.MODELS[name]()
    assert all(op.dims.get("N", 1) == 1 for op in layers)
    assert _digest([build_space(op).fingerprint() for op in layers]) \
        == _SPACE_DIGEST[name]
    ns = build_netspace(layers)
    assert _digest([s.fingerprint() for s in ns.spaces]
                   + [str(c.dims) for c in ns.classes]) \
        == _NETSPACE_DIGEST[name]


def test_zoo_layer_macs_match_the_closed_form():
    # DeepSeek-V3 config.json: hidden 7168, 128 heads, q_lora 1536,
    # kv_lora 512, rope 64, dense FFN 18432, expert FFN 2048, 256 routed
    # experts, 8 per token; deployment: 32 sequences at 65,536 tokens,
    # 32-way expert parallelism (8 experts here, 32 tokens each)
    b, t = 32, 65536
    attn = b * (7168 * 1536 + 1536 * 128 * 576 + 7168 * 576
                + 128 * 512 * 7168) + b * (128 * t * 576 + 128 * t * 512)
    dense = b * 3 * 7168 * 18432
    moe = b * 7168 * 256 + b * 3 * 7168 * 2048 + 8 * 32 * 3 * 7168 * 2048
    layers = zoo.deepseek_v3_decode()
    assert sum(op.total_macs for op in layers) == 2 * attn + dense + moe
    scores = [op for op in layers if "score" in op.name]
    assert len(scores) == 2 * b
    assert scores[0].total_macs == 128 * t * 576 == 4_831_838_208


def test_zoo_fusible_boundaries_are_producer_consumer_edges():
    layers = zoo.deepseek_v3_decode()
    mask = zoo.fusible_boundaries("deepseek_v3_decode", layers)
    assert len(mask) == len(layers) - 1
    fused = [(a.name.split("-", 2)[2], b.name.split("-", 2)[2])
             for a, b, f in zip(layers, layers[1:], mask) if f]
    assert len(fused) == 2 * (1 + 32) + 1 + 1 + 8
    for a, b in fused:
        assert (a, b) == ("q_a", "q_b") \
            or (a.startswith("score") and b == "av" + a[5:]) \
            or (a.endswith("gate_up") and b == a[:-7] + "down")
    for (a, b), f in zip(zip(layers, layers[1:]), mask):
        if f:        # the producer's output rows are the consumer's
            assert a.dims["N"] == b.dims["N"]
    assert zoo.fusible_boundaries("vgg16", zoo.vgg16()) is None


def test_workload_narrows_the_mask_to_selected_layers():
    wl = Workload(model="deepseek_v3_decode", layer="l0-score0,l0-av0,l0-o")
    assert [op.name for op in wl.resolve()] == \
        ["dsv3-l0-score0", "dsv3-l0-av0", "dsv3-l0-o"]
    assert zoo.fusible_boundaries(wl.model, wl.resolve()) == [True, False]
    vgg = Workload(model="vgg16")
    assert zoo.fusible_boundaries(vgg.model, vgg.resolve()) is None
    assert zoo.fusible_boundaries(None, _mla_ops()) is None


@pytest.fixture(scope="module")
def dsv3_network():
    q = Query(Workload.of_network("deepseek_v3_decode"), Hardware(),
              SearchSpec(budget=16, frontier_k=2, strategy="random",
                         reconfig=False, budget_policy="uniform",
                         block=256))
    return Session(jax_cache=False).run(q)


def test_network_query_fuses_exactly_the_declared_boundaries(dsv3_network):
    rep = dsv3_network
    sched = rep.raw.schedule
    layers = zoo.deepseek_v3_decode()
    mask = zoo.fusible_boundaries("deepseek_v3_decode", layers)
    assert [p["layer"] for p in sched.per_layer] == \
        [op.name for op in layers]
    # fc layers have no window halo: fusing is free, so every declared
    # boundary fuses and no other does
    assert sched.fuse == mask
    hw = rep.raw.model.hw
    for i, f in enumerate(mask):
        if not f:     # an unfused edge: the off-chip round trip only
            vol = layers[i].output.volume(layers[i].dims)
            assert sched.per_layer[i + 1]["edge_energy_pj"] == \
                pytest.approx(2.0 * vol * hw.dram_energy_pj)


def test_layer_and_codse_queries_search_the_rows_dim():
    session = Session(jax_cache=False)
    rep = session.run(Query(
        Workload(model="deepseek_v3_decode", layer="l1-score0"),
        Hardware(num_pes=256, noc_bw=32.0),
        SearchSpec(budget=64, strategy="random", block=256)))
    assert "N" in [ax.dim for ax in rep.raw.space.axes]
    op = op_from_json({"type": "mla_decode_score", "name": "mla-t-q",
                       "heads": 8, "context": 256, "qk_dim": 72})
    co = session.run(Query(
        Workload.of_layer(op),
        Hardware(num_pes=48, noc_bw=12.0, pe_range=(16, 32),
                 bw_range=(4.0, 8.0)),
        SearchSpec(budget=48, block=96, codse_top_k=2, joint_genes=8)))
    space = co.raw.search.space
    assert "N" in [ax.dim for ax in space.axes]
    assert co.raw.joint.top
    for d in co.raw.joint.top:      # the joint sweep's winners, faithful
        s = analyze(op, point_dataflow(space, d["point"]),
                    HWConfig(num_pes=d["num_pes"], noc_bw=d["noc_bw"],
                             noc_latency=2.0))
        assert d["runtime"] == pytest.approx(float(s.runtime), rel=_REL)
        assert d["l1_kb"] == pytest.approx(float(s.l1_req_kb), rel=_REL)


def test_op_builders_speclint_and_audit_know_mla():
    assert "attention_score" not in OP_BUILDERS
    assert OP_BUILDERS["mla_decode_score"] is ta.mla_decode_score
    assert OP_BUILDERS["mla_decode_av"] is ta.mla_decode_av

    def query(dims):
        return Query.from_json({
            "workload": {"op": {"type": "mla_decode_score", "name": "lint",
                                "heads": 8, "context": 256,
                                "qk_dim": 72}},
            "search": {"dims": dims}})

    assert speclint.lint_query(query(None)) == []
    assert speclint.lint_query(query(["N", "K", "C"])) == []
    assert [f.code for f in speclint.errors_only(
        speclint.lint_query(query(["H", "K"])))] == ["SPEC-DIMS"]
    conv = Query.from_json({
        "workload": {"op": {"type": "conv2d", "name": "lint-c", "k": 8,
                            "c": 8, "y": 10, "x": 10, "r": 3, "s": 3}},
        "search": {"dims": ["N", "K"]}})
    assert speclint.lint_query(conv) == []
    assert {op.name for op in _audit_ops()} >= {"audit-mla"}
    assert {"audit-mla:L1", "audit-mla:L2"} <= set(PRIMITIVE_BUDGET)
