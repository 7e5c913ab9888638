"""Tests for the mapping-space search engine (repro.mapspace)."""
import itertools

import numpy as np
import pytest

from repro.core import tensor_analysis as ta
from repro.core.dataflows import TABLE3, table3_for_layer
from repro.core.directives import (FULL, Cluster, Dataflow, SpatialMap, Sz,
                                   TemporalMap, divisors, extended_dims,
                                   is_legal, tile_candidates)
from repro.core.dse import tile_variants
from repro.core.model import analyze
from repro.core.performance import HWConfig
from repro.core.vectorized import FEATURES
from repro.mapspace import (build_space, enumerate_points, evaluate_points,
                            point_dataflow, sample_points, search)

HW = HWConfig(num_pes=64, noc_bw=16.0, noc_latency=2.0)


@pytest.fixture(scope="module")
def tiny_conv():
    return ta.conv2d("tiny", k=8, c=4, y=10, x=10, r=3, s=3)


@pytest.fixture(scope="module")
def tiny_space(tiny_conv):
    return build_space(tiny_conv, dims=("K", "C"), cluster_sizes=(4,))


# ----------------------------------------------------------------------
# Divisor / legality helpers
# ----------------------------------------------------------------------

def test_divisors():
    assert divisors(12) == (1, 2, 3, 4, 6, 12)
    assert divisors(1) == (1,)
    assert divisors(7) == (1, 7)
    with pytest.raises(ValueError):
        divisors(0)


def test_tile_candidates_thinning():
    full = tile_candidates(360)
    assert full == divisors(360)
    thin = tile_candidates(360, 5)
    assert len(thin) == 5
    assert thin[0] == 1 and thin[-1] == 360
    assert set(thin) <= set(full)


def test_is_legal():
    dims = {"K": 8, "C": 4}
    ok = Dataflow("ok", (SpatialMap(2, 2, "K"), TemporalMap(4, 4, "C")))
    assert is_legal(ok, dims)
    too_big = Dataflow("big", (SpatialMap(16, 16, "K"),))
    assert not is_legal(too_big, dims)
    # symbolic sizes are legal (resolve clamps them)
    sym = Dataflow("sym", (TemporalMap(Sz("R"), 1, "Y"),))
    assert is_legal(sym, {"Y": 10, "R": 3})


# ----------------------------------------------------------------------
# Space definition
# ----------------------------------------------------------------------

def test_space_size_matches_bruteforce(tiny_space):
    pts = list(enumerate_points(tiny_space))
    assert len(pts) == tiny_space.size
    assert len(set(pts)) == tiny_space.size
    # brute-force recomputation of the count from the gene ranges
    n = 1
    for r in tiny_space.gene_ranges():
        n *= r
    assert tiny_space.size == n


def test_every_point_is_legal(tiny_conv, tiny_space):
    for pt in enumerate_points(tiny_space):
        df = point_dataflow(tiny_space, pt)
        ext = extended_dims(df, tiny_conv.dims)
        assert is_legal(df, tiny_conv.dims), str(df)
        for d in df.directives:
            if isinstance(d, Cluster):
                continue
            if isinstance(d.size, int) and d.size != FULL:
                assert 0 < d.size <= ext[d.dim]


def test_window_dims_pinned_symbolic(tiny_conv, tiny_space):
    assert set(tiny_space.pinned) == {"R", "S"}
    df = point_dataflow(tiny_space, next(enumerate_points(tiny_space)))
    pinned = [d for d in df.directives
              if not isinstance(d, Cluster) and d.dim in ("R", "S")]
    assert len(pinned) == 2
    assert all(isinstance(d.size, Sz) for d in pinned)


def test_window_outer_tiles_cover_outputs():
    """Y/X tile candidates carry the input halo: every tile yields whole
    output rows and the offsets tile the output extent exactly."""
    op = ta.conv2d("s2", k=4, c=4, y=11, x=11, r=3, s=3, stride=2)
    space = build_space(op, dims=("K", "Y"), cluster=False)
    (y_axis,) = [ax for ax in space.axes if ax.dim == "Y"]
    out_extent = (11 - 3) // 2 + 1  # 5 output rows
    for size, off in zip(y_axis.sizes, y_axis.offsets):
        assert out_extent % off == 0
        assert size == (off - 1) * 2 + 3
        assert size <= 11


def test_sampling_deterministic_and_distinct(tiny_space):
    a = sample_points(tiny_space, np.random.default_rng(7), 20)
    b = sample_points(tiny_space, np.random.default_rng(7), 20)
    assert a == b
    assert len(set(a)) == len(a)


# ----------------------------------------------------------------------
# Batched evaluator vs faithful analyze()
# ----------------------------------------------------------------------

def test_batched_agrees_with_faithful(tiny_conv, tiny_space):
    rng = np.random.default_rng(0)
    pts = sample_points(tiny_space, rng, 5)
    assert len(pts) >= 3
    feats, _ = evaluate_points(tiny_conv, tiny_space, pts,
                               num_pes=HW.num_pes, noc_bw=HW.noc_bw,
                               block=8)
    for i, pt in enumerate(pts):
        df = point_dataflow(tiny_space, pt)
        s = analyze(tiny_conv, df, HW)
        ref = {"runtime": float(s.runtime), "energy_pj": float(s.energy_pj),
               "macs": float(s.total_macs), "l1_kb": float(s.l1_req_kb),
               "l2_kb": float(s.l2_req_kb), "util": float(s.utilization),
               "edp": float(s.edp)}
        got = dict(zip(FEATURES, feats[i]))
        for k, v in ref.items():
            assert got[k] == pytest.approx(v, rel=1e-3), (pt, k)


# ----------------------------------------------------------------------
# Search
# ----------------------------------------------------------------------

def test_search_exhaustive_finds_global_best(tiny_conv, tiny_space):
    r = search(tiny_conv, objective="edp", budget=10_000, space=tiny_space,
               num_pes=HW.num_pes, noc_bw=HW.noc_bw, seed=0, block=64)
    assert r.strategy == "exhaustive"
    assert r.n_evaluated == tiny_space.size
    # global best: no enumerated point does better
    vals = [e["value"] for e in r.top_k]
    assert vals == sorted(vals)
    assert r.best_value == vals[0]


def test_search_deterministic_under_seed(tiny_conv, tiny_space):
    kw = dict(objective="edp", budget=60, space=tiny_space,
              num_pes=HW.num_pes, noc_bw=HW.noc_bw, strategy="greedy",
              block=64)
    a = search(tiny_conv, seed=3, **kw)
    b = search(tiny_conv, seed=3, **kw)
    assert a.best_point == b.best_point
    assert a.best_value == b.best_value
    assert [e["point"] for e in a.top_k] == [e["point"] for e in b.top_k]


def test_search_beats_table3(tiny_conv):
    """Acceptance: the found mapping's EDP <= the best Table-3 dataflow's
    on the same layer and hardware."""
    space = build_space(tiny_conv, dims=("K", "Y"), spatial_dims=("Y",),
                        cluster_inner_dims=("X",), cluster_sizes=(8,),
                        perm_mode="all")
    r = search(tiny_conv, objective="edp", budget=400, space=space,
               num_pes=HW.num_pes, noc_bw=HW.noc_bw, seed=0, block=64)
    best_t3 = min(float(analyze(tiny_conv, table3_for_layer(f, tiny_conv),
                                HW).edp) for f in TABLE3)
    assert r.best_value <= best_t3 * (1 + 1e-6)


def test_search_cache_roundtrip(tiny_conv, tiny_space, tmp_path):
    kw = dict(objective="edp", budget=40, space=tiny_space,
              num_pes=HW.num_pes, noc_bw=HW.noc_bw, seed=1,
              strategy="random", block=64, cache_dir=str(tmp_path))
    a = search(tiny_conv, **kw)
    assert not a.cached
    b = search(tiny_conv, **kw)
    assert b.cached
    assert b.best_point == a.best_point
    assert b.best_value == a.best_value
    assert b.n_evaluated == a.n_evaluated
    # different search parameters must not hit the same cache entry
    c = search(tiny_conv, **{**kw, "max_groups": 2})
    assert not c.cached
    d = search(tiny_conv, **{**kw, "top_k": 3})
    assert not d.cached


def test_compilation_cache_dir_rule(monkeypatch):
    import pathlib
    from repro.mapspace import cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert cache.compilation_cache_dir() == str(root / ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/xla")
    assert cache.compilation_cache_dir() == "/elsewhere/xla"


def test_session_compiles_into_env_cache_dir(tmp_path):
    import os
    import subprocess
    import sys
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.api import Session\n"
            "s = Session()\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
            "print(s.jax_cache_dir)\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())


# ----------------------------------------------------------------------
# Satellite regression: tile_variants symbolic handling
# ----------------------------------------------------------------------

def test_tile_variants_preserve_symbolic():
    df = Dataflow("sym", (
        TemporalMap(Sz("R"), Sz("R"), "C"),   # symbolic: must not scale
        TemporalMap(FULL, FULL, "K"),         # FULL sentinel: must not scale
        SpatialMap(1, 1, "X"),
    ))
    variants = tile_variants(df, scales=(1, 2, 4))
    # nothing scalable -> only the base variant, no misleading tags
    assert [tag for tag, _ in variants] == ["base"]
    for _, v in variants:
        assert v.directives == df.directives


def test_tile_variants_tag_names_scaled_dims():
    df = Dataflow("mix", (
        TemporalMap(4, 4, "C"),
        TemporalMap(Sz("S"), Sz("S"), "K"),
        SpatialMap(1, 1, "X"),
    ))
    variants = dict(tile_variants(df, scales=(1, 2)))
    assert set(variants) == {"base", "x2[C]"}
    base, x2 = variants["base"], variants["x2[C]"]
    assert base.directives == df.directives
    (c_map,) = [d for d in x2.directives
                if not isinstance(d, Cluster) and d.dim == "C"]
    assert (c_map.size, c_map.offset) == (8, 8)
    (k_map,) = [d for d in x2.directives
                if not isinstance(d, Cluster) and d.dim == "K"]
    assert isinstance(k_map.size, Sz)  # symbolic preserved untouched
