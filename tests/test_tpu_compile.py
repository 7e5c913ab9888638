"""Compile the main path's executables for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and raises what the chip's compiler would raise
(alignment, memory, partitioning).  Shapes are passed, never arrays.
The topology is described inside a fixture, never at import, so every
xdist worker collects the same tests and only the worker that runs this
file loads the TPU library.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dnn_models as zoo
from repro.core.dse import DSEConfig
from repro.core.vectorized import (HWTail, ReduceSpec,
                                   universal_reduced_evaluator)
from repro.mapspace.space import build_space
from repro.mapspace.universal import encode_points, universal_specs
from repro.netspace.evaluator import COLS

BLOCK = 1024                   # the search and joint sweep's default block
V5E_HBM_BYTES = 16 * 10**9     # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without the chip: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _conv13():
    return [op for op in zoo.vgg16() if op.op_type == "CONV2D"][-1]


def _operands(op, space, spec, *, ext: bool) -> dict[str, np.ndarray]:
    """One block of operands of ``spec``'s family (shapes are what
    matters; the values are the family's minimum-tile points)."""
    cs = [i for i, c in enumerate(space.cluster_options)
          if (c is not None) == bool(spec.cluster)]
    pts = [(0, 0, cs[0]) + (0,) * len(space.axes)] * BLOCK
    ops = encode_points(op, space, pts, spec, num_pes=256, noc_bw=32.0)
    ops["live"] = np.ones((BLOCK,), np.float32)
    if ext:
        ops["ext"] = np.tile(np.asarray(
            [op.dims[d] for d in spec.dim_names], np.float32), (BLOCK, 1))
        if spec.cluster:
            for key, col in (("cin_size", 1), ("cin_off", 2)):
                ops[key] = np.tile(np.asarray(
                    [c[col] for c in spec.cluster], np.float32), (BLOCK, 1))
    return ops


def _reduce(kind: str) -> ReduceSpec:
    if kind == "netspace":
        return ReduceSpec(objective="runtime", k=1, pareto=False,
                          cols=COLS)
    reduce = ReduceSpec(objective="edp", k=8)
    if kind == "codse":
        cfg = DSEConfig()
        reduce = dataclasses.replace(reduce, hw=HWTail(
            area_power=cfg.area_power, area_budget_mm2=cfg.area_budget_mm2,
            power_budget_mw=cfg.power_budget_mw))
    return reduce


def _mla_score():
    return next(op for op in zoo.deepseek_v3_decode()
                if "score" in op.name)


def _compile(one_chip, op, levels: int, kind: str) -> None:
    space = build_space(op)
    spec = universal_specs(op, space)[levels - 1]
    assert spec is not None
    if kind == "netspace":
        spec = dataclasses.replace(spec, ext_operand=True)
    ops = _operands(op, space, spec, ext=kind == "netspace")
    shapes = {k: jax.ShapeDtypeStruct(v.shape, jnp.dtype(v.dtype),
                                      sharding=one_chip)
              for k, v in ops.items()}
    f = universal_reduced_evaluator(op, spec, _reduce(kind))
    compiled = f.lower(shapes).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


@pytest.mark.parametrize("levels,kind", [
    (1, "search"), (2, "search"), (2, "codse"), (1, "netspace")])
def test_conv13_family_compiles_for_v5e(one_chip, no_persistent_cache,
                                        levels, kind):
    _compile(one_chip, _conv13(), levels, kind)


@pytest.mark.parametrize("levels,kind", [
    (1, "codse"), (2, "codse"), (1, "netspace")])
def test_mla_score_family_compiles_for_v5e(one_chip, no_persistent_cache,
                                           levels, kind):
    """DeepSeek-V3's decode score at its published width, its rows dim
    (the 128 heads) a searched axis."""
    op = _mla_score()
    assert op.dims == {"N": 128, "K": 65536, "C": 576}
    _compile(one_chip, op, levels, kind)
