"""The MAESTRO↔TPU bridge: Table-1 predictions vs actual XLA collectives.

These tests lower tiny sharded GEMMs on a multi-device host mesh and check
that the collectives the SPMD partitioner inserts are exactly the ones the
directive-level reuse analysis predicts (spatial multicast -> all-gather,
spatial reduction -> all-reduce/reduce-scatter)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import tensor_analysis as ta
from repro.core.dataflows import table3_for_layer
from repro.core.mapper import expected_collectives, gemm_op

# Collective checks need >1 device; run them in a subprocess with a forced
# 8-device host platform (XLA device count locks at first jax init).
_SUB = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))

    def lower_gemm(spec_l, spec_r, spec_o):
        def f(a, b):
            return jax.lax.with_sharding_constraint(
                a @ b, NamedSharding(mesh, spec_o))
        a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((128, 64), jnp.float32)
        c = jax.jit(f, in_shardings=(NamedSharding(mesh, spec_l),
                                     NamedSharding(mesh, spec_r)))
        return c.lower(a, b).compile().as_text()

    # K-partitioned (tp): weights sharded on out dim, activations full.
    hlo = lower_gemm(P(), P(None, "model"), P(None, "model"))
    assert "all-gather" not in hlo and "all-reduce" not in hlo, "tp-K"

    # C-partitioned: contraction sharded -> spatial reduction (all-reduce
    # or reduce-scatter) must appear.
    hlo = lower_gemm(P(None, "model"), P("model", None), P())
    assert ("all-reduce" in hlo or "reduce-scatter" in hlo), "tp-C"

    # DP/FSDP: batch sharded, weights sharded on contraction dim ->
    # weight all-gather (spatial multicast of the decoupled tensor).
    hlo = lower_gemm(P("model", None), P("model", None), P("model", None))
    assert "all-gather" in hlo or "all-reduce" in hlo, "fsdp"
    print("OK")
""")


def test_spmd_collectives_match_taxonomy():
    r = subprocess.run([sys.executable, "-c", _SUB],
                       capture_output=True, text=True,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_expected_collectives_table1():
    from repro.core.mapper import contraction_tp, fsdp_dp, megatron_tp
    op = gemm_op("g", m=32, n=64, k=128)
    # K-partitioned: inputs (I) decoupled from K -> multicast; no psums
    exp = expected_collectives(megatron_tp(None), op)
    assert exp.get("I") == "all-gather"
    assert "O" not in exp
    # C-partitioned: contraction sharded -> output reduction
    exp = expected_collectives(contraction_tp(None), op)
    assert exp.get("O") == "all-reduce"
    # DP: weights decoupled from batch -> weight multicast (FSDP gather)
    exp = expected_collectives(fsdp_dp(None), op)
    assert exp.get("F") == "all-gather"


def test_dataflow_to_pspec_kc():
    import jax
    from repro.core.mapper import dataflow_to_pspec
    op = ta.conv2d("c", k=64, c=64, y=8, x=8, r=3, s=3)
    df = table3_for_layer("KC-P", op)
    mesh = jax.make_mesh((1, 1), ("x", "y"))
    specs = dataflow_to_pspec(df, mesh, op)
    # K spatial at level 0 -> first mesh axis on the K position of F and O
    assert specs["rhs"][1] == "x"      # F[K dim] sharded on level-0 axis
    assert specs["out"][1] == "x"
    assert specs["lhs"] == () or specs["lhs"][0] is None or \
        specs["lhs"][1] == "y"         # C inner -> second axis on lhs


def test_tpu_mapping_analysis_runs():
    import jax
    from repro.core.mapper import analyze_tpu_mapping, megatron_tp
    op = gemm_op("g", m=4096, n=8192, k=8192)
    mesh = jax.make_mesh((1,), ("model",))
    tm = analyze_tpu_mapping(megatron_tp(mesh), op, mesh)
    assert tm.stats.total_macs == op.total_macs
    assert tm.expected_collectives.get("I") == "all-gather"
