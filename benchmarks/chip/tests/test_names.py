"""Every name in BENCHMARK.json resolves to a file of its own, and the
command refuses to run where it cannot measure."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import bench
from conftest import ROOT

SPEC = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_every_name_resolves():
    configs = {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        cfg = bench.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
    used = set()
    for w in SPEC["workloads"]:
        cell = bench.load_cell(w["name"])
        used.add(w["config"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        assert cell.kind in cell.config["limits"], w["name"]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(bench.reader(m["name"])), m["name"]
    assert used == configs


def test_each_layer_metric_moves_a_metric_its_cells_report():
    for m in SPEC["per_layer"]:
        for w in m["workloads"]:
            cell = bench.load_cell(w)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
            assert m in cell.per_layer


def _run(cwd, env=None):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=600,
        env={**os.environ, **(env or {})})


def test_refuses_without_a_tpu():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_without_the_system(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
