"""Shared set-up of the benchmark's tests: the benchmark's directory on
``sys.path``, and a small checkout (``tiny_root``) whose cells are the
real cells cut to a size the CPU can run in seconds."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """BENCHMARK.json's cells over small copies of their configurations
    (16 joint mappings in place of 640), plus a network and a layer cell
    over three of VGG16's layers (``data/``), whose answer kinds the
    harness also checks. The grid and the cells' traffic files are the
    real ones."""
    root = tmp_path_factory.mktemp("tiny")
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    chip = root / "benchmarks" / "chip"
    (chip / "configs").mkdir(parents=True)
    (chip / "traffic").mkdir()
    for d in (os.path.join(BENCH, "traffic"),
              os.path.join(DATA, "traffic")):
        for f in os.listdir(d):
            os.symlink(os.path.join(d, f), chip / "traffic" / f)
    spec = _load(os.path.join(ROOT, "BENCHMARK.json"))
    for c in spec["configs"]:
        cfg = _load(os.path.join(ROOT, c["file"]))
        cfg["search"]["joint_genes"] = 16
        (root / c["file"]).write_text(json.dumps(cfg))
    spec["configs"].append({"name": "vgg16-3",
                            "file": "benchmarks/chip/configs/vgg16-3.json"})
    os.symlink(os.path.join(DATA, "vgg16-3.json"),
               chip / "configs" / "vgg16-3.json")
    spec["workloads"] += [
        {"name": f"vgg16-3.{t}", "config": "vgg16-3", "traffic": t,
         "chips": 1} for t in ("network", "layers")]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def cpu_devices(jax, n):
    """Stands in for the harness's look for chips."""
    return jax.devices()[:n]
