"""The comparison that decides ``correct``, driven through the rest of a
run at a size the CPU holds: a sound run of the benchmark's cell passes,
and for every kind of answer the harness checks, the bfloat16 control
fails and so does an answer altered where the program produces it; a
sweep that leaves half of its designs out fails too."""
from __future__ import annotations

import numpy as np
import pytest

import bench
import check
import faults
import querygen
from conftest import cpu_devices

CELLS = ("fig13-conv13.sweep", "vgg16-3.network", "vgg16-3.layers")
SEED = 3141592653589


def run(root, name, seconds=3.0):
    return bench.run_cell(name, SEED, seconds, False, root=root,
                          chips=cpu_devices)


@pytest.mark.parametrize("name", CELLS[:1])
def test_sound_run_is_correct(tiny_root, name):
    r = run(tiny_root, name)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(tiny_root, name):
    cell = bench.load_cell(name, tiny_root)
    session = bench.make_session(1)
    for wire in querygen.warmup(cell.config, cell.traffic):
        bench.run_query(session, wire)
    _, _, recs = bench.window(
        session, querygen.window(cell.config, cell.traffic, SEED), 2.0)
    ref = check.Reference(cell.config)
    answers = [bench.answer(cell, q) for q in recs]
    ok, table = check.verdict(
        check.readings(ref, check.control(ref, answers)),
        cell.config["limits"][cell.kind])
    assert not ok, table


def _alter(rep, kind):
    """One statistic of the answer off by 1%, where it is produced."""
    if kind == "layer":
        rep.best["stats"]["runtime"] *= 1.01
    elif kind == "layer_codse":
        rep.raw.joint.top[0]["energy_pj"] *= 1.01
    else:
        rep.raw.schedule.per_layer[1]["energy_pj"] *= 1.01
    return rep


@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_is_not_correct(tiny_root, name, monkeypatch):
    from repro.api.session import Session
    route = Session._route
    monkeypatch.setattr(Session, "_route", lambda self, kind, q: _alter(
        route(self, kind, q), kind))
    r = run(tiny_root, name)
    assert not r["correct"], r["checks"]


def test_dropped_designs_are_not_correct(tiny_root):
    undo = faults.drop_half()
    try:
        r = run(tiny_root, CELLS[0])
    finally:
        undo()
    assert not r["correct"], r["checks"]
    regret = r["checks"]["topk_regret"]
    assert regret["value"] > regret["limit"], r["checks"]


def test_grid_rescan_is_the_reference_at_each_point(tiny_root):
    cell = bench.load_cell(CELLS[0], tiny_root)
    ref = check.Reference(cell.config)
    df = "Dataflow d {\n" + "\n".join((
        "TemporalMap(128,128) C", "TemporalMap(9,7) Y", "TemporalMap(4,2) X",
        "SpatialMap(4,4) K", "TemporalMap(Sz(R),Sz(R)) R",
        "TemporalMap(Sz(S),Sz(S)) S", "Cluster(64)", "SpatialMap(1,1) C"
    )) + "\n}"
    g = ref.grid(df)
    for i in (0, 5, 47, len(g["pes"]) - 1):
        for j in range(len(g["bws"])):
            want = ref.stats(0, df, int(g["pes"][i]), float(g["bws"][j]),
                             sweep=True)
            for k in ("edp", "area_mm2", "power_mw"):
                assert g[k][i, j] == want[k], (k, i, j)
    assert np.isfinite(g["edp"]).all()
