"""Tests for repro.resilience: fault-tolerant, resumable sweep execution.

Load-bearing properties:

  * deterministic fault injection (``kind@site:index``) makes every
    recovery path exercisable without flakes;
  * transparent retry: a crashed device chunk re-dispatches and the
    result is bit-identical to an undisturbed run; exhausting the retry
    budget surfaces a structured ``DeviceError``;
  * kill-and-resume: a sweep killed at chunk k and re-launched with a
    ``SweepCheckpoint`` resumes from the last saved chunk and returns
    bit-identical results — at 1 device and at every available device
    count (CI re-runs this file under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``);
  * OOM chunk-splitting converges and matches the unsplit run;
  * corrupt/stale checkpoints and corrupt result-cache entries are
    quarantined misses, never crashes;
  * the Session degrades a persistently-failing gene-pipeline query to
    the legacy engine (and a poisoned coalesced batch to sequential
    queries) and still produces correct Reports;
  * spec validation raises ``SpecError`` naming the offending field, and
    the launch CLIs turn any ``ReproError`` into a one-line exit 2.
"""
import json
import os

import numpy as np
import pytest
import jax

from repro import obs
from repro.api import Hardware, Query, SearchSpec, Session, Workload
from repro.api.spec import (VALID_BUDGET_POLICIES, VALID_OBJECTIVES,
                            VALID_PIPELINES, VALID_STRATEGIES)
from repro.checkpoint.checkpointer import Checkpointer
from repro.core import tensor_analysis as ta
from repro.core.dse import DSEConfig
from repro.ft.coordinator import FaultTolerantLoop
from repro.mapspace import (build_space, evaluate_genes, joint_sweep,
                            sample_genes)
from repro.mapspace import cache as mcache
from repro.mapspace.search import (OBJECTIVES, PIPELINES, STRATEGIES,
                                   search_impl)
from repro.resilience import (DeviceError, ReproError, ResilienceConfig,
                              RetryPolicy, SpecError, StragglerWatchdog,
                              SweepCheckpoint, SweepKilled, faultinject,
                              set_default_policy)
from repro.resilience.faultinject import parse

PES, BW = 48, 12.0
NDEV = jax.local_device_count()

# small backoffs + min_rows below the test block size so the OOM split
# path is actually reachable
FAST = RetryPolicy(max_attempts=2, backoff_s=0.001, min_rows=16)


@pytest.fixture(autouse=True)
def _clean_process_state():
    yield
    faultinject.clear()
    set_default_policy(None)


@pytest.fixture(scope="module")
def conv_op():
    return ta.conv2d("res-conv", k=8, c=6, y=12, x=12, r=3, s=3)


@pytest.fixture(scope="module")
def conv_space(conv_op):
    return build_space(conv_op, dims=("K", "C", "Y"), cluster_sizes=(8,),
                       perm_mode="all")


@pytest.fixture(scope="module")
def genes(conv_space):
    return sample_genes(conv_space, np.random.default_rng(0), 256)


def ev_sig(ev):
    """The bit-identity signature of a GeneEval."""
    return ([(t["row"], t["value"], t["feats"].tobytes()) for t in ev.top],
            [(p["row"], p["energy_pj"], p["throughput"])
             for p in ev.pareto],
            None if ev.vals is None else ev.vals.tobytes())


def run_eval(conv_op, conv_space, genes, **kw):
    kw.setdefault("num_pes", PES)
    kw.setdefault("noc_bw", BW)
    kw.setdefault("block", 32)
    kw.setdefault("n_devices", 1)
    return evaluate_genes(conv_op, conv_space, genes, **kw)


def counter(name):
    return obs.metrics().value(name)


# ----------------------------------------------------------------------
# Fault-spec grammar
# ----------------------------------------------------------------------

def test_fault_spec_parse():
    ds = parse("crash@chunk:3, oom@chunk:2, slow@chunk:1:0.25,"
               "kill@design-chunk:5x2")
    assert [(d.kind, d.site, d.index, d.arg, d.times) for d in ds] == [
        ("crash", "chunk", 3, 0.0, 1), ("oom", "chunk", 2, 0.0, 1),
        ("slow", "chunk", 1, 0.25, 1), ("kill", "design-chunk", 5, 0.0, 2)]
    assert [d.spec() for d in ds] == ["crash@chunk:3", "oom@chunk:2",
                                     "slow@chunk:1:0.25",
                                     "kill@design-chunk:5x2"]
    for bad in ("explode@chunk:1", "crash@chunk", "crash@", "oom"):
        with pytest.raises(ValueError):
            parse(bad)


# ----------------------------------------------------------------------
# Retry: transparent recovery and budget exhaustion
# ----------------------------------------------------------------------

def test_retry_is_transparent_and_bit_identical(conv_op, conv_space,
                                                genes):
    ref = run_eval(conv_op, conv_space, genes)
    r0 = counter("resilience.retries")
    with faultinject.scoped("crash@chunk:1"):
        ev = run_eval(conv_op, conv_space, genes, retry=FAST)
    assert counter("resilience.retries") == r0 + 1
    assert ev_sig(ev) == ev_sig(ref)


def test_retry_exhaustion_surfaces_device_error(conv_op, conv_space,
                                                genes):
    with faultinject.scoped("crash@chunk:1x99"):
        with pytest.raises(DeviceError) as ei:
            run_eval(conv_op, conv_space, genes, retry=FAST)
    assert ei.value.details["attempts"] == FAST.max_attempts
    assert isinstance(ei.value, RuntimeError)          # taxonomy contract
    assert "failed after" in ei.value.one_line()


def test_oom_splits_chunk_and_matches(conv_op, conv_space, genes):
    ref = run_eval(conv_op, conv_space, genes)
    s0 = counter("resilience.chunk_splits")
    with faultinject.scoped("oom@chunk:2"):
        ev = run_eval(conv_op, conv_space, genes, retry=FAST)
    assert counter("resilience.chunk_splits") >= s0 + 1
    assert ev_sig(ev) == ev_sig(ref)


# ----------------------------------------------------------------------
# Kill + checkpoint resume (the headline bit-identity contract)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("ndev", sorted({1, NDEV}))
def test_kill_resume_bit_identical(conv_op, conv_space, genes, tmp_path,
                                   ndev):
    ref = run_eval(conv_op, conv_space, genes, n_devices=ndev)
    ck = SweepCheckpoint(str(tmp_path), f"kr{ndev}", every_chunks=1)
    with faultinject.scoped("kill@chunk:1"):
        with pytest.raises(SweepKilled):
            run_eval(conv_op, conv_space, genes, n_devices=ndev, ckpt=ck)
    assert os.path.exists(ck.path)
    r0 = counter("resilience.checkpoint_resumes")
    ev = run_eval(conv_op, conv_space, genes, n_devices=ndev, ckpt=ck)
    assert counter("resilience.checkpoint_resumes") == r0 + 1
    assert ev_sig(ev) == ev_sig(ref)
    assert not os.path.exists(ck.path)       # cleared on completion


def test_truncated_checkpoint_quarantined_and_rerun(conv_op, conv_space,
                                                    genes, tmp_path):
    ref = run_eval(conv_op, conv_space, genes)
    ck = SweepCheckpoint(str(tmp_path), "tr", every_chunks=1)
    # every save is truncated post-commit, then the sweep dies at chunk 4
    with faultinject.scoped("truncate@checkpoint:0x999,kill@chunk:4"):
        with pytest.raises(SweepKilled):
            run_eval(conv_op, conv_space, genes, ckpt=ck)
    c0 = counter("resilience.checkpoint_corrupt")
    ev = run_eval(conv_op, conv_space, genes, ckpt=ck)
    assert counter("resilience.checkpoint_corrupt") == c0 + 1
    assert os.path.exists(ck.path + ".corrupt")
    assert ev_sig(ev) == ev_sig(ref)          # full restart, same answer


def test_stale_checkpoint_discarded(conv_op, conv_space, genes, tmp_path):
    ck = SweepCheckpoint(str(tmp_path), "st", every_chunks=1)
    with faultinject.scoped("kill@chunk:2"):
        with pytest.raises(SweepKilled):
            run_eval(conv_op, conv_space, genes, ckpt=ck)
    other = sample_genes(conv_space, np.random.default_rng(9), 256)
    ref = run_eval(conv_op, conv_space, other)
    s0 = counter("resilience.checkpoint_stale")
    ev = run_eval(conv_op, conv_space, other, ckpt=ck)
    assert counter("resilience.checkpoint_stale") == s0 + 1
    assert ev_sig(ev) == ev_sig(ref)


def test_search_ckpt_dir_resume(conv_op, conv_space, tmp_path):
    kw = dict(budget=96, block=32, strategy="random", seed=3,
              num_pes=PES, noc_bw=BW, space=conv_space, devices=1,
              pipeline="gene")
    ref = search_impl(conv_op, **kw)
    with faultinject.scoped("kill@chunk:1"):
        with pytest.raises(SweepKilled):
            search_impl(conv_op, ckpt_dir=str(tmp_path), **kw)
    assert any(f.startswith("sweep-") for f in os.listdir(tmp_path))
    res = search_impl(conv_op, ckpt_dir=str(tmp_path), **kw)
    assert res.best_value == ref.best_value
    assert res.best_point == ref.best_point
    assert [e["value"] for e in res.top_k] == \
        [e["value"] for e in ref.top_k]


def test_joint_sweep_kill_resume(conv_op, conv_space, tmp_path):
    genes = sample_genes(conv_space, np.random.default_rng(0), 48)
    cfg = DSEConfig(pe_range=(32, 64, 96, 128), bw_range=(8.0, 16.0),
                    batch=1024)

    def sig(r):
        return ([(t["value"], t["point"], t["num_pes"], t["noc_bw"])
                 for t in r.top],
                [(p["point"], p["energy_pj"], p["throughput"])
                 for p in r.pareto], r.n_valid)

    ref = joint_sweep(conv_op, conv_space, genes, cfg, chunk_designs=64)
    ck = SweepCheckpoint(str(tmp_path), "joint")
    with faultinject.scoped("kill@design-chunk:2"):
        with pytest.raises(SweepKilled):
            joint_sweep(conv_op, conv_space, genes, cfg,
                        chunk_designs=64, ckpt=ck)
    assert os.path.exists(ck.path)
    res = joint_sweep(conv_op, conv_space, genes, cfg, chunk_designs=64,
                      ckpt=ck)
    assert sig(res) == sig(ref)
    assert not os.path.exists(ck.path)


# ----------------------------------------------------------------------
# Session: error boundary, degradation, batch isolation
# ----------------------------------------------------------------------

def _query(name="res-q", budget=96, seed=3):
    op = ta.conv2d(name, k=8, c=6, y=12, x=12, r=3, s=3)
    return Query(Workload.of_layer(op), Hardware(num_pes=PES, noc_bw=BW),
                 SearchSpec(budget=budget, block=32, strategy="random",
                            seed=seed))


def _session(**kw):
    return Session(resilience=ResilienceConfig(retry=FAST, **kw))


def test_session_degrades_to_legacy(conv_op):
    q = _query()
    d0 = counter("resilience.degraded_queries")
    with faultinject.scoped("crash@chunk:0x9999"):
        rep = _session().run(q)
    assert rep.kind == "layer"
    assert rep.extras["pipeline"] == "legacy"
    dg = rep.extras["degraded"]
    assert dg["from"] == "gene" and dg["to"] == "legacy"
    assert "DeviceError" in dg["error"]
    assert counter("resilience.degraded_queries") == d0 + 1
    # the degraded report is still a real answer
    assert np.isfinite(rep.best["value"]) and rep.n_evaluated > 0


def test_session_degrade_off_raises_classified():
    with faultinject.scoped("crash@chunk:0x9999"):
        with pytest.raises(DeviceError):
            _session(degrade=False).run(_query())


def test_run_many_isolates_poisoned_batch():
    qs = [_query(), _query("res-q2", budget=64, seed=1)]
    b0 = counter("resilience.batch_degraded")
    with faultinject.scoped("crash@chunk:0x9999"):
        reps = _session().run_many(qs)
    assert counter("resilience.batch_degraded") == b0 + 1
    assert [r.kind for r in reps] == ["layer", "layer"]
    assert all(r.extras.get("degraded") for r in reps)


def test_run_many_kill_resume_bit_identical(tmp_path):
    qs = [_query(), _query("res-q2", budget=64, seed=1)]
    clean = _session().run_many(qs)
    sig = [r.results_json() for r in clean]
    with faultinject.scoped("kill@chunk:1"):
        with pytest.raises(SweepKilled):
            _session(ckpt_dir=str(tmp_path)).run_many(qs)
    assert any(f.startswith("sweep-batch-") for f in os.listdir(tmp_path))
    r0 = counter("resilience.checkpoint_resumes")
    resumed = _session(ckpt_dir=str(tmp_path)).run_many(qs)
    assert counter("resilience.checkpoint_resumes") == r0 + 1
    assert [r.results_json() for r in resumed] == sig
    assert not os.listdir(tmp_path)           # cleared on completion


# ----------------------------------------------------------------------
# Spec validation
# ----------------------------------------------------------------------

def test_spec_errors_name_the_field():
    cases = [
        (lambda: SearchSpec(objective="speed"), "objective"),
        (lambda: SearchSpec(strategy="warp"), "strategy"),
        (lambda: SearchSpec(pipeline="quantum"), "pipeline"),
        (lambda: SearchSpec(budget=0), "budget"),
        (lambda: SearchSpec(block=-4), "block"),
        (lambda: SearchSpec(budget_policy="greedy"), "budget_policy"),
        (lambda: Hardware(num_pes=0), "num_pes"),
        (lambda: Hardware(noc_bw=0.0), "noc_bw"),
        (lambda: Hardware(pe_range=()), "pe_range"),
        (lambda: Hardware(bw_range=(8.0, -1.0)), "bw_range"),
        (lambda: Workload(), "ops"),
        (lambda: Workload(model="nosuch-net"), "model"),
        (lambda: DSEConfig(pe_range=()), "pe_range"),
        (lambda: DSEConfig(batch=0), "batch"),
    ]
    for build, field in cases:
        with pytest.raises(SpecError) as ei:
            build()
        assert ei.value.field == field, (field, ei.value)
        assert isinstance(ei.value, ValueError)   # old callers still work


def test_spec_unknown_json_fields():
    with pytest.raises(SpecError) as ei:
        SearchSpec.from_json({"objective": "edp", "budgett": 9})
    assert ei.value.field == "budgett"
    with pytest.raises(SpecError) as ei:
        Hardware.from_json({"num_pess": 4})
    assert ei.value.field == "num_pess"


def test_spec_literals_agree_with_engine():
    assert set(VALID_OBJECTIVES) == set(OBJECTIVES)
    assert set(VALID_STRATEGIES) == {"auto", *STRATEGIES}
    assert tuple(VALID_PIPELINES) == PIPELINES
    assert set(VALID_BUDGET_POLICIES) == {"adaptive", "uniform"}


def test_cli_prints_one_line_error_and_exits_2(tmp_path, capsys):
    from repro.launch import query as qcli
    bad = tmp_path / "queries.json"
    bad.write_text(json.dumps(
        [{"workload": {"model": "vgg16"}, "search": {"strategy": "warp"}}]))
    with pytest.raises(SystemExit) as ei:
        qcli.main(["--file", str(bad), "--cache-dir", "",
                   "--no-jax-cache"])
    assert ei.value.code == 2
    err = capsys.readouterr().err
    assert err.strip().splitlines()[-1].startswith("error: SpecError")


# ----------------------------------------------------------------------
# Result-cache hardening
# ----------------------------------------------------------------------

def test_cache_corruption_is_quarantined_miss(tmp_path):
    cdir = str(tmp_path)
    mcache.store(cdir, "deadbeef", {"x": 1})
    assert mcache.load(cdir, "deadbeef")["x"] == 1
    path = mcache._path(cdir, "deadbeef")
    with open(path, "w") as f:
        f.write("{not json")
    c0 = counter("result_cache.corrupt")
    assert mcache.load(cdir, "deadbeef") is None
    assert counter("result_cache.corrupt") == c0 + 1
    assert os.path.exists(path + ".corrupt")
    assert mcache.load(cdir, "deadbeef") is None   # now a plain miss
    # the slot is writable again after quarantine
    mcache.store(cdir, "deadbeef", {"x": 2})
    assert mcache.load(cdir, "deadbeef")["x"] == 2


def test_cache_concurrent_writers_never_tear(tmp_path):
    """Server workers share a cache dir: many threads storing the same
    key concurrently must never produce a torn entry — every load
    observes some writer's complete payload."""
    import threading

    cdir = str(tmp_path)
    n_writers, n_rounds = 8, 20
    start = threading.Barrier(n_writers)
    errors = []

    def writer(wid):
        try:
            start.wait()
            for r in range(n_rounds):
                mcache.store(cdir, "shared",
                             {"writer": wid, "round": r,
                              "pad": "x" * 4096})
                got = mcache.load(cdir, "shared")
                assert got is not None, "store then load missed"
                assert len(got["pad"]) == 4096
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    final = mcache.load(cdir, "shared")
    assert final["round"] == n_rounds - 1
    # no leftover temp files: every writer's commit completed
    leftovers = [f for f in os.listdir(cdir) if ".tmp-" in f]
    assert not leftovers, leftovers


def test_checkpointer_skips_unreadable_manifest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3)
    ck.save(1, {"w": np.arange(4)})
    ck.save(2, {"w": np.arange(4) * 2})
    with open(tmp_path / "step_000000002" / "manifest.json", "w") as f:
        f.write("{oops")
    assert ck.all_steps() == [1]
    assert ck.latest_step() == 1
    state, manifest = ck.restore({"w": np.zeros(4, np.int64)})
    assert manifest["step"] == 1
    assert np.array_equal(state["w"], np.arange(4))


# ----------------------------------------------------------------------
# Straggler watchdog (ported from ft.coordinator)
# ----------------------------------------------------------------------

def test_watchdog_flags_stragglers_without_poisoning_ewma():
    wd = StragglerWatchdog(threshold=3.0, alpha=0.2)
    assert wd.observe(1.0) is False           # first sample seeds EWMA
    assert wd.observe(1.0) is False
    assert wd.observe(10.0) is True           # 10 > 3 x 1.0
    assert wd.ewma == pytest.approx(1.0)      # straggler didn't update it
    assert wd.slow_count == 1
    assert wd.observe(1.2) is False           # baseline keeps adapting
    assert wd.ewma == pytest.approx(1.04)


def test_ft_loop_delegates_to_shared_watchdog(tmp_path):
    ck = Checkpointer(str(tmp_path))
    loop = FaultTolerantLoop(lambda s, b: (s, {}), ck)
    assert isinstance(loop._watchdog, StragglerWatchdog)
    for i, w in enumerate([1.0, 1.0, 10.0, 1.0]):
        loop._observe(i, w)
    assert loop.straggler_steps == [2]
