"""Tests for repro.analysis: the static-verification layer.

Load-bearing properties:

  * each analyzer *detects* its target defect class on a
    deliberately-broken fixture, with the right finding code — a linter
    that cannot catch the planted bug is worse than none;
  * the shipped tree is CLEAN: repo lint + dataflow corpus + jaxpr
    audit produce zero unwaived findings, and every checked-in waiver
    still matches something (unused waivers fail);
  * the jaxpr audit covers every (op-class, level-count) family variant
    CI compiles, at 1 and ``jax.local_device_count()`` devices, with
    the traced primitive count inside the checked-in budget;
  * the found-by-linter fixes hold under concurrency: the result-cache
    occupancy gauges track every directory transition exactly, and
    ``FlightRecorder.maybe_dump`` dumps once per interval no matter how
    many threads race it.
"""
import json
import os
import threading
import textwrap

import pytest

from repro.analysis import (CODES, Finding, Waiver, apply_waivers,
                            load_waivers, run_repo_lint)
from repro.analysis import concurrency, speclint
from repro.analysis.concurrency import ModulePolicy, lint_source
from repro.core.directives import Cluster, Dataflow, SpatialMap, TemporalMap
from repro.core.tensor_analysis import conv2d


CONV = conv2d("an-conv", k=64, c=64, y=28, x=28, r=3, s=3)


# ----------------------------------------------------------------------
# Finding / waiver schema
# ----------------------------------------------------------------------

def test_finding_schema_validates():
    f = Finding(code="SPEC-TILE", site="x.py::f", message="m",
                severity="warn")
    assert f.code in CODES and "SPEC-TILE" in f.one_line()
    with pytest.raises(ValueError):
        Finding(code="NOT-A-CODE", site="s", message="m")
    with pytest.raises(ValueError):
        Finding(code="SPEC-TILE", site="s", message="m", severity="meh")
    with pytest.raises(ValueError):
        Waiver(code="SPEC-TILE", site="s", reason="")


def test_waivers_partition_and_unused_detection():
    f1 = Finding(code="CONC-GLOBAL", site="a.py::f", message="m")
    f2 = Finding(code="CONC-GLOBAL", site="b.py::g", message="m")
    w_used = Waiver(code="CONC-GLOBAL", site="a.py::f", reason="ok")
    w_unused = Waiver(code="CONC-UNLOCKED", site="zz.py::h", reason="ok")
    unwaived, waived, unused = apply_waivers([f1, f2], [w_used, w_unused])
    assert [f.site for f in unwaived] == ["b.py::g"]
    assert [f.site for f in waived] == ["a.py::f"]
    assert unused == [w_unused]


def test_checked_in_waivers_load_and_all_match():
    waivers = load_waivers()
    assert waivers, "waivers.toml should ship at least one waiver"
    unwaived, _, unused = apply_waivers(run_repo_lint(), waivers)
    assert unwaived == [], [f.one_line() for f in unwaived]
    assert unused == [], [f"{w.code} @ {w.site}" for w in unused]


# ----------------------------------------------------------------------
# Concurrency linter: broken fixtures
# ----------------------------------------------------------------------

_BROKEN_COUNTER = textwrap.dedent("""\
    import threading

    class Ring:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []
            self._count = 0

        def locked_add(self, x):
            with self._lock:
                self._items.append(x)
                self._count += 1

        def racy_add(self, x):
            self._items.append(x)
            self._count += 1
""")


def test_concurrency_catches_unlocked_mutation_and_allows_locked():
    fs = lint_source(_BROKEN_COUNTER, "fix/ring.py", ModulePolicy())
    codes = {(f.code, f.site) for f in fs}
    assert ("CONC-UNLOCKED", "fix/ring.py::Ring.racy_add") in codes
    assert all("locked_add" not in f.site for f in fs)


def test_concurrency_catches_global_contextvar_threadlocal():
    src = textwrap.dedent("""\
        import threading
        from contextvars import ContextVar

        CURRENT = ContextVar("current")
        TOTAL = 0

        def bump():
            global TOTAL
            TOTAL += 1

        def set_and_leak(v):
            CURRENT.set(v)

        def set_and_reset(v):
            tok = CURRENT.set(v)
            CURRENT.reset(tok)

        def per_call_local():
            tls = threading.local()
            return tls
    """)
    fs = lint_source(src, "fix/ctx.py", ModulePolicy())
    codes = {(f.code, f.site) for f in fs}
    assert ("CONC-GLOBAL", "fix/ctx.py::bump") in codes
    assert ("CONC-CONTEXTVAR", "fix/ctx.py::set_and_leak") in codes
    assert ("CONC-THREADLOCAL", "fix/ctx.py::per_call_local") in codes
    assert all("set_and_reset" not in f.site for f in fs)


def test_concurrency_policy_exempts_unshared_classes():
    policy = ModulePolicy(unshared={"Ring": "externally locked"})
    assert lint_source(_BROKEN_COUNTER, "fix/ring.py", policy) == []


def test_concurrency_registry_covers_threaded_modules():
    for rel in ("serve/coalescer.py", "obs/metrics.py",
                "mapspace/cache.py", "obs/flightrec.py"):
        assert rel in concurrency.THREADED


# ----------------------------------------------------------------------
# Spec/dataflow linter: broken fixtures + clean corpus
# ----------------------------------------------------------------------

def test_speclint_non_divisor_tile_is_caught():
    df = Dataflow("bad-tile", (TemporalMap(5, 5, "K"), SpatialMap(1, 1, "C")))
    fs = speclint.lint_dataflow(df, CONV)
    assert [f.code for f in fs] == ["SPEC-TILE"]
    assert "does not divide" in fs[0].message


def test_speclint_sliding_window_is_not_a_tile_violation():
    # YX-P style: offset < size on X is a sliding window, never SPEC-TILE
    df = Dataflow("win", (TemporalMap(10, 8, "X"), SpatialMap(1, 1, "K")))
    assert speclint.lint_dataflow(df, CONV) == []


def test_speclint_cluster_and_spatial_fixtures():
    empty = Dataflow("c-empty", (SpatialMap(1, 1, "K"), Cluster(8)))
    assert [f.code for f in speclint.lint_dataflow(empty, CONV)] \
        == ["SPEC-CLUSTER"]
    big = Dataflow("c-big", (SpatialMap(1, 1, "K"), Cluster(64),
                             SpatialMap(1, 1, "C")))
    assert [f.code for f in
            speclint.lint_dataflow(big, CONV, num_pes=16)] \
        == ["SPEC-CLUSTER"]
    ragged = Dataflow("sp", (SpatialMap(2, 2, "Y"), SpatialMap(3, 3, "R")))
    assert [f.code for f in speclint.lint_dataflow(ragged, CONV)] \
        == ["SPEC-SPATIAL"]


def test_speclint_oversize_span_warns_illegal():
    df = Dataflow("over", (TemporalMap(100, 100, "K"),))
    fs = speclint.lint_dataflow(df, CONV)
    assert {(f.code, f.severity) for f in fs} \
        == {("SPEC-ILLEGAL", "warn")}


def test_speclint_parse_error_is_a_finding_not_a_crash():
    fs = speclint.lint_text("TemporalMap(2,2) K\nTemporalMap(3,3) K", CONV)
    assert [f.code for f in fs] == ["SPEC-PARSE"]
    ok = speclint.lint_text("SpatialMap(1,1) K\nTemporalMap(2,2) C", CONV)
    assert ok == []


def test_speclint_shipped_corpus_is_clean():
    assert speclint.lint_corpus() == []


def _query(**search):
    from repro.api import Query
    return Query.from_json({
        "workload": {"op": {"type": "conv2d", "name": "an-q", "k": 64,
                            "c": 64, "y": 28, "x": 28, "r": 3, "s": 3}},
        "hardware": {"num_pes": 48},
        "search": {"objective": "edp", **search}})


def test_speclint_query_bad_dims_and_budget():
    errs = speclint.errors_only(speclint.lint_query(
        _query(dims=["K", "Z"])))
    assert [f.code for f in errs] == ["SPEC-DIMS"]
    errs = speclint.errors_only(speclint.lint_query(
        _query(l1_prune_kb=0.001)))
    assert [f.code for f in errs] == ["SPEC-BUDGET"]
    assert speclint.errors_only(speclint.lint_query(_query())) == []


def test_query_lint_raises_specerror_with_findings():
    from repro.resilience.errors import SpecError
    with pytest.raises(SpecError) as ei:
        _query(dims=["K", "Z"]).lint()
    assert ei.value.details["findings"][0]["code"] == "SPEC-DIMS"
    _query().lint()          # legal query: no raise


# ----------------------------------------------------------------------
# Jaxpr audit: broken fixtures
# ----------------------------------------------------------------------

def _case(fn, ops, **kw):
    from repro.analysis.jaxpr_audit import FamilyCase
    return FamilyCase(name="fix:L1/x", family="fix:L1", fn=fn, ops=ops,
                      kind=kw.pop("kind", "plain"), **kw)


def test_jaxpr_audit_catches_f64_upcast():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis.jaxpr_audit import audit_case
    ops = {"x": np.ones((4,), np.float32)}
    with jax.enable_x64(True):
        fs, _ = audit_case(_case(
            lambda o: jnp.asarray(o["x"], jnp.float64) * 2.0, ops))
    assert "JAX-F64" in {f.code for f in fs}
    assert "JAX-WIDEN" in {f.code for f in fs}


def test_jaxpr_audit_catches_host_callback():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis.jaxpr_audit import audit_case

    def with_cb(o):
        return jax.pure_callback(
            lambda x: x, jax.ShapeDtypeStruct((4,), jnp.float32), o["x"])

    fs, _ = audit_case(_case(with_cb, {"x": np.ones((4,), np.float32)}))
    assert "JAX-CALLBACK" in {f.code for f in fs}


def test_jaxpr_audit_catches_ignored_operand():
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis.jaxpr_audit import audit_case
    ops = {"used": np.ones((4,), np.float32),
           "ignored": np.ones((4,), np.float32)}
    fn = lambda o: jnp.sum(o["used"])          # noqa: E731
    fs, _ = audit_case(_case(fn, ops, unwrapped=fn, unwrapped_ops=ops))
    bad = [f for f in fs if f.code == "JAX-CONSTFOLD"]
    assert len(bad) == 1 and "'ignored'" in bad[0].message


def test_jaxpr_audit_catches_non_shrinking_reduce():
    import numpy as np
    from repro.analysis.jaxpr_audit import audit_case
    ops = {"x": np.ones((64,), np.float32)}
    fs, _ = audit_case(_case(lambda o: o["x"] * 2.0, ops, kind="reduced"))
    assert "JAX-SHRINK" in {f.code for f in fs}


def test_jaxpr_audit_primitive_budget_trips():
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis import jaxpr_audit

    def big(o):
        x = o["x"]
        for _ in range(40):
            x = jnp.sin(x) + 1.0
        return x

    old = dict(jaxpr_audit.PRIMITIVE_BUDGET)
    jaxpr_audit.PRIMITIVE_BUDGET["fix:L1"] = 10
    try:
        fs, n = jaxpr_audit.audit_case(
            _case(big, {"x": np.ones((4,), np.float32)}))
    finally:
        jaxpr_audit.PRIMITIVE_BUDGET.clear()
        jaxpr_audit.PRIMITIVE_BUDGET.update(old)
    assert n > 10
    assert "JAX-PRIMBUDGET" in {f.code for f in fs}


def test_jaxpr_audit_trace_error_is_a_finding():
    import numpy as np
    from repro.analysis.jaxpr_audit import audit_case
    fs, n = audit_case(_case(
        lambda o: o["missing-key"], {"x": np.ones((4,), np.float32)}))
    assert n == 0 and [f.code for f in fs] == ["JAX-TRACE"]


# ----------------------------------------------------------------------
# Jaxpr audit: the shipped families are clean (1 and N devices)
# ----------------------------------------------------------------------

def test_jaxpr_audit_shipped_families_clean_all_devices():
    import jax
    from repro.analysis.jaxpr_audit import PRIMITIVE_BUDGET, audit
    nd = jax.local_device_count()
    counts = (1,) if nd <= 1 else (1, nd)
    findings, report = audit(counts)
    assert findings == [], [f.one_line() for f in findings]
    # every (op, level-count) family variant traced, budget recorded
    fams = {name.split("/")[0] for name in report["primitive_counts"]}
    assert fams == set(PRIMITIVE_BUDGET)
    assert report["device_counts"] == list(counts)
    for name, n in report["primitive_counts"].items():
        assert 0 < n, name


# ----------------------------------------------------------------------
# Found-by-linter regressions
# ----------------------------------------------------------------------

def test_cache_gauges_consistent_under_concurrent_writers(tmp_path):
    """PR-9 bug: gauges were published from an unsynchronized scan.  Now
    every directory transition (store commit, corrupt quarantine) and
    its gauge delta share one lock — so after any storm of concurrent
    writers, gauges == directory truth, with no rescan needed."""
    from repro import obs
    from repro.mapspace import cache

    d = str(tmp_path / "rc")
    cache.cache_stats(d)           # baseline the gauges for this dir
    errs = []

    def writer(w):
        try:
            for i in range(20):
                cache.store(d, f"w{w}-{i}", {"payload": list(range(8))})
                if i % 5 == 0:
                    cache.cache_stats(d)
        except Exception as e:    # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(w,)) for w in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == []

    m = obs.metrics()
    names = [n for n in os.listdir(d)
             if n.startswith("mapsearch-") and n.endswith(".json")]
    truth_bytes = sum(os.path.getsize(os.path.join(d, n)) for n in names)
    # incremental accounting alone (no trailing rescan) matches the dir
    assert m.gauge_value("result_cache.entries") == len(names) == 160
    assert m.gauge_value("result_cache.bytes") == truth_bytes
    # and the locked rescan agrees
    assert cache.cache_stats(d) == (len(names), truth_bytes)


def test_cache_quarantine_adjusts_gauges(tmp_path):
    from repro import obs
    from repro.mapspace import cache

    d = str(tmp_path / "rc")
    cache.cache_stats(d)
    cache.store(d, "good", {"v": 1})
    # plant a corrupt entry by hand, rescan to count it…
    bad = os.path.join(d, "mapsearch-bad.json")
    with open(bad, "w") as f:
        f.write("{truncated")
    e0, _ = cache.cache_stats(d)
    assert e0 == 2
    # …then the quarantining miss must subtract it from the gauges
    assert cache.load(d, "bad") is None
    assert os.path.exists(bad + ".corrupt")
    m = obs.metrics()
    assert m.gauge_value("result_cache.entries") == 1
    assert cache.cache_stats(d)[0] == 1


def test_maybe_dump_single_claim_under_race(tmp_path):
    """The found-by-linter flightrec fix: of N threads racing past the
    rate-limit interval, exactly one dumps."""
    from repro.obs.flightrec import FlightRecorder

    rec = FlightRecorder(capacity=16)
    rec.record("event", "warmup")
    results, barrier = [], threading.Barrier(8)

    def racer():
        barrier.wait()
        results.append(rec.maybe_dump(str(tmp_path), "storm",
                                      min_interval_s=60.0))

    threads = [threading.Thread(target=racer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    paths = [r for r in results if r is not None]
    assert len(paths) == 1
    with open(paths[0]) as f:
        assert json.load(f)["reason"] == "storm"
    # a second storm inside the interval stays suppressed
    assert rec.maybe_dump(str(tmp_path), "storm",
                          min_interval_s=60.0) is None


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------

def test_lint_cli_json_report_is_bench_schema(tmp_path):
    from repro.launch import lint as lint_cli

    out = str(tmp_path / "lint.json")
    rc = lint_cli.main(["--no-jaxpr", "--json", "--out", out, "-q"])
    assert rc == 0
    with open(out) as f:
        doc = json.load(f)
    assert (doc["kind"], doc["name"]) == ("bench", "lint")
    assert doc["n_unwaived"] == 0 and doc["unused_waivers"] == []
    assert "environment" in doc            # provenance block rides along
    from repro.api import Report
    rep = Report.from_json(doc)            # round-trips like any bench
    assert rep.name == "lint"


def test_lint_cli_fails_on_unused_waiver(tmp_path):
    from repro.launch import lint as lint_cli

    wpath = str(tmp_path / "waivers.toml")
    with open("src/repro/analysis/waivers.toml") as f:
        base = f.read()
    with open(wpath, "w") as f:
        f.write(base + '\n[[waiver]]\ncode = "CONC-UNLOCKED"\n'
                       'site = "zz/nowhere.py::gone"\n'
                       'reason = "stale"\n')
    rc = lint_cli.main(["--no-jaxpr", "--waivers", wpath, "-q"])
    assert rc == 1
