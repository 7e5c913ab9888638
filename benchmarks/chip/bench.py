"""Chip benchmark of the MAESTRO DSE engine, driven by BENCHMARK.json.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One run of one cell (an entry of BENCHMARK.json's ``workloads``) on the
TPU chips of this machine:

  1. refuses to run unless JAX finds TPUs, as many as the cell asks for;
  2. builds one ``repro.api.Session`` with no result cache, no retry, no
     split and no degraded answer, its compile cache in ``.jax_cache/``
     at the root of the checkout;
  3. warms up with small queries of the cell's own shapes (search seed
     0, which the window never uses), and counts all of that as set-up;
  4. drives ``Session.run`` in a closed loop with one client for
     ``--seconds``: every query distinct, drawn from ``--seed`` by the
     generator ``querygen.py`` from the cell's configuration file
     (``configs/``) and traffic file (``traffic/``). No query starts
     after ``--seconds``; the window ends at the last answer;
  5. with ``--trace 1``, records the program's ``repro.obs`` spans and a
     ``jax.profiler`` trace of the whole window;
  6. checks every answer of the window against the plain reference
     (``check.py``) once the window has closed;
  7. prints the number of compiles inside the window, then each compared
     number beside its limit on standard error, and as the last line of
     standard output one JSON object with ``correct``, ``attempted``,
     ``failed``, ``metrics``, ``device`` (and, traced, ``breakdown``),
     and ``checks`` last.

Each metric named in BENCHMARK.json is read by ``metrics/<name>.py``:
with ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Callable  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.relpath(HERE, ROOT)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
SYNC = "bench-sync"
# Counters that move only when a chunk was retried, split, degraded or
# cancelled: each a fallback that would hide a failure.
FALLBACK_COUNTERS = ("resilience.retries", "resilience.chunk_splits",
                     "resilience.degraded_queries",
                     "resilience.batch_degraded",
                     "resilience.cancelled_chunks")


class BenchError(Exception):
    """The run cannot be measured: no result is printed."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict[str, Any]
    traffic: dict[str, Any]
    end_to_end: list[dict[str, Any]]
    per_layer: list[dict[str, Any]]

    @property
    def kind(self) -> str:
        return self.traffic["query"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Resolve a cell and everything it names, by name, from
    BENCHMARK.json."""
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    spec = load_json(path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; one of "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, BENCH_DIR, "traffic",
                                     f"{w['traffic']}.json"))
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def reader(metric: str) -> Callable[["Run"], float | None]:
    """``read`` of ``metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class QueryRecord:
    wire: dict[str, Any]
    start: float
    end: float
    report: Any = None
    error: str | None = None
    n_evaluated: int = 0


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    setup_s: float
    t0: float                        # window start, perf_counter s
    t1: float                        # last answer, perf_counter s
    queries: list[QueryRecord]
    spans: list[dict] | None = None  # repro.obs span events, traced
    tracer_t0: float = 0.0           # the tracer's zero, perf_counter s
    device: Any = None               # devtrace.DeviceSummary, traced

    @property
    def done(self) -> list[QueryRecord]:
        return [q for q in self.queries if q.error is None]


def require_chips(jax, n: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise BenchError(f"needs {n} chips; JAX found {len(devs)}")
    return devs[:n]


class CompileCounter:
    """Executables built, compiled or loaded from the compile cache, as
    ``jax.monitoring`` reports them."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self, monitoring):
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, duration: float, **_: Any) -> None:
        if event in self.EVENTS:
            self.n += 1


def _stat(d: dict[str, Any], keys) -> dict[str, float]:
    return {k: float(d[k]) for k in keys}


def answer(cell: Cell, rec: QueryRecord) -> dict[str, Any]:
    """The plain record of one answer that ``check.py`` compares: the
    winning dataflows as text, hardware points and reported statistics."""
    from repro.mapspace.space import point_dataflow
    import check
    hw = {k: rec.wire["hardware"][k] for k in ("num_pes", "noc_bw")}
    rep = rec.report
    names = [d["name"] for d in cell.config["layers"]]
    if cell.kind == "layer":
        return {"kind": "layer", "hw": hw,
                "layer": names.index(rec.wire["workload"]["op"]["name"]),
                "best": {"dataflow": str(rep.raw.best_dataflow),
                         **_stat(rep.best["stats"], check.STATS)}}
    if cell.kind == "codse":
        co = rep.raw
        return {"kind": "codse", "hw": hw,
                "mapping": {"dataflow": str(co.search.best_dataflow),
                            **_stat(rep.best["mapping"]["stats"],
                                    check.STATS)},
                "top": [{"dataflow": str(point_dataflow(co.search.space,
                                                        d["point"])),
                         "num_pes": int(d["num_pes"]),
                         "noc_bw": float(d["noc_bw"]),
                         **_stat(d, check.STATS + check.COST_STATS)}
                        for d in co.joint.top[:cell.config["check_top"]]]}
    r = rep.raw
    s = r.schedule
    if [p["layer"] for p in s.per_layer] != names:
        raise BenchError("the schedule's layers are not the "
                         "configuration's")
    return {"kind": "network", "hw": hw,
            "runtime": float(s.runtime), "energy_pj": float(s.energy_pj),
            "layers": [{"dataflow": str(r.best_dataflow(i)),
                        **_stat(p, ("runtime", "energy_pj", "edge_cycles",
                                    "edge_energy_pj"))}
                       for i, p in enumerate(s.per_layer)]}


def make_session(chips: int):
    from repro.api import Session
    from repro.resilience import ResilienceConfig, RetryPolicy
    return Session(cache_dir=None, devices=chips,
                   resilience=ResilienceConfig(
                       degrade=False,
                       retry=RetryPolicy(max_attempts=1, max_splits=0)))


def _failure(rep) -> str | None:
    if rep.kind in ("error", "timeout"):
        return f"{rep.kind} report: {rep.extras.get(rep.kind)}"
    if "degraded" in rep.extras:
        return f"degraded answer: {rep.extras['degraded']}"
    return None


def run_query(session, wire: dict[str, Any]) -> QueryRecord:
    from repro.api import Query
    from repro.resilience import ReproError
    q = Query.from_json(wire)
    t = time.perf_counter()
    try:
        rep = session.run(q)
        err = _failure(rep)
    except ReproError as e:
        rep, err = None, e.one_line()
    rec = QueryRecord(wire, t, time.perf_counter(), rep, err)
    if err is None:
        rec.n_evaluated = int(rep.n_evaluated)
    return rec


def window(session, queries, seconds: float) -> tuple[float, float,
                                                      list[QueryRecord]]:
    """The closed loop: one query at a time until ``seconds`` have
    passed; the window ends at the last answer."""
    recs: list[QueryRecord] = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        recs.append(run_query(session, next(queries)))
    return t0, (recs[-1].end if recs else time.perf_counter()), recs


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            devices: list, *, log: Callable[[str], None]
            ) -> tuple[Run, dict[str, Any]]:
    """Set up, warm up, run the window (traced or not) and read the
    device; returns the run and the ``device`` block."""
    import jax
    from repro import obs
    import querygen

    counter = CompileCounter(jax.monitoring)
    session = make_session(cell.chips)
    log(f"setup import_and_devices_s={time.perf_counter() - T_START:.3f}")
    for wire in querygen.warmup(cell.config, cell.traffic):
        rec = run_query(session, wire)
        if rec.error:
            raise BenchError(f"warm-up query {wire['tag']}: {rec.error}")
        log(f"setup warmup {wire['tag']} s={rec.end - rec.start:.3f} "
            f"compiles={counter.n}")
    met = obs.metrics()
    before = {c: met.value(c) for c in FALLBACK_COUNTERS}
    compiles0, universal0 = counter.n, met.value("universal.compiles")
    queries = querygen.window(cell.config, cell.traffic, seed)
    tracer = logdir = None
    if trace:
        tracer = obs.enable_tracing()
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        # device ops and user annotations only: the Python tracer would
        # record every call the host makes
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        jax.profiler.start_trace(logdir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(SYNC):
            sync_pc = time.perf_counter()
    setup_s = time.perf_counter() - T_START
    t0, t1, recs = window(session, queries, seconds)
    log(f"compiles_in_window universal="
        f"{int(met.value('universal.compiles') - universal0)} "
        f"xla={counter.n - compiles0}")
    moved = {c: met.value(c) - v for c, v in before.items()
             if met.value(c) != v}
    if moved:
        raise BenchError(f"fallback counters moved in the window: {moved}")
    d = devices[0]
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices),
           "memory_peak_bytes": max(
               (x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in devices)}
    run = Run(cell, setup_s, t0, t1, recs)
    if trace:
        jax.profiler.stop_trace()
        obs.disable_tracing()
        run.spans = tracer.spans()
        run.tracer_t0 = time.perf_counter() - tracer.now_us() * 1e-6
        try:
            run.device = reduce_trace(run, logdir, sync_pc)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        dev["busy_s"] = run.device.device_s / cell.chips
        dev["window_s"] = run.device.window_s
    return run, dev


def reduce_trace(run: Run, logdir: str, sync_pc: float):
    import devtrace
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(devtrace.find_xplane(logdir))
    host = [(run.tracer_t0 + e["ts"] * 1e-6,
             run.tracer_t0 + (e["ts"] + e["dur"]) * 1e-6, e["name"])
            for e in run.spans]
    return devtrace.reduce(pd, n_devices=run.cell.chips, sync_name=SYNC,
                           sync_pc=sync_pc, lo=run.t0, hi=run.t1,
                           host_spans=host)


def judge(cell: Cell, run: Run) -> tuple[bool, dict[str, dict]]:
    """Every answer of the window against the plain reference."""
    import check
    ref = check.Reference(cell.config)
    worst: dict[str, int] = {}
    numbers = check.readings(ref, [answer(cell, q) for q in run.done],
                             worst)
    ok, table = check.verdict(numbers, cell.config["limits"][cell.kind])
    for k, i in worst.items():
        print(f"widest {k} in {run.done[i].wire['tag']}", file=sys.stderr)
    return ok and bool(run.done) and len(run.done) == len(run.queries), \
        table


def metrics_of(run: Run, wanted: list[dict[str, Any]]) -> dict[str, dict]:
    out = {}
    for m in wanted:
        v = reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT,
             chips: Callable[[Any, int], list] = require_chips
             ) -> dict[str, Any]:
    """One run of the cell ``name``; returns the result object."""
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    cell = load_cell(name, root)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise BenchError(f"the system under test is not at "
                         f"{os.path.join(root, 'src')}")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    devices = chips(jax, cell.chips)
    d = devices[0]
    log(f"device platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    run, dev = measure(cell, seed, seconds, trace, devices, log=log)
    correct, table = judge(cell, run)
    result = {"correct": correct, "attempted": len(run.queries),
              "failed": len(run.queries) - len(run.done),
              "metrics": metrics_of(run, cell.per_layer if trace
                                    else cell.end_to_end),
              "device": dev}
    if trace:
        result["breakdown"] = run.device.breakdown()
    for q in run.queries:
        if q.error:
            log(f"failed query {q.wire['tag']}: {q.error}")
    for k, t in table.items():
        log(f"check {k} value={t['value']!r} limit={t['limit']!r}")
    result["checks"] = table
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
