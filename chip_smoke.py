"""Chip smoke: drive the DSE engine's main path once on a TPU.

One process holds the chip and runs, through ``repro.api.Session`` and
``repro.serve``:

  layer    VGG16 conv13 at 256 PEs / 32 elem/cycle,
           ``SearchSpec(objective="edp", budget=5000, block=1024)``;
  network  ``Workload.of_network("vgg16")`` with the default policy;
  codse    conv13 over the full default ``DSEConfig`` grid (16,384
           hardware points) with ``joint_genes=640``: ~10.5M designs;
  served   a ``DSEServer`` on an ephemeral port answering the queries of
           ``examples/queries.json`` plus the conv13 layer query, posted
           concurrently so they land in one flush; the answers must equal
           ``repro.serve.execute_batch`` on the same set.

The winners of every layer query and the best co-DSE designs are
re-evaluated with the faithful engine (``core.model.analyze``, host f64)
at ``tests/test_universal.py``'s rel 1e-3.  No result cache, no retry,
no degradation to another engine: any engine failure fails the run.

The per-phase lines are one smoke run, not a benchmark.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the joint sweep, at
                                     # devices=4 against devices=1

The last line of stdout on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro import obs  # noqa: E402
from repro.api import (Hardware, Query, SearchSpec, Session,  # noqa: E402
                       Workload)
from repro.core import dnn_models as zoo  # noqa: E402
from repro.core.dse import DSEConfig  # noqa: E402
from repro.core.model import analyze  # noqa: E402
from repro.mapspace.space import point_dataflow  # noqa: E402
from repro.resilience import (ReproError, ResilienceConfig,  # noqa: E402
                              RetryPolicy)
from repro.serve import (DSEServer, ServeConfig, execute_batch,  # noqa: E402
                         http_json)

REL = 1e-3                    # tests/test_universal.py's tolerance
PES, BW = 256, 32.0           # the layer queries' fixed hardware point
JOINT_GENES = 640             # (640 + 4 winners) x 16,384 = 10.5M designs
# counters that move only when a chunk was retried, split, degraded or
# cancelled — each one a fallback that would hide a failure on the chip
FALLBACK_COUNTERS = ("resilience.retries", "resilience.chunk_splits",
                     "resilience.degraded_queries",
                     "resilience.batch_degraded",
                     "resilience.cancelled_chunks")
ROW_COUNTERS = ("gene.rows_evaluated", "netspace.rows_evaluated")


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def require_tpu(chips: int) -> list:
    """The local devices, if they are TPUs and there are ``chips`` of
    them; fails before anything compiles otherwise."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SmokeFailure(f"needs {chips} chips; JAX found {len(devs)}")
    return devs


def smoke_session(devices: int | None = None) -> Session:
    """A session that replays nothing and hides nothing: no result cache,
    one attempt per chunk, no OOM splitting, no degraded answers."""
    return Session(cache_dir=None, devices=devices,
                   resilience=ResilienceConfig(
                       degrade=False,
                       retry=RetryPolicy(max_attempts=1, max_splits=0)))


def conv13():
    return [op for op in zoo.vgg16() if op.op_type == "CONV2D"][-1]


def layer_query(op) -> Query:
    return Query(Workload.of_layer(op), Hardware(num_pes=PES, noc_bw=BW),
                 SearchSpec(objective="edp", budget=5000, block=1024))


def codse_query(op) -> Query:
    cfg = DSEConfig()
    return Query(Workload.of_layer(op),
                 Hardware(num_pes=PES, noc_bw=BW,
                          pe_range=tuple(cfg.pe_range),
                          bw_range=tuple(cfg.bw_range)),
                 SearchSpec(objective="edp", joint_genes=JOINT_GENES))


def served_queries() -> list[dict]:
    """The wire-format queries of the served phase."""
    with open(os.path.join(ROOT, "examples", "queries.json")) as f:
        wire = json.load(f)["queries"]
    return wire + [{"tag": "vgg16-conv13",
                    "workload": {"model": "vgg16", "layer": "conv13"},
                    "hardware": {"num_pes": PES, "noc_bw": BW},
                    "search": {"objective": "edp", "budget": 5000,
                               "block": 1024}}]


# ----------------------------------------------------------------------
# Checks
# ----------------------------------------------------------------------

def check_report(rep, label: str) -> None:
    if rep.kind in ("error", "timeout"):
        raise SmokeFailure(f"{label}: {rep.kind} report: "
                           f"{rep.extras.get(rep.kind)}")
    if "degraded" in rep.extras:
        raise SmokeFailure(f"{label}: degraded answer: "
                           f"{rep.extras['degraded']}")


def fallback_counts() -> dict[str, float]:
    met = obs.metrics()
    return {c: met.value(c) for c in FALLBACK_COUNTERS}


def agree(label: str, got: dict, ref: dict) -> None:
    for k in ("runtime", "energy_pj", "edp"):
        if not math.isclose(got[k], ref[k], rel_tol=REL):
            raise SmokeFailure(
                f"{label}: {k} {got[k]!r} disagrees with the faithful "
                f"engine's {ref[k]!r} (rel {REL})")


def faithful(op, dataflow, hw) -> dict:
    s = analyze(op, dataflow, hw)
    return {"runtime": float(s.runtime), "energy_pj": float(s.energy_pj),
            "edp": float(s.edp), "l1_kb": float(s.l1_req_kb),
            "l2_kb": float(s.l2_req_kb)}


def check_layer_winner(query: Query, rep, label: str) -> None:
    """The winning mapping of a layer answer against the faithful engine
    at the query's hardware point."""
    raw = rep.raw.search if rep.kind == "layer_codse" else rep.raw
    if hasattr(raw, "best_point"):             # SearchResult
        (op,) = query.workload.resolve()
        got = raw.best_stats
    else:                                      # coalesced FamilyBest
        op = raw.op
        got = rep.best["stats"]
    agree(label, got, faithful(op, raw.best_dataflow,
                               query.hardware.hwconfig()))


def check_codse_designs(query: Query, rep, label: str, n: int = 4) -> None:
    """The best joint-sweep designs against the faithful engine plus
    ``run_dse``'s SRAM placement, area and leakage accounting."""
    co = rep.raw
    cfg = query.hardware.dse_config()
    (op,) = query.workload.resolve()
    if not co.joint.top:
        raise SmokeFailure(f"{label}: the joint sweep found no valid design")
    for i, d in enumerate(co.joint.top[:n]):
        hw = query.hardware.hwconfig().replace(num_pes=d["num_pes"],
                                               noc_bw=d["noc_bw"])
        ref = faithful(op, point_dataflow(co.search.space, d["point"]), hw)
        sram = ref["l1_kb"] * d["num_pes"] + ref["l2_kb"]
        area = cfg.area_power.area(d["num_pes"], sram, d["noc_bw"])
        ref["energy_pj"] += cfg.area_power.static_energy_pj(
            area, ref["runtime"])
        ref["edp"] = ref["energy_pj"] * ref["runtime"]
        agree(f"{label} top[{i}]", d, ref)


# ----------------------------------------------------------------------
# Phases
# ----------------------------------------------------------------------

def run_phase(name: str, fn, devices: list) -> object:
    """Run one phase; fail when no row reached the device or a fallback
    fired, and print its one-run line."""
    met = obs.metrics()
    rows0 = sum(met.value(c) for c in ROW_COUNTERS)
    fallback0 = fallback_counts()
    comp0 = met.value("universal.compiles")
    comp_s0 = met.value("universal.compile_s")
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    rows = sum(met.value(c) for c in ROW_COUNTERS) - rows0
    if rows <= 0:
        raise SmokeFailure(f"{name}: no rows were evaluated on the device")
    moved = {c: v - fallback0[c] for c, v in fallback_counts().items()
             if v != fallback0[c]}
    if moved:
        raise SmokeFailure(f"{name}: fallback counters moved: {moved}")
    print(f"chip smoke (one run, not a benchmark) phase={name} "
          f"wall_s={wall:.3f} "
          f"compiles={int(met.value('universal.compiles') - comp0)} "
          f"compile_s={met.value('universal.compile_s') - comp_s0:.3f} "
          f"rows={int(rows)} device_kind={devices[0].device_kind} "
          f"devices={len(devices)}", flush=True)
    return out


def layer_phase(session: Session, op) -> None:
    q = layer_query(op)
    rep = session.run(q)
    check_report(rep, "layer")
    check_layer_winner(q, rep, "layer")


def network_phase(session: Session) -> None:
    rep = session.run(Query(Workload.of_network("vgg16")))
    check_report(rep, "network")
    if not rep.best["edp"] > 0 or not math.isfinite(rep.best["edp"]):
        raise SmokeFailure(f"network: schedule EDP {rep.best['edp']!r}")


def codse_phase(session: Session, op):
    q = codse_query(op)
    rep = session.run(q)
    check_report(rep, "codse")
    check_layer_winner(q, rep, "codse mapping")
    check_codse_designs(q, rep, "codse")
    return rep


def served_phase(session: Session, wire: list[dict]) -> None:
    """Serve ``wire`` concurrently in one flush; every answer must be a
    real report equal to the offline oracle on the same set."""
    cfg = ServeConfig(port=0, max_batch=len(wire), flush_interval_s=5.0,
                      default_deadline_s=None, max_cost=None,
                      exit_on_kill=False)
    met = obs.metrics()
    flushes0 = met.value("serve.flushes")

    async def serve() -> list[dict]:
        srv = DSEServer(session, cfg)
        await srv.start()
        try:
            answers = await asyncio.gather(*(
                http_json("127.0.0.1", srv.port, "POST", "/query", q,
                          timeout=900.0) for q in wire))
        finally:
            await srv.stop()
        for q, (status, body) in zip(wire, answers):
            if status != 200:
                raise SmokeFailure(f"served {q.get('tag')}: HTTP {status}: "
                                   f"{body}")
            if body["kind"] in ("error", "timeout") or "degraded" in body:
                raise SmokeFailure(
                    f"served {q.get('tag')}: {body['kind']} report: "
                    f"{body.get(body['kind'])}")
        return [body for _, body in answers]

    bodies = asyncio.run(serve())
    if met.value("serve.flushes") - flushes0 != 1:
        raise SmokeFailure("served: the batch split across flushes")
    queries = [Query.from_json(q) for q in wire]
    oracle = execute_batch(session, queries)
    for q, rep, body in zip(queries, oracle, bodies):
        check_report(rep, f"oracle {q.tag}")
        want = json.loads(json.dumps(rep.results_json()))
        if {k: body.get(k) for k in want} != want:
            raise SmokeFailure(f"served {q.tag}: answer differs from "
                               f"execute_batch on the same set")
        if rep.kind in ("layer", "layer_codse"):
            check_layer_winner(q, rep, f"served {q.tag}")


def one_chip(devices: list) -> None:
    op = conv13()
    session = smoke_session()
    run_phase("layer", lambda: layer_phase(session, op), devices)
    run_phase("network", lambda: network_phase(session), devices)
    run_phase("codse", lambda: codse_phase(session, op), devices)
    run_phase("served", lambda: served_phase(smoke_session(),
                                             served_queries()), devices)
    cache = session.jax_cache_dir
    print(f"chip smoke (one run, not a benchmark) compile cache {cache}: "
          f"{len(os.listdir(cache))} entries", flush=True)


def peak_bytes(devices: list) -> list[int]:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices]


def four_chips(devices: list) -> None:
    """The joint sweep striped over four chips against one: top-k rows
    and frontier bit-identical, and every chip received chunks."""
    op = conv13()

    def sweep(nd: int):
        rep = run_phase(f"codse@{nd}dev",
                        lambda: codse_phase(smoke_session(devices=nd), op),
                        devices)
        if rep.raw.joint.n_devices != nd:
            raise SmokeFailure(f"codse@{nd}dev ran on "
                               f"{rep.raw.joint.n_devices} devices")
        return rep.raw.joint

    peak0 = peak_bytes(devices)
    joint = {4: sweep(4)}
    peak4 = peak_bytes(devices)
    joint[1] = sweep(1)
    for what in ("top", "pareto"):
        if getattr(joint[4], what) != getattr(joint[1], what):
            raise SmokeFailure(f"joint sweep {what} differs between 4 "
                               f"devices and 1")
    idle = [d.id for d, a, b in zip(devices, peak0, peak4) if b <= a]
    if idle:
        raise SmokeFailure(f"devices {idle} received no chunks "
                           f"(peak bytes {peak0} -> {peak4})")
    print(f"chip smoke (one run, not a benchmark) 4-vs-1 devices: top "
          f"({len(joint[1].top)} rows) and frontier "
          f"({len(joint[1].pareto)} points) bit-identical; peak bytes per "
          f"device after the 4-device sweep {peak4}", flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the joint sweep, at 4 devices and at 1")
    args = ap.parse_args(argv)
    try:
        devices = require_tpu(args.chips)
        (four_chips if args.chips == 4 else one_chip)(devices)
    except (SmokeFailure, ReproError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
