#!/usr/bin/env bash
# Smoke gate: quick tier-1 subset + quick benchmarks + sharded smoke.
# Full tier-1 is `PYTHONPATH=src python -m pytest -x -q` (see ROADMAP.md).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 quick subset =="
python -m pytest -x -q \
    tests/test_directives.py \
    tests/test_reuse.py \
    tests/test_engine.py \
    tests/test_mapper.py \
    tests/test_mapspace.py \
    tests/test_universal.py \
    tests/test_genes.py \
    tests/test_netspace.py \
    tests/test_api.py \
    tests/test_obs.py \
    tests/test_resilience.py \
    tests/test_serve.py \
    tests/test_analysis.py

echo "== static analysis gate: repro.launch.lint =="
# Zero-findings gate: the jaxpr auditor (f64/widen/callback/weak-type/
# const-fold/donation/primitive-budget over every universal-executable
# family), the concurrency linter, and the dataflow/spec linter must all
# come back clean modulo the checked-in waivers — and every waiver must
# still match something (unused waivers fail the gate too).
python -m repro.launch.lint --json --out benchmarks/out/lint_findings.json

echo "== 4-host-device sharded smoke =="
# The gene pipeline stripes chunks over all local devices; forcing four
# host CPU devices exercises the pmap path and the 1-vs-N-device
# determinism assertions inside tests/test_genes.py, tests/test_netspace.py
# and tests/test_api.py (coalesced run_many) for real.
# tests/test_resilience.py rides along so kill-and-resume bit-identity
# is asserted at 4 devices too (its kill/resume test parametrizes over
# the available device count).  tests/test_analysis.py rides along so
# the jaxpr auditor's shipped-families-clean assertion runs against the
# real pmap executables (1 AND 4 devices), not just the jit path.
XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
    python -m pytest -x -q tests/test_genes.py tests/test_netspace.py \
    tests/test_api.py tests/test_resilience.py tests/test_analysis.py

echo "== small-budget netsearch smoke =="
# End-to-end network schedule search through the CLI shim: VGG16 at a
# tiny budget must complete with the shape-as-operand executables and
# print a schedule + baseline comparison.
python -m repro.launch.netsearch --model vgg16 --quick --no-jax-cache

echo "== declarative batch front door (--file) smoke =="
# Serving-style mixed batch through repro.launch.query: 4 coalescible
# layer queries (conv + GEMM classes, heterogeneous objectives AND fixed
# hardware points), one adaptive-budget network query, one hardware-grid
# co-DSE query.  Runs with --trace + --metrics, so the compile and
# cache budgets below are asserted from the STRUCTURED obs snapshot
# embedded in the --out payload (not grepped from stdout), and the
# Chrome trace_event timeline is validated and uploaded as a CI
# artifact.
python -m repro.launch.query --file examples/queries.json \
    --out benchmarks/out/api_batch_smoke.json \
    --trace benchmarks/out/api_batch_trace.json --metrics \
    --cache-dir '' --no-jax-cache
python - <<'EOF'
import json
d = json.load(open("benchmarks/out/api_batch_smoke.json"))
b = d["batch"]
print(json.dumps(b, indent=2))
assert b["n_queries"] == 6, b
# the 4 layer queries coalesce; network + grid queries route to their
# engines
assert b["n_coalesced"] == 4, b
assert b["n_families"] <= 4, b
assert b["n_compiles"] <= b["compile_budget"], b
kinds = [r["kind"] for r in d["reports"]]
assert kinds.count("layer") == 4, kinds
assert "network" in kinds and "layer_codse" in kinds, kinds
assert all(r["schema_version"] == 2 for r in d["reports"])

# --- obs metrics snapshot: the budget asserts read ONE structured
# payload now ------------------------------------------------------
m = d["metrics"]
c = m["counters"]
assert m["schema_version"] == 1, m["schema_version"]
fam = {k: v for k, v in c.items()
       if k.startswith("universal.compiles_by_family[")}
# single-writer parity: the process total == the per-family sum
assert c["universal.compiles"] == sum(fam.values()), (c, fam)
# the 4 coalesced families (conv + gemm class reps x 1/2 levels)
# compiled EXACTLY once each — the coalescing headline, asserted
# per family instead of as one opaque total
for f in ("q-conv1:L1", "q-conv1:L2", "q-gemm1:L1", "q-gemm1:L2"):
    k = f"universal.compiles_by_family[family={f}]"
    assert fam.get(k) == 1, (k, fam)
assert c["session.queries"] == 6, c
assert c["session.queries_by_kind[kind=layer_coalesced]"] == 4, c
# environment provenance rides with every payload
assert d["environment"]["backend"], d.get("environment")

# --- the trace renders the whole batch as a timeline ---------------
t = json.load(open("benchmarks/out/api_batch_trace.json"))
evs = t["traceEvents"]
assert evs and t["displayTimeUnit"] == "ms", "empty/invalid trace"
names = {e["name"] for e in evs}
for want in ("run_many", "coalesce", "encode", "compile",
             "device-pass", "topk-merge", "compose", "query"):
    assert want in names, (want, sorted(names))
n_compile_spans = sum(e["name"] == "compile" for e in evs)
assert n_compile_spans == b["n_compiles"], \
    (n_compile_spans, b["n_compiles"],
     "one compile span per actual XLA compile")
print(f"trace OK: {len(evs)} events, {n_compile_spans} compile spans")
EOF

echo "== fault-injection kill/resume smoke =="
# The resilience headline, end to end through the CLI: a batch run is
# killed mid-chunk by deterministic fault injection, the re-launch with
# the same flags resumes from the sweep checkpoint, and the resumed
# reports are BIT-IDENTICAL to an undisturbed reference run.  The
# resilience.* recovery counters are asserted from the structured --out
# payload, not grepped from logs.
RES_OUT=benchmarks/out
RES_CKPT="$RES_OUT/resilience_ckpt"
rm -rf "$RES_CKPT"
mkdir -p "$RES_OUT"
cat > "$RES_OUT/resilience_queries.json" <<'EOF'
[
  {"workload": {"op": {"type": "conv2d", "name": "r-conv1",
                       "k": 8, "c": 6, "y": 12, "x": 12, "r": 3, "s": 3}},
   "hardware": {"num_pes": 48, "noc_bw": 12.0},
   "search": {"budget": 96, "block": 32, "strategy": "random", "seed": 3}},
  {"workload": {"op": {"type": "conv2d", "name": "r-conv2",
                       "k": 16, "c": 8, "y": 10, "x": 10, "r": 3, "s": 3}},
   "hardware": {"num_pes": 48, "noc_bw": 12.0},
   "search": {"budget": 64, "block": 32, "strategy": "random", "seed": 1}}
]
EOF
python -m repro.launch.query --file "$RES_OUT/resilience_queries.json" \
    --out "$RES_OUT/resilience_ref.json" --cache-dir '' --no-jax-cache
if python -m repro.launch.query --file "$RES_OUT/resilience_queries.json" \
    --checkpoint-dir "$RES_CKPT" --faults kill@chunk:1 \
    --cache-dir '' --no-jax-cache 2> "$RES_OUT/resilience_kill.log"
then
    echo "FAIL: injected kill@chunk:1 did not kill the sweep"
    exit 1
fi
grep -q SweepKilled "$RES_OUT/resilience_kill.log"
ls "$RES_CKPT"/sweep-batch-*.npz > /dev/null   # checkpoint survived
python -m repro.launch.query --file "$RES_OUT/resilience_queries.json" \
    --checkpoint-dir "$RES_CKPT" \
    --out "$RES_OUT/resilience_resumed.json" --cache-dir '' \
    --no-jax-cache
python - <<'EOF'
import json
DET = ("kind", "name", "objective", "strategy", "best", "top_k",
       "pareto", "n_evaluated")
ref = json.load(open("benchmarks/out/resilience_ref.json"))
res = json.load(open("benchmarks/out/resilience_resumed.json"))
for a, b in zip(ref["reports"], res["reports"]):
    for k in DET:
        assert a.get(k) == b.get(k), (k, a.get(k), b.get(k))
c = res["metrics"]["counters"]
assert c.get("resilience.checkpoint_resumes", 0) >= 1, c
assert c.get("resilience.checkpoint_saves", 0) >= 1, c
print("kill/resume bit-identical across process restarts; "
      f"resumes={c['resilience.checkpoint_resumes']}")
EOF
# a completed sweep clears its checkpoint
if ls "$RES_CKPT"/sweep-*.npz 2>/dev/null; then
    echo "FAIL: checkpoint not cleared after completed resume"
    exit 1
fi

echo "== DSE serving smoke: loadgen + counter invariant =="
# The serving headline, end to end through the CLIs: a real
# repro.launch.serve process on a free port absorbs a 10-client load
# burst; EVERY request must reach a terminal status, request p99 must
# stay under the server deadline, and the admission ledger must balance
# (serve.shed + serve.completed == serve.admitted) — all asserted from
# the STRUCTURED /metricsz snapshot the loadgen appends, not from logs.
SERVE_OUT=benchmarks/out
SERVE_CKPT="$SERVE_OUT/serve_ckpt"
rm -rf "$SERVE_CKPT"
mkdir -p "$SERVE_OUT"
cat > "$SERVE_OUT/serve_queries.json" <<'EOF'
[
  {"tag": "s-a",
   "workload": {"op": {"type": "conv2d", "name": "s-conv1",
                       "k": 8, "c": 6, "y": 10, "x": 10, "r": 3, "s": 3}},
   "hardware": {"num_pes": 48, "noc_bw": 12.0},
   "search": {"objective": "edp", "budget": 32, "block": 64}},
  {"tag": "s-b",
   "workload": {"op": {"type": "conv2d", "name": "s-conv2",
                       "k": 12, "c": 6, "y": 10, "x": 10, "r": 3, "s": 3}},
   "hardware": {"num_pes": 48, "noc_bw": 12.0},
   "search": {"objective": "runtime", "budget": 32, "block": 64}}
]
EOF
SERVE_PORT=$(python - <<'EOF'
import socket
s = socket.socket()
s.bind(("127.0.0.1", 0))
print(s.getsockname()[1])
s.close()
EOF
)
SERVE_DEADLINE=120
python -m repro.launch.serve --port "$SERVE_PORT" \
    --deadline "$SERVE_DEADLINE" --checkpoint-dir "$SERVE_CKPT" \
    --cache-dir '' --no-jax-cache 2> "$SERVE_OUT/serve.log" &
SERVE_PID=$!
python - "$SERVE_PORT" <<'EOF'
import asyncio, sys, time
from repro.serve import http_json
async def wait_ready(port):
    for _ in range(120):
        try:
            st, body = await http_json("127.0.0.1", port, "GET", "/readyz")
            if st == 200:
                return
        except OSError:
            pass
        await asyncio.sleep(0.5)
    raise SystemExit("server never became ready")
asyncio.run(wait_ready(int(sys.argv[1])))
EOF
python -m repro.launch.loadgen --port "$SERVE_PORT" \
    --file "$SERVE_OUT/serve_queries.json" --clients 10 --requests 2 \
    --metricsz --out "$SERVE_OUT/serve_load.json"
SERVE_DEADLINE="$SERVE_DEADLINE" python - <<'EOF'
import json, os
d = json.load(open("benchmarks/out/serve_load.json"))
assert d["transport_errors"] == 0, d
assert d["n_terminal"] == d["n_requests"] == 20, d
assert set(d["statuses"]) <= {"200", "429", "503"}, d["statuses"]
assert d["p99_s"] < float(os.environ["SERVE_DEADLINE"]), d["p99_s"]
c = d["server_metrics"]["counters"]
shed = c.get("serve.shed", 0)
assert shed + c["serve.completed"] == c["serve.admitted"], c
print(f"serve loadgen OK: p50={d['p50_s']}s p99={d['p99_s']}s "
      f"qps={d['queries_per_s']} shed={shed}")
EOF
# graceful SIGTERM: nothing pending -> clean drain, exit 0
kill -TERM "$SERVE_PID"
SERVE_RC=0; wait "$SERVE_PID" || SERVE_RC=$?
test "$SERVE_RC" -eq 0
if [ -f "$SERVE_CKPT/serve-pending.json" ]; then
    echo "FAIL: clean drain left a pending file"
    exit 1
fi

echo "== DSE serving kill@serve-drain restart drill =="
# Chaos drill: the server dies mid-drain (deterministic fault between
# persisting the unanswered queue and the final flush), a restart with
# the same checkpoint dir recovers the debt, and the recovered answers
# are BIT-IDENTICAL to the offline --file oracle on the same queries —
# the server and the oracle share one execution path.
python -m repro.launch.serve --port "$SERVE_PORT" \
    --checkpoint-dir "$SERVE_CKPT" --faults kill@serve-drain:0 \
    --flush-interval 30 --max-batch 64 --deadline 5 \
    --cache-dir '' --no-jax-cache 2>> "$SERVE_OUT/serve.log" &
SERVE_PID=$!
python - "$SERVE_PORT" "$SERVE_OUT/serve_queries.json" <<'EOF'
import asyncio, json, sys
from repro.serve import http_json
async def main(port, qfile):
    for _ in range(120):
        try:
            st, _ = await http_json("127.0.0.1", port, "GET", "/readyz")
            if st == 200:
                break
        except OSError:
            pass
        await asyncio.sleep(0.5)
    else:
        raise SystemExit("server never became ready")
    # park two requests in the (slow-flush) buffer; fire-and-forget —
    # the drill kills the server before they would be answered
    for q in json.load(open(qfile)):
        r, w = await asyncio.open_connection("127.0.0.1", port)
        body = json.dumps(q).encode()
        w.write(b"POST /query HTTP/1.1\r\nHost: x\r\n"
                b"Content-Length: %d\r\nConnection: close\r\n\r\n"
                % len(body) + body)
        await w.drain()
        await asyncio.sleep(0.3)       # let the server admit it
        w.close()
asyncio.run(main(int(sys.argv[1]), sys.argv[2]))
EOF
kill -TERM "$SERVE_PID"
SERVE_RC=0; wait "$SERVE_PID" || SERVE_RC=$?
test "$SERVE_RC" -eq 17   # os._exit(17): death mid-drain IS the drill
test -f "$SERVE_CKPT/serve-pending.json"
# restart (no faults): recovery replays the persisted queue at start
python -m repro.launch.serve --port "$SERVE_PORT" \
    --checkpoint-dir "$SERVE_CKPT" \
    --cache-dir '' --no-jax-cache 2>> "$SERVE_OUT/serve.log" &
SERVE_PID=$!
python - "$SERVE_PORT" <<'EOF'
import asyncio, sys
from repro.serve import http_json
async def wait_ready(port):
    for _ in range(240):
        try:
            st, _ = await http_json("127.0.0.1", port, "GET", "/readyz")
            if st == 200:
                return
        except OSError:
            pass
        await asyncio.sleep(0.5)
    raise SystemExit("restarted server never became ready")
asyncio.run(wait_ready(int(sys.argv[1])))
EOF
kill -TERM "$SERVE_PID"
SERVE_RC=0; wait "$SERVE_PID" || SERVE_RC=$?
test "$SERVE_RC" -eq 0
test -f "$SERVE_CKPT/serve-recovered.json"
if [ -f "$SERVE_CKPT/serve-pending.json" ]; then
    echo "FAIL: recovery did not clear the pending file"
    exit 1
fi
python -m repro.launch.query --file "$SERVE_OUT/serve_queries.json" \
    --out "$SERVE_OUT/serve_oracle.json" --cache-dir '' --no-jax-cache
python - <<'EOF'
import json
DET = ("kind", "name", "objective", "strategy", "best", "top_k",
       "pareto", "n_evaluated")
rec = json.load(open("benchmarks/out/serve_ckpt/serve-recovered.json"))
oracle = json.load(open("benchmarks/out/serve_oracle.json"))
by_name = {r["name"]: r for r in rec["reports"]}
assert len(by_name) == 2, by_name.keys()
for ref in oracle["reports"]:
    got = by_name[ref["name"]]
    for k in DET:
        assert got.get(k) == ref.get(k), (k, got.get(k), ref.get(k))
print("killed drain recovered bit-identical to the offline oracle")
EOF

echo "== observability smoke: request tracing + SLO histograms =="
# The obs v2 headline, end to end through the CLIs: a traced server
# absorbs a 100-client burst; then (a) ONE client-minted request id must
# thread server -> coalescer -> engine spans in the saved Perfetto
# trace, and (b) every report's extras.timing phases must sum to its
# measured wall latency, and the per-phase Prometheus histogram sums
# must reconcile with the per-report breakdowns — all from structured
# artifacts (the loadgen --out payload + the trace file), not logs.
OBS_OUT=benchmarks/out
OBS_CKPT="$OBS_OUT/obs_serve_ckpt"
rm -rf "$OBS_CKPT"
python -m repro.launch.serve --port "$SERVE_PORT" \
    --checkpoint-dir "$OBS_CKPT" --max-queue 512 --deadline 120 \
    --trace "$OBS_OUT/obs_serve_trace.json" \
    --cache-dir '' --no-jax-cache 2> "$OBS_OUT/obs_serve.log" &
SERVE_PID=$!
python - "$SERVE_PORT" <<'EOF'
import asyncio, sys
from repro.serve import http_json
async def wait_ready(port):
    for _ in range(120):
        try:
            st, _ = await http_json("127.0.0.1", port, "GET", "/readyz")
            if st == 200:
                return
        except OSError:
            pass
        await asyncio.sleep(0.5)
    raise SystemExit("server never became ready")
asyncio.run(wait_ready(int(sys.argv[1])))
EOF
python -m repro.launch.loadgen --port "$SERVE_PORT" \
    --file "$SERVE_OUT/serve_queries.json" --clients 100 --requests 1 \
    --metricsz --prometheus --save-reports \
    --out "$OBS_OUT/obs_load.json"
kill -TERM "$SERVE_PID"
SERVE_RC=0; wait "$SERVE_PID" || SERVE_RC=$?
test "$SERVE_RC" -eq 0
# SIGTERM drain must save the trace + metrics snapshot (the fix this
# PR ships): both files land in the checkpoint dir
test -f "$OBS_CKPT/serve-trace.json"
test -f "$OBS_CKPT/serve-metrics.json"
python - <<'EOF'
import json, re
d = json.load(open("benchmarks/out/obs_load.json"))
assert d["transport_errors"] == 0, d
assert d["n_terminal"] == d["n_requests"] == 100, d
c = d["server_metrics"]["counters"]
assert c.get("serve.shed", 0) + c["serve.completed"] \
    == c["serve.admitted"], c
reports = [e["report"] for e in d["reports"]]
assert len(reports) == d["statuses"].get("200", 0) and reports, \
    d["statuses"]

# --- (b) per-report timing: phases sum to measured wall (<=10%) ----
# (Report.to_json flattens extras to the top level on the wire)
phase_sums: dict[str, float] = {}
for rep in reports:
    tim = rep["timing"]
    assert tim["request_id"].startswith("lg-"), tim
    wall, s = tim["wall_s"], sum(tim["phases"].values())
    assert abs(s - wall) <= max(0.10 * wall, 1e-3), (rep["name"], s, wall)
    for p, v in tim["phases"].items():
        phase_sums[p] = phase_sums.get(p, 0.0) + v
assert "queue_wait" in phase_sums, phase_sums

# --- the Prometheus histograms reconcile with the reports ----------
text = d["server_prometheus"]
assert "# TYPE serve_latency_s histogram" in text, "no latency histogram"
assert 'le="+Inf"' in text
assert re.search(r'# \{request_id="lg-\d{4}-\d{3}"\}', text), \
    "no client request-id exemplars in the exposition"
prom_sums = {m.group(1): float(m.group(2)) for m in re.finditer(
    r'serve_phase_s_sum\{phase="(\w+)"\} ([0-9.eE+-]+)', text)}
for p, want in phase_sums.items():
    got = prom_sums.get(p, 0.0)
    assert abs(got - want) <= max(0.10 * want, 0.05), (p, got, want)
n_count = sum(int(float(m.group(1))) for m in re.finditer(
    r'serve_latency_s_count\{[^}]*\} ([0-9.eE+-]+)', text))
assert n_count == len(reports), (n_count, len(reports))

# --- (a) one request id threads server -> coalescer -> engine ------
t = json.load(open("benchmarks/out/obs_serve_trace.json"))
rid = reports[0]["timing"]["request_id"]
def has_rid(e):
    r = e.get("args", {}).get("rid")
    return r == rid or (isinstance(r, list) and rid in r)
names = {e["name"] for e in t["traceEvents"] if has_rid(e)}
for want in ("request", "queue-wait", "flush"):
    assert want in names, (rid, want, sorted(names))
assert names & {"query", "run_many", "encode", "compile", "dispatch",
                "device-pass", "topk-merge"}, \
    (rid, "no engine spans carry the request id", sorted(names))
print(f"observability smoke OK: {len(reports)} reports reconciled; "
      f"rid {rid} threads {len(names)} span names")
EOF

echo "== crash@serve-worker flight-recorder drill =="
# Chaos drill for the always-on flight recorder: a deterministic crash
# in the flush worker must (1) still answer the in-flight request with
# an error report (no hang), and (2) dump the recorder ring to
# flight-<ts>.json naming the failing request id, with the error entry
# and the request's spans inside.
OBS_FLIGHT="$OBS_OUT/obs_flight"
rm -rf "$OBS_FLIGHT"
mkdir -p "$OBS_FLIGHT"
python -m repro.launch.serve --port "$SERVE_PORT" \
    --faults crash@serve-worker:0 --flight-dir "$OBS_FLIGHT" \
    --deadline 60 --cache-dir '' --no-jax-cache \
    2>> "$OBS_OUT/obs_serve.log" &
SERVE_PID=$!
python - "$SERVE_PORT" "$SERVE_OUT/serve_queries.json" <<'EOF'
import asyncio, json, sys
from repro.serve import http_json
async def main(port, qfile):
    for _ in range(120):
        try:
            st, _ = await http_json("127.0.0.1", port, "GET", "/readyz")
            if st == 200:
                break
        except OSError:
            pass
        await asyncio.sleep(0.5)
    else:
        raise SystemExit("server never became ready")
    q = json.load(open(qfile))[0]
    st, body = await http_json("127.0.0.1", port, "POST", "/query", q,
                               headers={"X-Request-Id": "ci-crash-1"})
    assert st == 200 and body["kind"] == "error", (st, body)
asyncio.run(main(int(sys.argv[1]), sys.argv[2]))
EOF
kill -TERM "$SERVE_PID"
SERVE_RC=0; wait "$SERVE_PID" || SERVE_RC=$?
test "$SERVE_RC" -eq 0
python - <<'EOF'
import glob, json
paths = sorted(glob.glob("benchmarks/out/obs_flight/flight-*.json"))
assert paths, "crash drill produced no flight-recorder dump"
doc = json.load(open(paths[0]))
assert doc["reason"] == "flush-error", doc["reason"]
assert "ci-crash-1" in doc.get("request_ids", ()), doc.get("request_ids")
ents = doc["entries"]
assert any(e["name"] == "serve-flush-error" for e in ents), \
    [e["name"] for e in ents]
def rid_has(e):
    r = e.get("rid")
    return r == "ci-crash-1" or (isinstance(r, list) and "ci-crash-1" in r)
assert any(rid_has(e) for e in ents), \
    "no flight entries attributed to the failing request"
print(f"flight drill OK: {paths[0]} ({len(ents)} entries)")
EOF

echo "== benchmarks --quick =="
python -m benchmarks.run --quick

echo "== bench_mapspace smoke artifact =="
# BENCH_mapspace.json (written by the mapspace benchmark above) tracks the
# perf trajectory per PR: end-to-end + eval-only mappings/s, gene-vs-legacy
# speedup, joint-sweep designs/s, universal-evaluator compile count, device
# count.  It lands BOTH under benchmarks/out (CI artifact upload) and at
# the repo root (perf trajectory tracker).
test -f benchmarks/out/BENCH_mapspace.json
test -f BENCH_mapspace.json
python - <<'EOF'
import json
d = json.load(open("BENCH_mapspace.json"))
print(json.dumps(d, indent=2))
# the gene pipeline must keep the <= 2-compiles-per-(op, level-count,
# batch-shape) model: `compile_budget` is the closed-form bound the bench
# derives from the evaluation contexts it runs — O(1) per layer family,
# never O(structure groups)
assert d["universal_compiles_process"] <= d["compile_budget"], \
    (d["universal_compiles_process"], d["compile_budget"],
     "compile count must stay O(1) per (layer, level-count), not O(groups)")
# the gene pipeline must beat the legacy tuple-point path end to end
assert d["e2e_speedup_vs_legacy"] >= 1.0, d["e2e_speedup_vs_legacy"]
# checkpointing the headline search must cost <= 5% of its wall time,
# and the checkpointed run must reproduce the uncheckpointed answer
assert d["checkpoint_overhead_frac"] <= 0.05, d["checkpoint_overhead_frac"]
assert d["checkpoint"]["deterministic"] is True, d["checkpoint"]
assert d["checkpoint"]["saves"] >= 1, d["checkpoint"]
# tracing the headline search must cost <= 1% of its wall time, and the
# traced run must reproduce the untraced answer bit-identically
assert d["obs_overhead_frac"] <= 0.01, (d["obs_overhead_frac"], d["obs"])
assert d["obs"]["deterministic"] is True, d["obs"]
assert d["obs"]["trace_events"] > 0, d["obs"]
# every BENCH artifact ships the obs metrics snapshot + environment
# provenance (schema_version 2)
assert d["schema_version"] == 2, d["schema_version"]
assert d["environment"]["backend"], d.get("environment")
c = d["metrics"]["counters"]
fam = {k: v for k, v in c.items()
       if k.startswith("universal.compiles_by_family[")}
assert c["universal.compiles"] == sum(fam.values()), (c, fam)
# the jaxpr audit rides with the artifact: every traced family must be
# finding-free AND within its primitive-count budget, so a PR that
# bloats the traced program (or sneaks in an f64 upcast / host
# callback) fails here even if wall-clock noise hides the slowdown
assert d["jaxpr_findings"] == [], d["jaxpr_findings"]
counts, budget = d["jaxpr_primitive_counts"], d["jaxpr_primitive_budget"]
# counts are per traced case ("family/kind"); budgets are per family —
# every budgeted family must be covered, and every case must fit
fams = {case.rsplit("/", 1)[0] for case in counts}
assert counts and fams >= set(budget), (sorted(fams), sorted(budget))
for case, n in counts.items():
    cap = budget.get(case.rsplit("/", 1)[0])
    assert cap is None or n <= cap, (case, n, cap)
print(f"jaxpr audit OK: {len(counts)} traced cases within primitive budget")
EOF

echo "== BENCH_netspace smoke artifact =="
test -f benchmarks/out/BENCH_netspace.json
test -f BENCH_netspace.json
python - <<'EOF'
import json
d = json.load(open("BENCH_netspace.json"))
print(json.dumps(d, indent=2))
# whole-network search must stay on the <= 2-compiles-per-(op-class,
# level-count) model: compile_budget = 2 * n_op_classes
assert d["universal_compiles_process"] <= d["compile_budget"], \
    (d["universal_compiles_process"], d["compile_budget"],
     "netspace compile count must be O(op-classes), not O(layers)")
# the searched schedule's network EDP must beat the best single uniform
# Table-3 dataflow applied network-wide
assert d["edp_win_vs_best_uniform"] >= 1.0, d["edp_win_vs_best_uniform"]
assert d["schema_version"] == 2 and d["environment"]["backend"], d
assert "universal.compiles" in d["metrics"]["counters"], d["metrics"]
EOF

echo "== BENCH_api smoke artifact =="
test -f benchmarks/out/BENCH_api.json
test -f BENCH_api.json
python - <<'EOF'
import json
d = json.load(open("BENCH_api.json"))
print(json.dumps(d, indent=2))
# Session.run_many on the mixed heterogeneous batch must compile at most
# ONE executable per unique (op-class, level-count) family ...
assert d["n_compiles"] <= d["n_families"], \
    (d["n_compiles"], d["n_families"],
     "coalesced batch must stay within the family compile budget")
# ... answer identically whether queries are coalesced or run one at a
# time through the same family spaces ...
assert d["coalesced_deterministic"] is True
# ... and beat sequential per-query search() wall time by >= 2x (the
# compile amortization IS the headline)
assert d["run_many_speedup_vs_sequential_search"] >= 2.0, \
    d["run_many_speedup_vs_sequential_search"]
assert d["schema_version"] == 2 and d["environment"]["backend"], d
assert "universal.compiles" in d["metrics"]["counters"], d["metrics"]
EOF

echo "== BENCH_serve smoke artifact =="
test -f benchmarks/out/BENCH_serve.json
test -f BENCH_serve.json
python - <<'EOF'
import json
d = json.load(open("BENCH_serve.json"))
print(json.dumps(d, indent=2))
# every load-burst request must reach a terminal status, and the
# admission ledger must balance: shed + completed == admitted
for key in (k for k in d if k.startswith("clients_")):
    s = d[key]
    assert s["all_terminal"] is True, (key, s)
    assert s["p50_s"] > 0 and s["p99_s"] >= s["p50_s"], (key, s)
    assert s["queries_per_s"] > 0, (key, s)
assert d["invariant_holds"] is True, d["counters"]
assert d["schema_version"] == 2 and d["environment"]["backend"], d
EOF

echo "== bench regression gate =="
# Fresh quick-mode artifacts vs the committed baselines (read from git,
# since the bench run overwrites the root copies).  Full-mode-only
# baselines (BENCH_serve) are skipped automatically on quick runs.
python scripts/bench_check.py --out-dir benchmarks/out

echo "CI smoke gate passed."
