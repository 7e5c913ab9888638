"""The trace reduction on small traces with known answers."""
from __future__ import annotations

import gzip
import os

import pytest
from jax.profiler import ProfileData

import devtrace
from conftest import HERE

# Host sync annotation at trace time 1,000 ns; two TPUs. Device 0 runs
# fusion.1 over [2,000, 7,000) ns and sort.2 over [12,000, 14,000) ns,
# nested inside a while.3 over [11,000, 15,000) ns; device 1 runs
# fusion.1 over [3,000, 4,000) ns. Device 0's module jit_eval runs over
# [2,000, 8,000) ns.
TRACE = """
planes { id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench-sync" } } }
planes { id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 2000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 2000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 2000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "sort.2" } }
  event_metadata { key: 3 value { id: 3 name: "while.3" } }
  event_metadata { key: 4 value { id: 4 name: "jit_eval" } } }
planes { id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 3000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } } }
"""
# perf_counter reading at the sync annotation: trace ns t maps to
# perf_counter seconds 100 + (t - 1,000) * 1e-9.
SYNC_PC = 100.0


def pc(ns: float) -> float:
    return SYNC_PC + (ns - 1000) * 1e-9


@pytest.fixture(scope="module")
def pd():
    return ProfileData.from_text_proto(TRACE)


def test_one_device_busy_idle_and_ops(pd):
    s = devtrace.reduce(pd, n_devices=1, sync_name="bench-sync",
                        sync_pc=SYNC_PC, lo=pc(1000), hi=pc(17000),
                        host_spans=[(pc(7500), pc(10500), "encode"),
                                    (pc(5000), pc(16000), "query")])
    assert s.window_s == pytest.approx(16e-6)
    # union of [2,7) and [11,15) thousand ns: nested sort.2 counts once
    assert s.busy_s == [pytest.approx(9e-6)]
    assert s.idle_share == pytest.approx(1 - 9 / 16)
    # an op inside a module takes its name
    assert s.op_s == pytest.approx({"jit_eval:fusion.1": 5e-6,
                                    "while.3": 4e-6, "sort.2": 2e-6})
    # gaps [7,11), [1,2) and [15,17): the first in encode, inner to query
    assert [g[0] for g in s.gaps] == ["encode", "query", "outside-spans"]
    assert [g[1] for g in s.gaps] == pytest.approx([4e-6, 2e-6, 1e-6])


def test_window_clips_and_devices_average(pd):
    s = devtrace.reduce(pd, n_devices=2, sync_name="bench-sync",
                        sync_pc=SYNC_PC, lo=pc(3000), hi=pc(13000))
    assert s.busy_s == [pytest.approx(6e-6), pytest.approx(1e-6)]
    assert s.device_s == pytest.approx(7e-6)
    assert s.idle_share == pytest.approx(1 - 3.5 / 10)
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit_eval:fusion.1", pytest.approx(4e-6)]


def test_missing_sync_or_devices_refused(pd):
    with pytest.raises(ValueError, match="sync"):
        devtrace.reduce(pd, n_devices=1, sync_name="other", sync_pc=0.0,
                        lo=0.0, hi=1.0)
    with pytest.raises(ValueError, match="TPU planes"):
        devtrace.reduce(pd, n_devices=4, sync_name="bench-sync",
                        sync_pc=SYNC_PC, lo=pc(1000), hi=pc(2000))


RECORDED = os.path.join(HERE, "data", "tpu_sweep.xplane.pb.gz")


def test_recorded_tpu_trace():
    """A short trace of one co-DSE query on a TPU v5e: every number the
    harness reads comes out, inside its bounds."""
    with gzip.open(RECORDED) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    planes = [p for p in pd.planes if devtrace.DEVICE_PLANE.match(p.name)]
    assert planes
    starts = [e.start_ns for p in planes for ln in p.lines
              if ln.name == devtrace.OPS_LINE for e in ln.events]
    ends = [e.end_ns for p in planes for ln in p.lines
            if ln.name == devtrace.OPS_LINE for e in ln.events]
    sync = next(e.start_ns for p in pd.planes for ln in p.lines
                for e in ln.events if e.name == "bench-sync")
    lo = (min(starts) - sync) * 1e-9
    hi = (max(ends) - sync) * 1e-9
    s = devtrace.reduce(pd, n_devices=1, sync_name="bench-sync",
                        sync_pc=0.0, lo=lo, hi=hi)
    assert 0 < s.busy_s[0] <= s.window_s
    assert 0 <= s.idle_share < 1
    assert sum(s.op_s.values()) >= s.busy_s[0] * (1 - 1e-9)
    assert s.breakdown()["device_ops"]
