"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to device
metrics: busy time as the union of device-op intervals per device, idle
share, op time by name, and the longest idle gaps labelled by the host
span the program was in during each.

Device planes are ``/device:TPU:<n>``. Their ops are the events of the
``XLA Ops`` line, each named ``<module>:<op>`` after the ``XLA Modules``
event around it (``jit_chunk_fn:%fusion.2``). Host and device times are put on one clock by a sync
annotation: the harness opens a ``jax.profiler.TraceAnnotation`` of a
known name at a known ``perf_counter`` reading, and the trace's copy of
that event gives the offset.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Sequence

from spanstats import covered, union

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class DeviceSummary:
    window_s: float
    busy_s: list[float]                  # per device
    op_s: dict[str, float]               # by op name, summed over devices
    gaps: list[tuple[str, float]]        # longest idle gaps, labelled

    @property
    def idle_share(self) -> float:
        return 1.0 - sum(self.busy_s) / len(self.busy_s) / self.window_s

    @property
    def device_s(self) -> float:
        return sum(self.busy_s)

    def breakdown(self) -> dict[str, list]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps]}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _sync_offset(pd, sync_name: str, sync_pc: float) -> float:
    """Trace seconds minus perf_counter seconds."""
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == sync_name:
                    return ev.start_ns * 1e-9 - sync_pc
    raise ValueError(f"sync annotation {sync_name!r} is not in the trace")


def _label(spans: Sequence[tuple[float, float, str]], t: float) -> str:
    """The innermost host span open at ``t``."""
    best = None
    for a, b, name in spans:
        if a <= t <= b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "outside-spans"


def _namer(plane):
    """Names an op event by the module executing around it."""
    mods = sorted((e.start_ns, e.end_ns, e.name.split("(")[0])
                  for line in plane.lines if line.name == MODULES_LINE
                  for e in line.events)
    starts = [m[0] for m in mods]

    def name(ev) -> str:
        op = ev.name.split(" = ")[0]
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        if i >= 0 and ev.start_ns < mods[i][1]:
            return f"{mods[i][2]}:{op}"
        return op
    return name


def reduce(pd, *, n_devices: int, sync_name: str, sync_pc: float,
           lo: float, hi: float,
           host_spans: Sequence[tuple[float, float, str]] = ()
           ) -> DeviceSummary:
    """Reduce ``pd`` (a ``jax.profiler.ProfileData``) over the window
    [lo, hi] of perf_counter seconds, on its first ``n_devices`` TPUs."""
    off = _sync_offset(pd, sync_name, sync_pc)
    planes = sorted((int(m.group(1)), p) for p in pd.planes
                    if (m := DEVICE_PLANE.match(p.name)))
    if len(planes) < n_devices:
        raise ValueError(f"trace has {len(planes)} TPU planes, "
                         f"{n_devices} needed")
    busy: list[float] = []
    op_s: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []          # (length, midpoint)
    for _, plane in planes[:n_devices]:
        name = _namer(plane)
        iv = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = ev.start_ns * 1e-9 - off
                b = a + ev.duration_ns * 1e-9
                c = max(0.0, min(b, hi) - max(a, lo))
                if c > 0:
                    iv.append((a, b))
                    k = name(ev)
                    op_s[k] = op_s.get(k, 0.0) + c
        busy.append(covered(iv, lo, hi))
        edge = lo
        for a, b in union(iv) + [(hi, hi)]:
            a, b = max(a, lo), min(b, hi)
            if a > edge:
                gaps.append((a - edge, (edge + a) / 2))
            edge = max(edge, b)
    gaps.sort(reverse=True)
    return DeviceSummary(
        window_s=hi - lo, busy_s=busy, op_s=op_s,
        gaps=[(_label(host_spans, mid), n) for n, mid in gaps[:TOP]])
