"""Arithmetic over the program's host spans: interval unions and shares
of the window.

Spans are ``repro.obs`` trace events: dicts with ``name``, ``ts`` and
``dur`` in microseconds on the tracer's clock. Intervals here are
(start, end) pairs in seconds on ``time.perf_counter``'s clock.
"""
from __future__ import annotations

from typing import Iterable, Sequence


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float,
                                                                   float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(intervals: Iterable[tuple[float, float]], lo: float,
            hi: float) -> float:
    """Seconds of [lo, hi] that the intervals cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo))
               for a, b in union(intervals))


def span_intervals(spans: Sequence[dict], t0_pc: float,
                   names: Iterable[str]) -> list[tuple[float, float]]:
    """The named spans as perf_counter intervals; ``t0_pc`` is the
    tracer's zero."""
    names = set(names)
    return [(t0_pc + e["ts"] * 1e-6, t0_pc + (e["ts"] + e["dur"]) * 1e-6)
            for e in spans if e["name"] in names]


def window_share(spans: Sequence[dict], t0_pc: float, name: str,
                 lo: float, hi: float) -> float | None:
    """Share of the window [lo, hi] spent inside ``name`` spans; None
    when the window holds no such span."""
    iv = span_intervals(spans, t0_pc, [name])
    if not any(b > lo and a < hi for a, b in iv):
        return None
    return covered(iv, lo, hi) / (hi - lo)
