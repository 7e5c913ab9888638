"""The per-layer readers of the program's spans and timing, on a
synthetic run with known answers; each reads nothing, and raises
nothing, where the program has no such span."""
from __future__ import annotations

import types

import pytest

import bench

CELL = "fig13-conv13.sweep"
T0 = 50.0                 # the tracer's zero, perf_counter seconds


def span(name, start_s, dur_s, **args):
    """A span event from perf_counter seconds."""
    ev = {"name": name, "ph": "X", "ts": (start_s - T0) * 1e6,
          "dur": dur_s * 1e6}
    if args:
        ev["args"] = args
    return ev


def record(start, end, designs, other):
    rep = types.SimpleNamespace(extras={"timing": {
        "wall_s": end - start,
        "phases": {"encode": 0.5, "other": other}}})
    return bench.QueryRecord({}, start, end, rep, None, designs)


def make_run(spans):
    # window [100, 110] s: two answers of 1,000 and 3,000 designs
    return bench.Run(bench.load_cell(CELL), setup_s=1.0, t0=100.0,
                     t1=110.0, queries=[record(100.0, 104.0, 1000, 0.2),
                                        record(104.0, 110.0, 3000, 0.3)],
                     spans=spans, tracer_t0=T0)


SPANS = [
    span("h2d", 99.0, 0.5, bytes=999),        # before the window
    span("h2d", 100.5, 1.0, bytes=76_000),
    span("h2d", 101.0, 1.0, bytes=88_000),    # overlaps the one before
    span("d2h", 103.0, 0.25, bytes=40),
    span("h2d", 109.5, 1.0, bytes=16_000),    # runs past the window
    span("encode", 105.0, 2.0),
]


def test_transfer_shares_are_window_shares():
    run = make_run(SPANS)
    # h2d covers [100.5, 102] and [109.5, 110] of the window
    assert bench.reader("h2d_share.sweep")(run) == pytest.approx(0.2)
    assert bench.reader("d2h_share.sweep")(run) == pytest.approx(0.025)


def test_bytes_per_design_counts_spans_that_start_in_the_window():
    run = make_run(SPANS)
    assert bench.reader("h2d_bytes_per_design.sweep")(run) == \
        pytest.approx((76_000 + 88_000 + 16_000) / 4000)


def test_other_share_sums_the_answers_remainders():
    run = make_run(SPANS)
    assert bench.reader("other_share.sweep")(run) == pytest.approx(0.05)


@pytest.mark.parametrize("name", ["h2d_share.sweep", "d2h_share.sweep",
                                  "h2d_bytes_per_design.sweep"])
def test_no_transfer_spans_reads_nothing(name):
    # a program from before the transfer spans: encode held the copy
    assert bench.reader(name)(make_run([span("encode", 101.0, 1.0)])) \
        is None


def test_readers_are_listed_for_the_cell():
    names = {m["name"] for m in bench.load_cell(CELL).per_layer}
    assert {"h2d_share.sweep", "d2h_share.sweep",
            "h2d_bytes_per_design.sweep", "other_share.sweep"} <= names
