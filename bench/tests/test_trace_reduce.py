"""The trace reduction on a small trace recorded on a TPU v5e.

``data/sample.xplane.pb`` was recorded by ``record_sample_trace.py``:
``call:scale`` (the Pallas scale kernel on 2^20 float32 elements with
the wrapper's two relayout programs), a 20 ms host sleep, and
``call:matmul`` (one jitted 1024x1024 matmul, no Pallas).  Its numbers,
read by hand from ``sample_dump.txt`` of the same recording, are what
these tests expect.
"""
from pathlib import Path

import pytest

from bench.trace_reduce import Span, breakdown, reduce_trace

SAMPLE = Path(__file__).parent / "data" / "sample.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return reduce_trace(str(SAMPLE), ("call:",))


@pytest.fixture(scope="module")
def window(red):
    return Span("window", red.spans[0].start, red.spans[-1].end)


def test_spans_and_devices(red):
    assert [s.name for s in red.spans] == ["call:scale", "call:matmul"]
    assert red.devices == 1


def test_device_clock_offset_from_run_ids(red):
    # device starts lead the host's enqueue by 1.36-1.42 ms in this
    # trace; the least of them is the offset
    assert red.clock_offset_ns == pytest.approx(-1_420_248.0)


def test_ops_belong_to_the_span_that_enqueued_them(red):
    by_span = {}
    for o in red.ops:
        by_span.setdefault(o.span, []).append((o.module, o.op, o.pallas))
    assert by_span["call:scale"] == [
        ("jit_reshape", "reshape.1", False),
        ("jit__elementwise_grid", "_elementwise_grid.1", True),
        ("jit_reshape", "copy", False)]
    assert [p for _, _, p in by_span["call:matmul"]] == [False] * 3
    # on the host clock every op lies inside its span
    for o in red.ops:
        span = next(s for s in red.spans if s.name == o.span)
        assert span.start <= o.start and o.end <= span.end


def test_busy_pallas_and_idle(red, window):
    # union of the 6 op intervals (copy-start overlaps copy-done)
    assert red.busy_ns(window) == pytest.approx(57_918.0)
    assert red.busy_ns(window, pallas=True) == pytest.approx(13_164.0)
    gaps = red.idle_gaps(window)
    assert sum(ns for _, ns in gaps) == pytest.approx(
        window.dur - 57_918.0)
    label, longest = max(gaps, key=lambda g: g[1])
    assert label == "between spans / no host event"  # the 20 ms sleep
    assert 20e6 < longest < 22e6


def test_breakdown_lists_seconds(red, window):
    b = breakdown(red, window)
    ops = dict(b["device_ops"])
    assert ops["call:scale: _elementwise_grid.1"] == pytest.approx(13.164e-6)
    assert len(b["device_ops"]) == 6 and len(b["idle_gaps"]) <= 10


def test_clipping_to_a_window(red):
    scale = red.spans[0]
    assert red.busy_ns(scale) == pytest.approx(13_182 + 13_164 + 12_456)
    assert red.ops_in(Span("none", 0, 1)) == []
