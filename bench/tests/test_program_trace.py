"""``bench/program_trace.py`` and the four metrics that read the
program's spans: the interval arithmetic on a synthetic trace, the
guard that reads only the trace of the run it is given, and the metrics
on a small trace recorded on a TPU v5e.

``data/program.xplane.pb`` was recorded by ``record_program_trace.py``
(its docstring says what the window holds): the numbers these tests
expect were read from that recording with this module.

Run by path: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""
import shutil
from collections import Counter
from pathlib import Path

import pytest

from bench import program_trace as pt
from bench.common import BENCH, load_module
from bench.trace_reduce import DeviceOp, Reduced, Span, reduce_trace

WINDOW = Span("window", 0.0, 100.0)


def _op(start, end, module="jit_decode_step", span="batch", device=0):
    return DeviceOp(device=device, op="fusion.1", hlo="fusion.1 = f32[] x",
                    module=module, span=span, start=start, end=end)


def _red(ops, spans):
    return Reduced(ops=ops, spans=[WINDOW, *spans], host_events=[],
                   devices=1, clock_offset_ns=0.0)


#: device 0 busy in [10, 20], [40, 50], [80, 90] (device 1 always busy,
#: which no metric reads); idle [0, 10], [20, 40], [50, 80], [90, 100]
OPS = [_op(10, 20, "jit_prefill"), _op(40, 50, "jit_argmax"),
       _op(80, 90), _op(0, 100, device=1)]
ENGINE = [Span("engine.generate", 5, 95), Span("engine.prefill", 5, 12),
          Span("engine.step", 20, 40), Span("engine.wait", 50, 60),
          Span("host.gc", 60, 70), Span("host.gc", 96, 98)]
DISPATCH = [Span("dispatch.route", 0, 5), Span("dispatch.launch", 5, 12),
            Span("dispatch.route", 41, 43)]


def _read(metric, red, monkeypatch):
    monkeypatch.setattr(pt, "program_trace", lambda ctx: red)
    ctx = {"reduced": red, "window": WINDOW}
    return load_module(BENCH / "metrics" / f"{metric}.py").read(ctx)


def test_interval_arithmetic():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (45, 60)]
    assert pt.intersect(a, b) == [(5, 10), (20, 25), (45, 50)]
    assert pt.subtract(a, b) == [(0, 5), (25, 30), (40, 45)]
    assert pt.subtract(a, []) == a and pt.subtract([], b) == []
    assert pt.subtract(a, [(-5, 100)]) == []
    assert pt.union([Span("x", 3, 8), Span("y", 0, 4),
                     Span("z", 9, 10)]) == [(0, 8), (9, 10)]
    assert pt.length(a) == 30


def test_idle_of_device_zero():
    red = _red(OPS, [])
    assert pt.idle(red, WINDOW) == [(0, 10), (20, 40), (50, 80), (90, 100)]


def test_engine_and_gc_idle(monkeypatch):
    red = _red(OPS, ENGINE)
    # idle in generate: [5,10] [20,40] [50,80] [90,95] = 60; less the
    # wait [50,60] and the collection [60,70]
    assert _read("engine_idle.lm", red, monkeypatch) == pytest.approx(40.0)
    # idle in a collection: [60,70] and [96,98], outside generate too
    assert _read("gc_idle.lm", red, monkeypatch) == pytest.approx(12.0)
    device_idle = load_module(BENCH / "metrics" / "device_idle.lm.py").read(
        {"reduced": _red(OPS[:3], []), "window": WINDOW})
    assert 40.0 + 12.0 <= device_idle == pytest.approx(70.0)


def test_dispatch_idle(monkeypatch):
    red = _red(OPS, DISPATCH)
    # idle in [0, 12] is [0, 10]; [41, 43] is busy
    assert _read("dispatch_idle.kernels", red,
                 monkeypatch) == pytest.approx(10.0)


def test_engine_glue_share(monkeypatch):
    red = _red(OPS, ENGINE)
    # of 30 busy units on device 0, jit_argmax's 10 are neither program
    assert _read("engine_glue_share.lm", red,
                 monkeypatch) == pytest.approx(100.0 / 3)
    outside = _red([_op(0, 50, "jit_convert", span="")] + OPS, ENGINE)
    assert _read("engine_glue_share.lm", outside,
                 monkeypatch) == pytest.approx(100.0 / 3)


def test_a_program_without_the_spans_reads_nothing(monkeypatch):
    bare = _red([_op(10, 20, "jit__lambda"), _op(40, 50, "jit_argmax")], [])
    for metric in ("dispatch_idle.kernels", "engine_idle.lm", "gc_idle.lm",
                   "engine_glue_share.lm"):
        assert _read(metric, bare, monkeypatch) is None, metric
    # a window that holds no collection reads 0, not nothing
    no_gc = _red(OPS, [Span("engine.generate", 5, 95)])
    assert _read("gc_idle.lm", no_gc, monkeypatch) == 0.0
    for metric in ("dispatch_idle.kernels", "engine_idle.lm", "gc_idle.lm"):
        assert _read(metric, None, monkeypatch) is None, metric


RECORDED = Path(__file__).parent / "data" / "program.xplane.pb"
HARNESS_SPANS = ("window", "call:", "batch")


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """The recorded trace where ``program_trace`` looks for the run's
    trace, and the context the harness gives a metric for it."""
    profile = tmp_path / "cell" / "plugins" / "profile" / "t"
    profile.mkdir(parents=True)
    shutil.copy(RECORDED, profile)
    monkeypatch.setattr(pt, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(pt, "_CACHE", {})
    red = reduce_trace(str(RECORDED), HARNESS_SPANS)
    return {"reduced": red, "window": red.window("window"), "work": {},
            "peak": {}}


def test_reads_only_the_trace_of_its_run(recorded, tmp_path, monkeypatch):
    reads = []
    host_spans = pt._host_spans
    monkeypatch.setattr(pt, "_host_spans",
                        lambda path: reads.append(path) or host_spans(path))
    window = recorded["window"]
    red = pt.program_trace(recorded)
    assert red.window("window") == window
    assert red.ops == recorded["reduced"].ops
    assert pt.program_trace(recorded).spans == red.spans
    assert len(reads) == 1  # the file is read once
    for shifted in (Span("window", window.start + 1, window.end),
                    Span("window", window.start, window.end - 1)):
        assert pt.program_trace({"window": shifted}) is None
    monkeypatch.setattr(pt, "TRACE_DIR", tmp_path / "none")
    assert pt.program_trace(recorded) is None  # no trace at all


def test_recorded_trace_names_spans_programs_and_kernels(recorded):
    red = pt.program_trace(recorded)
    assert Counter(s.name for s in red.spans) == {
        "window": 1, "dispatch.route": 1, "dispatch.launch": 1,
        "engine.generate": 1, "engine.prefill": 1, "engine.step": 3,
        "engine.wait": 2, "host.gc": 1}
    modules = {o.module for o in red.ops}
    assert {"jit_prefill", "jit_decode_step"} <= modules
    # the Pallas kernel is named after its body, not its wrapper
    assert {o.op for o in red.ops if o.pallas} == {"_scale_vpu_kernel.1"}


def test_recorded_trace_metrics(recorded):
    def read(metric):
        return load_module(BENCH / "metrics" / f"{metric}.py").read(recorded)
    window_ms = recorded["window"].dur / 1e6
    device_idle = read("device_idle.lm")
    engine_idle, gc_idle = read("engine_idle.lm"), read("gc_idle.lm")
    dispatch_idle = read("dispatch_idle.kernels")
    glue = read("engine_glue_share.lm")
    assert engine_idle + gc_idle <= device_idle
    assert dispatch_idle <= device_idle
    assert 0 < glue < 100
    # the 20 ms sleep in the first step is idle inside engine.step
    assert engine_idle >= 100 * 20 / window_ms
    # the forced collection (40.7 ms) ran with the device idle
    red = pt.program_trace(recorded)
    gc_ms = sum(s.dur for s in red.spans if s.name == "host.gc") / 1e6
    assert gc_idle == pytest.approx(100 * gc_ms / window_ms, rel=0.01)
    assert (engine_idle, gc_idle, dispatch_idle, glue) == pytest.approx(
        (47.27497324, 48.53539617, 2.23649238, 16.61185023))
