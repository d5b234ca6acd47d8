"""Observability-layer tests (``repro.obs``): span-tree invariants,
span-is-the-sample reconciliation against ``time_fn``, dispatch launch
spans carrying re-derivable roofline counters, virtual-clock trace
determinism (same seed => byte-identical Chrome-trace export),
Chrome-trace schema validation + byte round-trips, metrics-registry
percentiles against numpy, the structured logger's level/capture
contract, and the program's spans in a JAX profiler trace (CPU
profiler)."""
import gc
import io
import json
import pathlib
import re
import statistics

import numpy as np
import pytest

from repro.core.dispatch import DEFAULT_DISPATCHER
from repro.core.timing import time_fn
from repro.kernels import registry
from repro.obs.counters import roofline_sample
from repro.obs.log import LEVELS, StructuredLogger
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import (PROGRAM_SPANS, TRACER, capture, chrome_trace,
                             dump_chrome_trace, read_chrome_trace,
                             validate_chrome_trace, write_chrome_trace)
from repro.serving import (BatchPolicy, ContinuousBatchingScheduler,
                           PoissonLoadGen)
from repro.serving.scheduler import BatchExecution

REPO = pathlib.Path(__file__).resolve().parent.parent
RUNS = REPO / "runs"
#: the names the benchmark gives its own profiler spans
BENCH_SPAN_PREFIXES = ("window", "call:", "batch")


class FakeExecutor:
    """Deterministic executor: fixed per-batch compute, no kernels."""

    def __init__(self, compute_s=0.003):
        self.compute_s = compute_s

    def execute(self, batch):
        return BatchExecution(engine="vector", compute_s=self.compute_s)


# -- span trees -------------------------------------------------------------

def test_span_tree_nesting_and_finalization():
    with capture() as view:
        with TRACER.span("outer", layer="test", tag="a"):
            with TRACER.span("inner", layer="test"):
                pass
        with TRACER.span("sibling", layer="test"):
            pass
    events = view.events
    by_name = {e.name: e for e in events}
    outer, inner, sibling = (by_name[k] for k in
                             ("outer", "inner", "sibling"))
    assert outer.parent == -1 and outer.depth == 0
    # parent indices are absolute into the process tracer's list
    assert TRACER.events[inner.parent].name == "outer"
    assert inner.depth == 1
    assert sibling.parent == -1 and sibling.depth == 0
    # durations finalized on exit, children contained in the parent
    assert outer.dur_us > 0 and inner.dur_us >= 0
    assert inner.start_us >= outer.start_us
    assert (inner.start_us + inner.dur_us
            <= outer.start_us + outer.dur_us + 1e-6)
    assert outer.attrs["tag"] == "a"


def test_disabled_tracer_emits_nothing():
    before = len(TRACER.events)
    with TRACER.span("ghost", layer="test"):
        pass
    TRACER.emit("ghost", layer="test", start_s=0.0, dur_s=1.0)
    TRACER.virtual("ghost", layer="test", start_s=0.0, dur_s=1.0)
    TRACER.instant("ghost", layer="test", at_s=0.0)
    assert len(TRACER.events) == before


def test_capture_is_reentrant_with_distinct_slices():
    with capture() as outer:
        with TRACER.span("a", layer="test"):
            pass
        with capture() as inner:
            with TRACER.span("b", layer="test"):
                pass
        with TRACER.span("c", layer="test"):
            pass
    assert [e.name for e in inner.events] == ["b"]
    assert [e.name for e in outer.events] == ["a", "b", "c"]
    assert not TRACER.enabled  # outermost exit disables


# -- span-is-the-sample reconciliation --------------------------------------

def test_time_fn_spans_reconcile_with_timing():
    with capture() as view:
        t = time_fn(lambda: np.arange(256.0).sum(), warmup=1, iters=5,
                    label="ref_call", layer="bench", kernel="unit")
    spans = [e for e in view.events if e.name == "ref_call"]
    assert len(spans) == t.iters == 5
    # each span carries its sample verbatim, in iteration order
    assert [e.attrs["iter"] for e in spans] == list(range(5))
    for e, sample_us in zip(spans, t.samples_us):
        assert e.clock == "wall" and e.layer == "bench"
        assert e.dur_us == pytest.approx(sample_us, abs=1e-6)
    # odd iters: median span == Timing.median_us bit-for-bit modulo
    # the s->us conversion — the trace_reconciliation claim's basis
    med = statistics.median(e.dur_us for e in spans)
    assert med == pytest.approx(t.median_us, abs=1e-6)


def test_dispatch_launch_span_carries_roofline_counters():
    op = registry.get("scale")
    rng = np.random.default_rng(0)
    size = min(op.bench_sizes)
    args, kw = op.make_inputs(rng, size, op.dtypes[0])
    with capture() as view:
        op(*args, engine="vector", **kw)
    launches = [e for e in view.events if e.name == "launch"]
    assert len(launches) == 1
    launch = launches[0]
    assert sum(1 for e in view.events if e.name == "dispatch") == 1
    # the launch nests under its dispatch span (absolute parent index)
    assert TRACER.events[launch.parent].name == "dispatch"
    a = launch.attrs
    assert a["engine"] == "vector"
    # counters re-derive from the span's own traffic and duration
    traits = op.traits(*args, **kw)
    assert a["traffic_bytes"] == pytest.approx(traits.traffic_bytes)
    want = roofline_sample(traits, DEFAULT_DISPATCHER.hw, "vector",
                           a["dtype"], a["measured_us"]).as_attrs()
    for key in ("achieved_gbs", "pct_of_bound", "pct_of_ceiling"):
        assert a[key] == pytest.approx(want[key], abs=1e-3), key


# -- virtual clock determinism ----------------------------------------------

def _virtual_session_trace():
    gen = PoissonLoadGen(kernel="scale", rate_rps=200, size=1024, seed=11)
    sched = ContinuousBatchingScheduler(
        FakeExecutor(), BatchPolicy(max_batch=4, max_wait_s=0.01))
    with capture() as view:
        sched.run(gen, 1.0)
    return [e for e in view.events if e.clock == "virtual"]


def test_virtual_trace_is_byte_deterministic():
    first = _virtual_session_trace()
    second = _virtual_session_trace()
    assert first  # the session actually emitted spans
    dump_a = dump_chrome_trace(chrome_trace(first, meta={"seed": 11}))
    dump_b = dump_chrome_trace(chrome_trace(second, meta={"seed": 11}))
    assert dump_a == dump_b
    # no wall-clock leakage: every serving event sits on the virtual pid
    payload = json.loads(dump_a)
    clocked = [e for e in payload["traceEvents"] if e["ph"] in ("X", "i")]
    assert clocked and all(e["pid"] == 2 for e in clocked)


# -- Chrome-trace export ----------------------------------------------------

def test_chrome_trace_schema_and_byte_roundtrip(tmp_path):
    with capture() as view:
        with TRACER.span("work", layer="test", size=8):
            pass
        TRACER.virtual("vspan", layer="serving", start_s=0.5, dur_s=0.25)
        TRACER.instant("mark", layer="elastic", at_s=0.75)
    payload = chrome_trace(view.events, meta={"source": "test"})
    assert validate_chrome_trace(payload) == []
    path = tmp_path / "trace.json"
    write_chrome_trace(str(path), view.events, meta={"source": "test"})
    raw = path.read_bytes()
    back = read_chrome_trace(str(path))
    assert dump_chrome_trace(back).encode() == raw
    # both clocks present, metadata events name them
    pids = {e["pid"] for e in back["traceEvents"] if e["ph"] != "M"}
    assert pids == {1, 2}
    names = {e["args"]["name"] for e in back["traceEvents"]
             if e["ph"] == "M"}
    assert names == {"wall clock", "virtual clock"}


def test_validate_chrome_trace_flags_problems():
    assert validate_chrome_trace([]) == ["payload is not an object"]
    assert validate_chrome_trace({}) == ["traceEvents missing or not a list"]
    bad = {"traceEvents": [
        {"ph": "X", "name": "x", "pid": 1, "tid": 0, "ts": 0.0},  # no dur
        {"ph": "Z", "name": "y", "pid": 1, "tid": 0, "ts": 0.0},  # bad ph
    ]}
    problems = validate_chrome_trace(bad)
    assert any("missing numeric dur" in p for p in problems)
    assert any("unsupported ph" in p for p in problems)


def test_committed_chaos_artifact_roundtrips():
    artifacts = sorted(RUNS.glob("TRACE_*.json"))
    assert artifacts, "no committed runs/TRACE_*.json chaos artifact"
    for path in artifacts:
        payload = read_chrome_trace(str(path))
        assert dump_chrome_trace(payload).encode() == path.read_bytes()
        clocks = {e["args"]["clock"] for e in payload["traceEvents"]
                  if e["ph"] in ("X", "i")}
        assert "virtual" in clocks  # a replayable serving timeline


# -- metrics ----------------------------------------------------------------

def test_histogram_percentiles_match_numpy():
    h = Histogram("lat")
    rng = np.random.default_rng(3)
    for v in rng.exponential(5.0, size=257):
        h.observe(float(v))
    for q in (50.0, 95.0, 99.0):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(h._samples, q)))
    s = h.summary()
    assert s["count"] == 257 and s["p99"] >= s["p50"]


def test_registry_instruments_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("launches").inc()
    reg.counter("launches").inc(2)
    reg.gauge("mesh_width").set(4)
    reg.histogram("us").observe(1.0)
    with pytest.raises(ValueError):
        reg.counter("launches").inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("launches")  # name already a Counter
    snap = reg.snapshot()
    assert snap["launches"] == 3.0 and snap["mesh_width"] == 4.0
    assert snap["us"]["count"] == 1
    assert list(snap) == sorted(snap)


# -- structured logging -----------------------------------------------------

def test_logger_levels_and_stream():
    out = io.StringIO()
    log = StructuredLogger(stream=out)
    log.info("quiet", k=1)          # below default 'warning': dropped
    log.warning("loud", reason="x")
    lines = out.getvalue().splitlines()
    assert lines == ["[repro:warning] loud reason=x"]
    log.configure(level="debug")
    log.debug("now visible")
    assert out.getvalue().splitlines()[-1] == "[repro:debug] now visible"
    with pytest.raises(ValueError):
        log.configure(level="chatty")
    with pytest.raises(ValueError):
        StructuredLogger(level="nope")
    assert set(LEVELS) == {"debug", "info", "warning", "error"}


def test_logger_capture_collects_below_level():
    out = io.StringIO()
    log = StructuredLogger(stream=out)  # level 'warning'
    with log.capture() as records:
        log.debug("hidden", a=1)
        with log.capture() as inner:
            log.info("both")
        log.error("visible")
    assert [r.level for r in records] == ["debug", "info", "error"]
    assert [r.level for r in inner] == ["info"]
    assert records[0].fields == {"a": 1}
    # stream only saw the error (captures never mute the stream)
    assert out.getvalue().splitlines() == ["[repro:error] visible"]


# -- spans on the profiler clock --------------------------------------------

def _profiled(tmp_path, fn):
    """(name, stats) of every host event of a CPU profile of ``fn()``."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    return [(e.name, dict(e.stats or {}))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_span_of_disabled_tracer_lands_on_the_profiler_trace(tmp_path):
    assert not TRACER.enabled
    before = len(TRACER.events)

    def work():
        with TRACER.span("probe", layer="test", size=3):
            pass
    events = _profiled(tmp_path, work)
    assert ("test.probe", {"size": 3}) in events
    assert len(TRACER.events) == before  # the in-memory record stays off


def test_forced_gc_is_a_host_gc_span(tmp_path):
    TRACER.watch_gc()
    events = _profiled(tmp_path, gc.collect)
    assert ("host.gc", {"generation": 2}) in events


def test_watch_gc_installs_one_hook():
    TRACER.watch_gc()
    n = len(gc.callbacks)
    TRACER.watch_gc()
    assert len(gc.callbacks) == n
    assert sum(1 for cb in gc.callbacks if cb == TRACER._on_gc) == 1


def test_program_span_names_are_not_benchmark_span_names():
    assert not [n for n in PROGRAM_SPANS if n.startswith(BENCH_SPAN_PREFIXES)]
    # every span the program's sources open is in the list
    opened = set()
    for path in (REPO / "src" / "repro").rglob("*.py"):
        opened |= {f"{layer}.{name}" for name, layer in re.findall(
            r'TRACER\.span\(\s*"([^"]+)",\s*layer="([^"]+)"',
            path.read_text())}
    assert opened and opened <= set(PROGRAM_SPANS)
    assert set(PROGRAM_SPANS) - opened == {"host.gc"}


def test_engine_generate_trace_names_spans_and_programs(tmp_path):
    from repro.models import DecodeEngine, ModelConfig

    cfg = ModelConfig(name="tiny-dense", family="dense", n_layers=2,
                      d_model=16, n_heads=2, n_kv_heads=1, d_ff=32,
                      vocab=50, rope_theta=1e4, pad_vocab_to=8)
    eng = DecodeEngine(cfg, max_batch=2, prompt_len=4, max_gen=3,
                       attention_impl="dense")
    batch = eng.make_prompt_batch()
    eng.warmup(batch)
    events = _profiled(tmp_path, lambda: eng.generate(batch))
    names = [name for name, _ in events]
    assert names.count("engine.generate") == 1
    assert names.count("engine.prefill") == 1
    assert names.count("engine.step") == 2
    assert names.count("engine.wait") == 2
    modules = {stats.get("hlo_module") for _, stats in events}
    assert {"jit_prefill", "jit_decode_step"} <= modules
