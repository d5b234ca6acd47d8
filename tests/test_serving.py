"""Serving-subsystem tests: loadgen replay determinism, scheduler
invariants (batch-size/age bounds, no starvation, FIFO fairness,
closed-loop concurrency), metrics percentiles vs numpy, schema-4
round-trips through ``repro.report.records``, the serving claim checks,
the ``benchmarks/compare.py`` p99/goodput gate, and one small
end-to-end session against a real registered kernel."""
import dataclasses
import json
import pathlib

import numpy as np
import pytest

from repro.report import (SERVING_CLAIMS, check_records,
                          check_serving_record, load_file, page_name,
                          render_report, render_serving_page, violations)
from repro.report.records import ServingRecord
from repro.serving import (BatchPolicy, BurstyLoadGen, ClosedLoopLoadGen,
                           ContinuousBatchingScheduler, PoissonLoadGen,
                           SLO, SessionConfig, load_trace, make_loadgen,
                           percentile, run_session, save_trace, summarize)
from repro.serving.scheduler import BatchExecution

REPO = pathlib.Path(__file__).resolve().parent.parent
RUNS = REPO / "runs"


class FakeExecutor:
    """Deterministic executor: fixed per-batch compute, no kernels."""

    def __init__(self, compute_s=0.003, engine="vector"):
        self.compute_s = compute_s
        self.engine = engine
        self.batches = []

    def execute(self, batch):
        self.batches.append(list(batch))
        return BatchExecution(engine=self.engine,
                              compute_s=self.compute_s)

    def advice_for(self, kernel, size, dtype):
        raise NotImplementedError  # scheduler tests never need Advice


def _run(gen, *, max_batch=4, max_wait_s=0.01, duration=1.0,
         compute_s=0.003):
    ex = FakeExecutor(compute_s=compute_s)
    sched = ContinuousBatchingScheduler(
        ex, BatchPolicy(max_batch=max_batch, max_wait_s=max_wait_s))
    return sched.run(gen, duration), ex


# -- loadgen ----------------------------------------------------------------

def test_poisson_replay_is_deterministic():
    a = PoissonLoadGen(kernel="scale", rate_rps=100, size=1024, seed=7)
    b = PoissonLoadGen(kernel="scale", rate_rps=100, size=1024, seed=7)
    assert a.initial(2.0) == b.initial(2.0)
    c = PoissonLoadGen(kernel="scale", rate_rps=100, size=1024, seed=8)
    assert a.initial(2.0) != c.initial(2.0)


def test_bursty_modulates_rate():
    gen = BurstyLoadGen(kernel="scale", rate_hi=400, rate_lo=4,
                        period_s=1.0, duty=0.5, seed=3)
    reqs = gen.initial(10.0)
    assert reqs == gen.initial(10.0)  # replayable
    on = sum(1 for r in reqs if (r.arrival_s % 1.0) < 0.5)
    off = len(reqs) - on
    assert on > 10 * off  # ~100x the rate, well beyond noise


def test_closed_loop_restarts_deterministically():
    gen = ClosedLoopLoadGen(kernel="scale", clients=4, think_s=0.01,
                            seed=5)
    first = gen.initial(1.0)
    assert len(first) == 4
    assert {r.client for r in first} == {0, 1, 2, 3}
    assert first == gen.initial(1.0)  # initial() reseeds


def test_trace_round_trip(tmp_path):
    gen = PoissonLoadGen(kernel="scale", rate_rps=50, size=2048, seed=1)
    reqs = gen.initial(1.0)
    path = tmp_path / "trace.json"
    save_trace(str(path), reqs)
    replay = load_trace(str(path)).initial(1.0)
    assert [(r.kernel, r.size, r.dtype, r.client) for r in replay] == \
        [(r.kernel, r.size, r.dtype, r.client) for r in reqs]
    assert [round(r.arrival_s, 9) for r in replay] == \
        [round(r.arrival_s, 9) for r in reqs]
    # malformed traces are rejected, not silently empty
    path.write_text(json.dumps({"schema": 99}))
    with pytest.raises(ValueError, match="schema"):
        load_trace(str(path))


def test_make_loadgen_dispatches_and_validates():
    for name in ("poisson", "bursty", "closed"):
        assert make_loadgen(name, "scale").name == name
    with pytest.raises(ValueError, match="trace"):
        make_loadgen("trace", "scale")
    with pytest.raises(ValueError, match="unknown workload"):
        make_loadgen("nope", "scale")


def test_trace_sessions_only_serve_their_kernel(tmp_path):
    """A mixed-kernel trace must not leak other kernels' requests into
    one kernel's session (their latencies would be misattributed)."""
    mixed = (PoissonLoadGen(kernel="scale", rate_rps=30, seed=1)
             .initial(1.0)
             + PoissonLoadGen(kernel="triad", rate_rps=30, seed=2)
             .initial(1.0))
    path = tmp_path / "mixed.json"
    save_trace(str(path), mixed)
    gen = make_loadgen("trace", "scale", trace_path=str(path))
    reqs = gen.initial(1.0)
    assert reqs and all(r.kernel == "scale" for r in reqs)
    # a trace with nothing for the requested kernel is an error, not
    # a silently idle session
    with pytest.raises(ValueError, match="no requests for kernel"):
        make_loadgen("trace", "axpy", trace_path=str(path))


def test_closed_loop_first_arrivals_respect_horizon():
    gen = ClosedLoopLoadGen(kernel="scale", clients=16, think_s=0.1,
                            seed=0)
    horizon = 0.05
    assert all(r.arrival_s < horizon for r in gen.initial(horizon))


# -- scheduler invariants ---------------------------------------------------

def test_no_starvation_every_arrival_is_served():
    gen = PoissonLoadGen(kernel="scale", rate_rps=300, size=64, seed=2)
    log, _ = _run(gen, duration=1.0)
    assert log.offered == len(gen.initial(1.0))
    assert log.completed == log.offered
    served = {r.request.rid for r in log.results}
    assert served == {r.rid for r in gen.initial(1.0)}


def test_batch_size_bound_respected():
    gen = PoissonLoadGen(kernel="scale", rate_rps=500, size=64, seed=4)
    log, ex = _run(gen, max_batch=3, duration=1.0)
    assert ex.batches and all(len(b) <= 3 for b in ex.batches)
    assert all(r.batch_size <= 3 for r in log.results)


def test_age_trigger_bounds_queueing():
    # service far faster than arrivals: a lone request must not wait
    # past max_wait_s for companions that never come
    gen = PoissonLoadGen(kernel="scale", rate_rps=5, size=64, seed=6)
    log, _ = _run(gen, max_batch=64, max_wait_s=0.02, duration=2.0,
                  compute_s=0.0001)
    assert log.completed > 0
    # one batch may be in flight when the trigger fires
    bound = 0.02 + 0.0001 + 1e-9
    assert all(r.queue_s <= bound for r in log.results), \
        max(r.queue_s for r in log.results)


def test_fifo_within_batch_key():
    gen = PoissonLoadGen(kernel="scale", rate_rps=400, size=64, seed=9)
    log, _ = _run(gen, duration=1.0)
    by_arrival = sorted(log.results, key=lambda r: r.request.arrival_s)
    starts = [r.start_s for r in by_arrival]
    assert starts == sorted(starts)  # earlier arrival never starts later


def test_same_timestamp_ties_dequeue_in_arrival_order():
    """Two queue heads admitted at the same virtual timestamp must
    dequeue in arrival (rid) order, not dict-insertion order — the
    fairness tie-break replay determinism leans on."""
    from repro.serving.requests import Request

    class _ListGen:
        name = "list"

        def __init__(self, reqs):
            self._reqs = reqs

        def initial(self, duration_s):
            return [r for r in self._reqs if r.arrival_s < duration_s]

        def on_complete(self, result, duration_s):
            return None  # open-loop: no follow-up traffic

    # triad's queue is created first (dict-insertion order), but at
    # the 0.01s tie the scale head has the lower rid: arrival order
    # must win the dequeue
    reqs = [
        Request(rid=0, kernel="triad", arrival_s=0.0, size=64),
        Request(rid=1, kernel="scale", arrival_s=0.01, size=64),
        Request(rid=2, kernel="triad", arrival_s=0.01, size=64),
    ]
    ex = FakeExecutor(compute_s=0.003)
    sched = ContinuousBatchingScheduler(
        ex, BatchPolicy(max_batch=1, max_wait_s=0.05))
    log = sched.run(_ListGen(reqs), 1.0)
    assert log.completed == 3
    starts = {r.request.rid: r.start_s for r in log.results}
    assert starts[0] < starts[1] < starts[2]


def test_closed_loop_concurrency_bounded_by_clients():
    gen = ClosedLoopLoadGen(kernel="scale", clients=3, think_s=0.001,
                            seed=1)
    log, _ = _run(gen, max_batch=8, duration=1.0)
    assert log.completed == log.offered
    # with 3 clients, no batch can ever hold more than 3 requests
    assert all(r.batch_size <= 3 for r in log.results)
    per_client = {}
    for r in log.results:
        per_client.setdefault(r.request.client, []).append(r)
    for results in per_client.values():
        # a client's next request never arrives before its previous done
        ordered = sorted(results, key=lambda r: r.request.arrival_s)
        for prev, nxt in zip(ordered, ordered[1:]):
            assert nxt.request.arrival_s >= prev.finish_s


def test_policy_validation():
    with pytest.raises(ValueError, match="max_batch"):
        BatchPolicy(max_batch=0)
    with pytest.raises(ValueError, match="max_wait_s"):
        BatchPolicy(max_wait_s=-1.0)
    with pytest.raises(ValueError, match="latency_ms"):
        SLO(latency_ms=0.0)


# -- metrics ----------------------------------------------------------------

def test_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    xs = rng.exponential(10.0, size=257).tolist()
    for q in (0.0, 25.0, 50.0, 95.0, 99.0, 100.0):
        assert percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12)
    assert percentile([], 99.0) == 0.0
    with pytest.raises(ValueError):
        percentile(xs, 101.0)


def test_summarize_splits_queue_and_compute():
    gen = PoissonLoadGen(kernel="scale", rate_rps=200, size=64, seed=3)
    log, _ = _run(gen, duration=1.0, compute_s=0.004)
    s = summarize(log, SLO(latency_ms=30.0))
    assert s.completed == log.completed
    assert s.p50_ms <= s.p95_ms <= s.p99_ms
    assert s.compute_p50_ms == pytest.approx(4.0, abs=1e-6)
    assert 0.0 <= s.slo_attainment <= 1.0
    assert s.goodput_rps == pytest.approx(
        s.slo_attainment * s.completed / s.duration_s, abs=1e-6)


# -- schema-4 records + claims ----------------------------------------------

def _serving_raw(**overrides):
    """A healthy schema-4 serving record for a memory-bound session."""
    rec = {
        "kernel": "scale", "engine": "vector", "engine_auto": "vector",
        "workload": "poisson", "rate_rps": 64.0, "duration_s": 2.0,
        "size": 65536, "dtype": "float32", "seed": 0,
        "offered": 100, "completed": 100, "batches": 30,
        "mean_batch": 3.3, "p50_ms": 10.0, "p95_ms": 20.0,
        "p99_ms": 25.0, "queue_p50_ms": 5.0, "queue_p99_ms": 12.0,
        "compute_p50_ms": 5.0, "compute_p99_ms": 13.0,
        "throughput_rps": 50.0, "goodput_rps": 50.0, "slo_ms": 50.0,
        "slo_attainment": 1.0, "intensity": 0.125,
        "memory_bound": True, "mxu_ceiling": 1.0,
    }
    rec.update(overrides)
    return rec


def _write_serving(path, records):
    payload = {"schema": 4, "kind": "serving", "kernel": "scale",
               "env": {"jax": "0", "device": "cpu", "interpret": True,
                       "hw_model": "TPU-v5e"},
               "records": records}
    path.write_text(json.dumps(payload))


def test_schema4_round_trip(tmp_path):
    p = tmp_path / "BENCH_serve_scale.json"
    _write_serving(p, [_serving_raw(),
                       _serving_raw(engine="matrix", p99_ms=40.0,
                                    goodput_rps=30.0,
                                    slo_attainment=0.6)])
    rs = load_file(str(p))
    assert rs.kind == "serving" and rs.schema == 4
    assert rs.kernel == "scale" and len(rs.records) == 2
    rec = rs.records[0]
    assert isinstance(rec, ServingRecord)
    # legacy records (no num_shards) key as unsharded sessions
    assert rec.point == ("scale", "vector", "poisson", 65536,
                         "float32", 1)
    assert rec.p99_ms == 25.0 and rec.memory_bound is True
    # the round-tripped record passes every serving claim
    results = check_serving_record(rec)
    assert tuple(r.claim for r in results) == SERVING_CLAIMS
    assert all(r.passed for r in results)


def test_schema4_rejects_malformed(tmp_path):
    p = tmp_path / "BENCH_serve_scale.json"
    bad = _serving_raw()
    del bad["p99_ms"]
    _write_serving(p, [bad])
    with pytest.raises(ValueError, match="serving record missing"):
        load_file(str(p))
    p.write_text(json.dumps({"schema": 4, "kind": "mystery",
                             "records": [_serving_raw()]}))
    with pytest.raises(ValueError, match="unknown kind"):
        load_file(str(p))


@pytest.mark.parametrize("overrides,failing", [
    # memory-bound session claiming a 9x MXU win: Eq. 23/24 busted
    ({"mxu_ceiling": 9.0}, "ceiling"),
    # memory-bound stream auto-routed to the matrix engine: §6 busted
    ({"engine_auto": "matrix"}, "routing"),
    # record disagrees with a fresh Eq. 4 derivation
    ({"memory_bound": False}, "boundedness"),
    # impossible tail: p99 below p50
    ({"p99_ms": 5.0}, "percentiles"),
    # goodput above what attainment x throughput allows
    ({"goodput_rps": 200.0}, "goodput"),
    # attainment out of range
    ({"slo_attainment": 1.5, "goodput_rps": 75.0}, "goodput"),
])
def test_serving_claim_violations_detected(overrides, failing):
    rec = load_file_record(overrides)
    results = check_serving_record(rec)
    assert failing in {r.claim for r in results if not r.passed}


def load_file_record(overrides):
    """Build a ServingRecord via the real ingestion path."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        p = pathlib.Path(d) / "BENCH_serve_scale.json"
        _write_serving(p, [_serving_raw(**overrides)])
        return load_file(str(p)).records[0]


def test_report_renders_serving_section(tmp_path):
    runs = tmp_path / "runs"
    runs.mkdir()
    _write_serving(runs / "BENCH_serve_scale.json",
                   [_serving_raw(),
                    _serving_raw(engine="matrix", p99_ms=40.0,
                                 goodput_rps=30.0, slo_attainment=0.6)])
    from repro.report import load_dir
    recsets = load_dir(str(runs))
    report = render_report(recsets)
    assert "## Serving under load" in report
    assert "VPU vs MXU under load" in report
    assert "1.6x" in report  # 40/25 mxu/vpu p99 ratio
    assert "zero serving-claim violations" in report
    page = render_serving_page(recsets[0])
    assert "serving evidence" in page and "poisson" in page
    assert page_name(recsets[0]) == "scale-serving.md"


def test_committed_serving_runs_verify():
    """The committed runs/ contain schema-4 serving sets and they pass
    every serving claim (§6 routing holds under load)."""
    from repro.report import load_dir
    sets = load_dir(str(RUNS))
    serving = [s for s in sets if s.kind == "serving"]
    assert serving, "no committed serving record sets under runs/"
    assert violations(check_records(serving)) == []
    for s in serving:
        engines = {r.engine for r in s.records}
        assert {"vector", "matrix"} <= engines  # both sides measured


# -- compare gate -----------------------------------------------------------

def test_serving_compare_gate(tmp_path):
    from benchmarks.compare import compare

    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    _write_serving(base / "BENCH_serve_scale.json",
                   [_serving_raw(), _serving_raw(engine="matrix")])
    _write_serving(cand / "BENCH_serve_scale.json",
                   [_serving_raw(), _serving_raw(engine="matrix")])
    assert compare(str(base), str(cand), kind="serving") == []
    # p99 blow-up + goodput collapse + a dropped session: all caught
    _write_serving(cand / "BENCH_serve_scale.json",
                   [_serving_raw(p99_ms=100.0, goodput_rps=10.0,
                                 slo_attainment=0.2)])
    msgs = "\n".join(compare(str(base), str(cand), kind="serving"))
    assert "perf regression" in msgs and "p99_ms" in msgs
    assert "goodput drop" in msgs and "goodput_rps" in msgs
    assert "missing: serving" in msgs
    # a generous threshold forgives the perf drift but not lost coverage
    msgs = "\n".join(compare(str(base), str(cand), threshold=100.0,
                             kind="serving"))
    assert "regression" not in msgs and "missing" in msgs
    # kind filters are honored: no bench records on either side
    msgs = "\n".join(compare(str(base), str(cand), kind="bench"))
    assert "empty comparison" in msgs
    with pytest.raises(ValueError, match="unknown kind"):
        compare(str(base), str(cand), kind="nope")
    # sessions under different load knobs refuse to compare at all —
    # even a threshold that would forgive any metric delta
    _write_serving(cand / "BENCH_serve_scale.json",
                   [_serving_raw(rate_rps=32.0),
                    _serving_raw(engine="matrix")])
    msgs = "\n".join(compare(str(base), str(cand), threshold=100.0,
                             kind="serving"))
    assert "config mismatch" in msgs and "rate_rps=64.0 vs 32.0" in msgs


def _events_raw(**overrides):
    """A healthy events block for a chaos session record: one applied
    shard failure, bit-exact checksums, full availability."""
    ev = {
        "spec": "fail@0.1:1", "availability": 1.0,
        "availability_target": 0.99, "p99_bound": 10.0,
        "p99_slack_ms": 250.0, "checksum": 123.5,
        "failures": 1, "resizes": 0, "recovery_ms_total": 2.0,
        "fault_free": {"completed": 100, "offered": 100,
                       "p99_ms": 25.0, "checksum": 123.5},
        "log": [{"kind": "fail", "at_s": 0.1, "shard": 1, "width": 2,
                 "batch_id": 3, "recovery_ms": 2.0,
                 "redispatch_exact": True}],
    }
    ev.update(overrides)
    return ev


def test_chaos_compare_gate(tmp_path):
    """Chaos sessions gate availability, and sessions under different
    injected adversaries refuse to compare at all."""
    from benchmarks.compare import compare

    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    _write_serving(base / "BENCH_serve_scale.json",
                   [_serving_raw(events=_events_raw())])
    _write_serving(cand / "BENCH_serve_scale.json",
                   [_serving_raw(events=_events_raw())])
    assert compare(str(base), str(cand), kind="serving") == []
    # recovery path starts dropping requests: availability gated
    _write_serving(cand / "BENCH_serve_scale.json", [_serving_raw(
        completed=50, throughput_rps=25.0, goodput_rps=25.0,
        events=_events_raw(
            availability=0.5,
            fault_free={"completed": 50, "offered": 100,
                        "p99_ms": 25.0, "checksum": 123.5}))])
    msgs = "\n".join(compare(str(base), str(cand), kind="serving"))
    assert "availability" in msgs
    # a different chaos spec is a different experiment, not a regression
    _write_serving(cand / "BENCH_serve_scale.json",
                   [_serving_raw(events=_events_raw(spec="fail@0.3:0"))])
    msgs = "\n".join(compare(str(base), str(cand), threshold=100.0,
                             kind="serving"))
    assert "config mismatch" in msgs and "chaos_spec" in msgs


def test_chaos_replay_is_deterministic(tmp_path):
    """Two elastic sessions under the identical seeded adversary replay
    the same events, the same checksums, and the same record — and the
    ingested record passes every serving claim plus elastic_integrity."""
    from repro.report.claims import ELASTIC_CLAIMS, TRACE_CLAIMS
    from repro.serving import ChaosInjector, ElasticSession

    def _session():
        cfg = SessionConfig(
            kernel="scale", workload="bursty", rate_rps=128,
            duration_s=0.5, size=4096, seed=0, num_shards=2,
            policy=BatchPolicy(max_batch=4, max_wait_s=0.01))
        return ElasticSession(
            cfg, injector=ChaosInjector("fail@0.05:1,resize@0.1:4"),
            max_shards=4)

    _, _, rec1 = _session().run()
    _, _, rec2 = _session().run()

    def _shape(rec):
        # the replayable invariants: event structure, checksums, and
        # request accounting.  Latencies (recovery_ms, reactive at_s,
        # percentiles) are *measured* walls and legitimately vary.
        return {
            "log": [tuple(e.get(k) for k in
                          ("kind", "shard", "width", "from", "to",
                           "reason", "skipped", "redispatch_exact",
                           "reshard_exact"))
                    for e in rec["events"]["log"]],
            "checksum": rec["events"]["checksum"],
            "availability": rec["events"]["availability"],
            "offered": rec["offered"], "completed": rec["completed"],
        }

    assert _shape(rec1) == _shape(rec2)
    assert rec1["events"]["checksum"] == rec1["events"]["fault_free"]["checksum"]
    applied = [e for e in rec1["events"]["log"] if not e.get("skipped")]
    assert any(e["kind"] == "fail" for e in applied)
    # through the real ingestion path: serving claims + the elastic one
    from benchmarks.common import write_serving_json
    path = write_serving_json("scale", [rec1], str(tmp_path), mesh=2)
    rec = load_file(path).records[0]
    results = check_serving_record(rec)
    assert (tuple(r.claim for r in results)
            == SERVING_CLAIMS + ELASTIC_CLAIMS + TRACE_CLAIMS)
    assert all(r.passed for r in results)


def test_batcher_survives_oversized_policy_batches():
    """A scheduler policy with a larger max_batch than the executor's
    must cost an extra compile, never a negative-pad crash."""
    from repro.serving import KernelBatchExecutor
    from repro.serving.requests import Request

    ex = KernelBatchExecutor(engine="vpu", max_batch=2)
    batch = [Request(rid=i, kernel="scale", arrival_s=0.0, size=4096)
             for i in range(5)]  # 5 > the executor's capacity of 2
    result = ex.execute(batch)
    assert result.engine == "vector" and result.compute_s > 0


# -- SLO routing ------------------------------------------------------------

def test_slo_routed_session_is_deterministic():
    """``slo_route``: the router sets the shard width at every dequeue
    from queue depth and SLO headroom.  With batch costs a pure function
    of the width, the same seed gives the same decisions, an overload
    grows the width within [1, max_width], and the dispatcher's mesh is
    put back after the session."""
    from repro.core.dispatch import DEFAULT_DISPATCHER
    from repro.serving import RoutedKernelBatchExecutor, SLORouter

    class _Deterministic(RoutedKernelBatchExecutor):
        def _run_packed(self, op, batch, engine):
            return 1e-2 / self.num_shards  # pure function of the width

    def _session():
        ex = _Deterministic(engine="auto", max_batch=4, seed=0,
                            router=SLORouter(slo_ms=50.0, max_width=4))
        cfg = SessionConfig(
            kernel="scale", workload="poisson", rate_rps=2000,
            duration_s=0.5, size=4096, seed=0, slo_route=True,
            policy=BatchPolicy(max_batch=4, max_wait_s=0.01))
        try:
            _, summary, rec = run_session(cfg, executor=ex)
        finally:
            ex.dispatcher.set_mesh(1)
        return ex.router.decisions, summary, rec

    (d1, s1, rec1), (d2, _, rec2) = _session(), _session()
    assert d1 == d2 and rec1["p99_ms"] == rec2["p99_ms"]
    assert "grow" in {d.reason for d in d1}
    assert all(1 <= d.width <= 4 for d in d1)
    assert all(d.engine == "vector" for d in d1)  # §6: memory-bound
    assert s1.completed > 0 and rec1["engine"] == "vector"
    assert DEFAULT_DISPATCHER.mesh_shards == 1


def test_slo_route_refuses_a_preset_mesh():
    """The router owns the width: a session that also fixes a mesh, or
    asks for the measured mesh, is refused before it serves."""
    for kw in ({"num_shards": 2}, {"num_shards": 2, "real_mesh": True}):
        cfg = SessionConfig(kernel="scale", duration_s=0.1, size=4096,
                            slo_route=True, **kw)
        with pytest.raises(ValueError, match="slo_route owns the mesh"):
            run_session(cfg)


# -- end-to-end (real kernel, small) ----------------------------------------

def test_session_end_to_end_scale():
    cfg = SessionConfig(kernel="scale", workload="poisson", rate_rps=40,
                        duration_s=0.3, size=4096, seed=0,
                        policy=BatchPolicy(max_batch=4, max_wait_s=0.01))
    log, summary, record = run_session(cfg)
    assert log.completed == log.offered > 0
    assert record["engine"] == "vector"          # §6: memory-bound
    assert record["engine_auto"] == "vector"
    assert record["memory_bound"] is True
    assert record["p50_ms"] <= record["p99_ms"]
    # the record is exactly what the ingestion layer expects
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        from benchmarks.common import write_serving_json
        path = write_serving_json("scale", [record], d)
        rs = load_file(path)
        assert rs.kind == "serving"
        assert violations(check_records([rs])) == []
