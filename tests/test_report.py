"""Claims-report pipeline tests: record ingestion (schema 1 + 2), the
per-record claim checks (Eq. 4/17/23/24, §6 routing, oracle accuracy),
deterministic rendering, and the compare regression gate -- including
the acceptance bar that the committed runs/ records carry zero Eq. 23/24
ceiling violations."""
import json
import os
import pathlib

import pytest

from repro.core.balance import machine_balance
from repro.core.bounds import tensor_core_upper_bound, workload_upper_bound
from repro.core.hw import TPU_V5E
from repro.report import (CLAIMS, ceiling_bound, check_record,
                          check_records, load_dir, load_file,
                          render_kernel_page, render_report, violations,
                          write_report)
from repro.report.records import BenchRecord

REPO = pathlib.Path(__file__).resolve().parent.parent
RUNS = REPO / "runs"


def _raw(**overrides):
    """A schema-2 record dict for a healthy memory-bound sweep point."""
    rec = {
        "kernel": "scale", "engine": "vector", "size": 1024,
        "dtype": "float32", "ref_us_per_call": 100.0, "iqr_us": 5.0,
        "iters": 5, "max_err": 0.0, "intensity": 0.125,
        "memory_bound": True, "engine_auto": "vector",
        "pred_us_v5e": 1.0, "mxu_ceiling": 1.0,
    }
    rec.update(overrides)
    return rec


def _write_set(path, records, schema=2, kernel="scale"):
    payload = {"schema": schema, "kernel": kernel,
               "env": {"jax": "0", "device": "cpu", "interpret": True,
                       "hw_model": "TPU-v5e"},
               "records": records}
    path.write_text(json.dumps(payload if schema == 2 else records))


# -- ingestion --------------------------------------------------------------

def test_load_committed_runs_schema6():
    sets = load_dir(str(RUNS))
    keys = [(s.kernel, s.kind, s.mesh_devices) for s in sets]
    assert keys == sorted(keys)
    assert {s.kernel for s in sets} >= {"attention", "axpy", "scale",
                                        "spmv", "stencil", "triad"}
    tuned_points = 0
    mesh_points = 0
    for s in sets:
        if s.kind == "serving":
            assert s.schema == 5  # serving sessions live in schema 5
            continue
        assert s.schema == 7
        assert "jax" in s.env and "device" in s.env
        assert s.env["interpret"] is True
        for rec in s.records:
            assert rec.iters and rec.iqr_us is not None
            if rec.tile_config is not None:
                assert rec.tile_config["params"]  # present + non-empty
                tuned_points += 1
            # mesh sweeps carry a shard spec on every record; the
            # single-device baseline carries none
            if s.mesh_devices > 1:
                assert rec.shard_spec is not None
                assert rec.num_shards > 1
                mesh_points += 1
            else:
                assert rec.shard_spec is None and rec.num_shards == 1
    # the committed baseline was swept with tuned tiles: every family
    # with a tile space contributes tuned sweep points — and the mesh
    # baseline (scale 2/4-way, stencil 2-way) is present for the CI
    # mesh-smoke gate to join against
    assert tuned_points > 0
    assert mesh_points > 0


def test_load_schema3_tile_config(tmp_path):
    p = tmp_path / "BENCH_scale.json"
    cfg = {"params": {"block_rows": 128, "lanes": 512},
           "tuned_us": 10.0, "default_us": 15.0, "source": "xla-proxy"}
    payload = {"schema": 3, "kernel": "scale", "env": {},
               "records": [_raw(tile_config=cfg), _raw(engine="matrix")]}
    p.write_text(json.dumps(payload))
    rs = load_file(str(p))
    assert rs.schema == 3
    tuned, untuned = rs.records
    assert tuned.tile_config == cfg  # kept as data
    assert untuned.tile_config is None
    # malformed tile_config is rejected, not silently dropped
    payload["records"] = [_raw(tile_config={"tuned_us": 1.0})]
    p.write_text(json.dumps(payload))
    with pytest.raises(ValueError, match="tile_config"):
        load_file(str(p))


def test_load_schema1_legacy_list(tmp_path):
    p = tmp_path / "BENCH_scale.json"
    _write_set(p, [_raw()], schema=1)
    rs = load_file(str(p))
    assert rs.schema == 1 and rs.env == {} and len(rs.records) == 1
    # legacy records join as unsharded points (trailing shard count 1)
    assert rs.records[0].point == ("scale", "vector", 1024, "float32", 1)


def test_load_rejects_missing_fields_and_bad_schema(tmp_path):
    p = tmp_path / "BENCH_scale.json"
    bad = _raw()
    del bad["mxu_ceiling"]
    _write_set(p, [bad], schema=1)
    with pytest.raises(ValueError, match="missing fields"):
        load_file(str(p))
    p.write_text(json.dumps({"schema": 99, "records": [_raw()]}))
    with pytest.raises(ValueError, match="unsupported schema"):
        load_file(str(p))
    p.write_text(json.dumps({"schema": 2, "env": {}}))
    with pytest.raises(ValueError, match="missing its 'records'"):
        load_file(str(p))
    with pytest.raises(FileNotFoundError):
        load_dir(str(tmp_path / "nowhere"))


# -- claim checks -----------------------------------------------------------

def test_committed_runs_have_zero_violations():
    """The acceptance bar: every committed record passes every claim --
    in particular zero Eq. 23/24 ceiling violations across all six
    kernel families."""
    results = check_records(load_dir(str(RUNS)))
    assert results, "no claim results produced"
    assert violations(results) == []


def test_ceiling_bound_matches_paper_formulas():
    b = machine_balance(TPU_V5E, "vector")
    i = 0.125
    assert ceiling_bound(i, TPU_V5E) == pytest.approx(
        min(tensor_core_upper_bound(TPU_V5E.alpha),
            workload_upper_bound(i, b)))


def _record(**overrides):
    d = _raw()
    d.update(overrides)
    return BenchRecord(**{k: d[k] for k in d})


def test_healthy_record_passes_all_claims():
    results = check_record(_record(), TPU_V5E)
    assert tuple(r.claim for r in results) == CLAIMS
    assert all(r.passed for r in results)


@pytest.mark.parametrize("overrides,failing", [
    # memory-bound record claiming a 1.9x MXU win: Eq. 23/24 busted
    ({"mxu_ceiling": 1.9}, "ceiling"),
    # memory-bound work auto-routed to the matrix engine: §6 busted
    ({"engine_auto": "matrix"}, "routing"),
    # engine variant diverged from the oracle
    ({"max_err": 0.5}, "accuracy"),
    # record disagrees with a fresh Eq. 4 derivation
    ({"memory_bound": False, "engine_auto": "matrix",
      "mxu_ceiling": 2.0}, "boundedness"),
])
def test_claim_violations_detected(overrides, failing):
    results = check_record(_record(**overrides), TPU_V5E)
    failed = {r.claim for r in results if not r.passed}
    assert failing in failed


def test_bf16_tolerance_is_looser():
    rec = _record(dtype="bfloat16", max_err=0.0625, intensity=0.25)
    assert all(r.passed for r in check_record(rec, TPU_V5E))
    rec32 = _record(dtype="float32", max_err=0.0625)
    assert not [r for r in check_record(rec32, TPU_V5E)
                if r.claim == "accuracy"][0].passed


# -- rendering --------------------------------------------------------------

def test_write_report_deterministic(tmp_path):
    """Two regenerations from the same records are byte-identical."""
    out1, out2 = tmp_path / "a", tmp_path / "b"
    for out in (out1, out2):
        paths = write_report(runs_dir=str(RUNS),
                             report_path=str(out / "REPORT.md"),
                             docs_dir=str(out / "docs"))
        assert len(paths) >= 7  # REPORT.md + one page per family
    assert (out1 / "REPORT.md").read_bytes() == \
        (out2 / "REPORT.md").read_bytes()
    for page in sorted(p.name for p in (out1 / "docs").iterdir()):
        assert (out1 / "docs" / page).read_bytes() == \
            (out2 / "docs" / page).read_bytes()


def test_write_report_removes_orphan_pages(tmp_path):
    """Pages of removed kernels are deleted so docs/ matches runs/."""
    runs, docs = tmp_path / "runs", tmp_path / "docs"
    runs.mkdir(), docs.mkdir()
    _write_set(runs / "BENCH_scale.json", [_raw()])
    (docs / "removed-kernel.md").write_text("stale evidence")
    write_report(runs_dir=str(runs),
                 report_path=str(tmp_path / "REPORT.md"),
                 docs_dir=str(docs))
    assert not (docs / "removed-kernel.md").exists()
    assert (docs / "scale.md").exists()


def test_committed_report_is_current():
    """REPORT.md and docs/benchmarks/ match the committed runs/ records
    (i.e. `python -m benchmarks.run report` was run before commit)."""
    from repro.report import page_name, render_serving_page

    recsets = load_dir(str(RUNS))
    assert (REPO / "REPORT.md").read_text() == render_report(recsets)
    for rs in recsets:
        page = REPO / "docs" / "benchmarks" / page_name(rs)
        render = (render_serving_page if rs.kind == "serving"
                  else render_kernel_page)
        assert page.read_text() == render(rs), page


def test_report_flags_violations(tmp_path):
    runs = tmp_path / "runs"
    runs.mkdir()
    _write_set(runs / "BENCH_scale.json",
               [_raw(), _raw(engine="matrix", mxu_ceiling=1.9)])
    recsets = load_dir(str(runs))
    report = render_report(recsets)
    assert "❌" in report and "violation" in report
    page = render_kernel_page(recsets[0])
    assert "## Violations" in page and "ceiling" in page


# -- compare gate -----------------------------------------------------------

def _shard_spec(**overrides):
    """A healthy 2-way data-split shard_spec for _raw()'s sweep point."""
    spec = {"kind": "data", "num_shards": 2, "axis": "data", "halo": 0,
            "total_bytes": 8192.0, "agg_bytes": 8192.0,
            "shard_bytes": 4096.0, "shard_intensity": 0.125,
            "pred_shard_us_v5e": 0.5}
    spec.update(overrides)
    return spec


def _write_schema5(path, records, kernel="scale", mesh=2):
    payload = {"schema": 5, "kernel": kernel,
               "env": {"jax": "0", "device": "cpu", "interpret": True,
                       "hw_model": "TPU-v5e", "mesh_shape": [mesh]},
               "records": records}
    path.write_text(json.dumps(payload))


def test_schema5_shard_spec_round_trip(tmp_path):
    p = tmp_path / "BENCH_scale_mesh2.json"
    _write_schema5(p, [_raw(mesh_shape=[2], shard_spec=_shard_spec())])
    rs = load_file(str(p))
    assert rs.schema == 5 and rs.mesh_devices == 2
    rec = rs.records[0]
    assert rec.mesh_shape == (2,) and rec.num_shards == 2
    assert rec.point[-1] == 2  # shards are part of the join key
    assert not violations(check_records([rs]))


@pytest.mark.parametrize("spec_overrides,expect", [
    # per-shard intensity above the unsharded one: impossible split
    ({"shard_intensity": 0.5}, "shard_ceiling"),
    # more shards than the recorded mesh provides
    ({"num_shards": 8}, "shard_ceiling"),
    ({"kind": "diagonal"}, "shard_ceiling"),
    # aggregate below the unsharded total: invented traffic savings
    ({"agg_bytes": 4096.0}, "shard_traffic"),
    # halo-free data split must move exactly the unsharded bytes
    ({"agg_bytes": 9000.0}, "shard_traffic"),
    # max-shard bytes times N cannot cover the aggregate
    ({"shard_bytes": 1000.0}, "shard_traffic"),
    # a rowblock split escapes the exactness arm but not the cap: no
    # shard may move more bytes than the unsharded kernel, so a
    # hand-edited 100x aggregate-traffic story still fails
    ({"kind": "rowblock", "agg_bytes": 819200.0,
      "shard_bytes": 409600.0}, "shard_traffic"),
])
def test_shard_claim_violations_detected(tmp_path, spec_overrides,
                                         expect):
    p = tmp_path / "BENCH_scale_mesh2.json"
    _write_schema5(p, [_raw(mesh_shape=[2],
                            shard_spec=_shard_spec(**spec_overrides))])
    bad = violations(check_records([load_file(str(p))]))
    assert expect in {v.claim for v in bad}, (
        f"{spec_overrides} should violate {expect}")


def test_report_renders_sharded_section(tmp_path):
    runs = tmp_path / "runs"
    runs.mkdir()
    _write_set(runs / "BENCH_scale.json", [_raw()])
    _write_schema5(runs / "BENCH_scale_mesh2.json",
                   [_raw(mesh_shape=[2], shard_spec=_shard_spec())])
    report = render_report(load_dir(str(runs)))
    assert "## Sharded execution" in report
    assert "zero shard-claim violations" in report
    assert "scale-mesh2.md" in report
    # the single-device claim table does not double-count mesh sets
    assert report.count("| scale | 1 |") == 1


def test_clamped_mesh_sweep_keeps_its_requested_width(tmp_path):
    """A 4-way mesh over a 2-extent split plans 2 shards but must
    still key (and filter) as a mesh-4 point — not collide with a
    genuine 2-way sweep or vanish under ``--mesh 4``."""
    from benchmarks.compare import compare

    base = tmp_path / "base"
    base.mkdir()
    clamped = _raw(mesh_shape=[4],
                   shard_spec=_shard_spec(num_shards=2))
    _write_schema5(base / "BENCH_scale_mesh4.json", [clamped], mesh=4)
    rs = load_file(str(base / "BENCH_scale_mesh4.json"))
    rec = rs.records[0]
    assert rec.num_shards == 2 and rec.mesh_devices == 4
    assert rec.point[-1] == 4
    # self-comparison scoped to the requested width joins, not empties
    assert compare(str(base), str(base), mesh=4) == []


def test_compare_gate_mesh_filter(tmp_path):
    from benchmarks.compare import compare

    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    _write_set(base / "BENCH_scale.json", [_raw()])
    _write_schema5(base / "BENCH_scale_mesh2.json",
                   [_raw(mesh_shape=[2], shard_spec=_shard_spec())])
    # candidate reproduces only the single-device sweep
    _write_set(cand / "BENCH_scale.json", [_raw()])
    # default (--mesh all): the lost 2-way width is missing coverage
    msgs = "\n".join(compare(str(base), str(cand)))
    assert "missing" in msgs
    # scoped to the width the candidate actually ran: clean pass
    assert compare(str(base), str(cand), mesh=1) == []
    # and scoping to a width nobody ran fails loudly, not vacuously
    msgs = "\n".join(compare(str(base), str(cand), mesh=4))
    assert "empty comparison" in msgs


def test_compare_gate(tmp_path):
    from benchmarks.compare import compare

    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    _write_set(base / "BENCH_scale.json",
               [_raw(), _raw(engine="matrix")])
    # identical candidate: clean pass
    _write_set(cand / "BENCH_scale.json",
               [_raw(), _raw(engine="matrix")])
    assert compare(str(base), str(cand)) == []
    # >25% slower + a dropped sweep point + a claim violation: all caught
    _write_set(cand / "BENCH_scale.json",
               [_raw(ref_us_per_call=200.0, mxu_ceiling=1.9)])
    msgs = "\n".join(compare(str(base), str(cand)))
    assert "perf regression" in msgs
    assert "missing" in msgs
    assert "claim violation" in msgs
    # a generous threshold forgives the slowdown but not the violation
    msgs = "\n".join(compare(str(base), str(cand), threshold=2.0))
    assert "perf regression" not in msgs
    assert "claim violation" in msgs
    # a filter matching nothing must fail, not pass vacuously
    msgs = "\n".join(compare(str(base), str(cand), kernels=["triad"]))
    assert "empty comparison" in msgs


# -- schema 6: measured real-mesh execution ---------------------------------

def _mesh_exec(**overrides):
    """Healthy measured evidence for _shard_spec()'s halo-free 2-way
    split: zero wire bytes -> zero collective."""
    mex = {"mode": "mesh", "devices": 2, "mesh_wall_us": 500.0,
           "mesh_iqr_us": 10.0, "collective_us": 0.0,
           "virtual_us": 100.0, "skew": 5.0, "mesh_max_err": 0.0}
    mex.update(overrides)
    return mex


def _write_schema6(path, records, kernel="scale", mesh=2):
    payload = {"schema": 6, "kernel": kernel,
               "env": {"jax": "0", "device": "cpu", "interpret": True,
                       "hw_model": "TPU-v5e", "mesh_shape": [mesh],
                       "mesh_exec_mode": "mesh"},
               "records": records}
    path.write_text(json.dumps(payload))


def test_schema6_mesh_exec_round_trip(tmp_path):
    p = tmp_path / "BENCH_scale_mesh2.json"
    _write_schema6(p, [_raw(mesh_shape=[2], shard_spec=_shard_spec(),
                            mesh_exec=_mesh_exec())])
    rs = load_file(str(p))
    assert rs.schema == 6 and rs.mesh_devices == 2
    rec = rs.records[0]
    assert rec.mesh_exec["mesh_wall_us"] == 500.0
    assert not violations(check_records([rs]))


def test_schema6_rejects_malformed_mesh_exec(tmp_path):
    p = tmp_path / "BENCH_scale_mesh2.json"
    _write_schema6(p, [_raw(mesh_shape=[2], shard_spec=_shard_spec(),
                            mesh_exec={"mode": "mesh"})])
    with pytest.raises(ValueError, match="mesh_exec"):
        load_file(str(p))


@pytest.mark.parametrize("spec_overrides,mex_overrides,expect", [
    # a plan that wires nothing cannot measure a nonzero collective
    ({}, {"collective_us": 50.0}, "collective_cost"),
    # halo bytes on a 2-way mesh must cost *something*
    ({"kind": "rowblock", "halo": 3, "wire_bytes": 3072.0},
     {"collective_us": 0.0}, "collective_cost"),
    # implied wire bandwidth beyond any interconnect (1 GB in 1 us)
    ({"kind": "rowblock", "halo": 3, "wire_bytes": 1e9},
     {"collective_us": 1.0}, "collective_cost"),
    # devices disagreeing with the plan's width
    ({}, {"devices": 4}, "collective_cost"),
    # recorded skew inconsistent with wall/virtual
    ({}, {"skew": 2.0}, "mesh_skew"),
    # skew outside the anti-flake band (wall 500000x virtual)
    ({}, {"mesh_wall_us": 5e7, "skew": 5e5}, "mesh_skew"),
    # the real execution produced the wrong answer
    ({}, {"mesh_max_err": 1.0}, "mesh_skew"),
])
def test_mesh_claim_violations_detected(tmp_path, spec_overrides,
                                        mex_overrides, expect):
    p = tmp_path / "BENCH_scale_mesh2.json"
    _write_schema6(p, [_raw(
        mesh_shape=[2], shard_spec=_shard_spec(**spec_overrides),
        mesh_exec=_mesh_exec(**mex_overrides))])
    bad = violations(check_records([load_file(str(p))]))
    assert expect in {v.claim for v in bad}, (
        f"{spec_overrides}/{mex_overrides} should violate {expect}")


def test_compare_gates_measured_mesh_wall(tmp_path):
    from benchmarks.compare import compare

    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    point = _raw(mesh_shape=[2], shard_spec=_shard_spec(),
                 mesh_exec=_mesh_exec())
    _write_schema6(base / "BENCH_scale_mesh2.json", [point])
    # identical candidate: clean
    _write_schema6(cand / "BENCH_scale_mesh2.json", [point])
    assert compare(str(base), str(cand)) == []
    # 3x slower measured wall (ref time unchanged): caught
    slow = _raw(mesh_shape=[2], shard_spec=_shard_spec(),
                mesh_exec=_mesh_exec(mesh_wall_us=1500.0, skew=15.0))
    _write_schema6(cand / "BENCH_scale_mesh2.json", [slow])
    msgs = "\n".join(compare(str(base), str(cand)))
    assert "mesh_wall_us" in msgs
    # a virtual-only candidate re-sweep is not blamed for timings it
    # never took (claims/coverage own schema drift, not the perf gate)
    _write_schema5(cand / "BENCH_scale_mesh2.json",
                   [_raw(mesh_shape=[2], shard_spec=_shard_spec())])
    assert all("mesh_wall_us" not in m
               for m in compare(str(base), str(cand)))


def test_serving_mesh_exec_mode_is_a_config_knob(tmp_path):
    """A measured-mesh serving session must refuse to gate against a
    virtual-clock baseline: the two p99s are not comparable."""
    from benchmarks.compare import compare

    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()

    def serving_payload(mode):
        rec = {"kernel": "scale", "engine": "vector",
               "engine_auto": "vector", "workload": "poisson",
               "rate_rps": 32.0, "duration_s": 1.0, "size": 8192,
               "dtype": "float32", "seed": 0, "offered": 30,
               "completed": 30, "p50_ms": 1.0, "p95_ms": 2.0,
               "p99_ms": 3.0, "queue_p50_ms": 0.5,
               "compute_p50_ms": 0.5, "goodput_rps": 30.0,
               "slo_ms": 50.0, "slo_attainment": 1.0,
               "intensity": 0.125, "memory_bound": True,
               "mxu_ceiling": 1.0, "num_shards": 2,
               "mesh_exec_mode": mode}
        return {"schema": 4, "kind": "serving", "kernel": "scale",
                "env": {}, "records": [rec]}

    (base / "BENCH_serve_scale.json").write_text(
        json.dumps(serving_payload("virtual")))
    (cand / "BENCH_serve_scale.json").write_text(
        json.dumps(serving_payload("mesh")))
    msgs = "\n".join(compare(str(base), str(cand), kind="serving"))
    assert "config mismatch" in msgs and "mesh_exec_mode" in msgs
