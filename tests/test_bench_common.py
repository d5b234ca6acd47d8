"""Benchmark-utility tests: CSV escaping in emit (derived fields with
commas must survive a csv round trip), time_fn's median/IQR statistics,
the schema-3 write_json wrapper, and the compare gate's per-kernel
summary table (a red CI log must be actionable, not just the first
violation)."""
import csv
import io
import json

import numpy as np

from benchmarks.common import (SCHEMA_VERSION, Timing, bench_env, emit,
                               time_fn, write_json)


def test_emit_plain_rows_unquoted():
    buf = io.StringIO()
    emit([{"name": "scale/vector", "us_per_call": "1.5",
           "derived": "I=0.125"}], out=buf)
    assert buf.getvalue() == "scale/vector,1.5,I=0.125\n"


def test_emit_escapes_commas_and_quotes():
    rows = [
        {"name": "k/v", "us_per_call": "2.0",
         "derived": "pred=1,2 and note=\"q\""},
        {"name": "with,comma", "us_per_call": "", "derived": "a\nb"},
    ]
    buf = io.StringIO()
    emit(rows, out=buf)
    parsed = list(csv.reader(io.StringIO(buf.getvalue())))
    assert parsed == [
        ["k/v", "2.0", "pred=1,2 and note=\"q\""],
        ["with,comma", "", "a\nb"],
    ]


def test_emit_defaults_missing_fields_to_empty():
    buf = io.StringIO()
    emit([{"name": "only-name"}], out=buf)
    assert buf.getvalue() == "only-name,,\n"


def test_time_fn_returns_median_iqr_iters():
    t = time_fn(lambda: np.arange(16), iters=7, warmup=1)
    assert isinstance(t, Timing)
    assert t.median_us > 0
    assert t.iqr_us >= 0
    assert t.iters == 7


def test_write_json_schema7(tmp_path):
    recs = [{"kernel": "demo", "engine": "vector", "size": 8,
             "dtype": "float32", "ref_us_per_call": 1.0,
             "tile_config": None, "mesh_shape": None,
             "shard_spec": None}]
    env = bench_env(hw_model="TPU-v5e")
    path = write_json("demo", recs, out_dir=str(tmp_path), env=env)
    payload = json.loads(open(path).read())
    assert payload["schema"] == SCHEMA_VERSION == 7
    assert payload["kernel"] == "demo"
    assert payload["records"] == recs
    for key in ("jax", "numpy", "device", "device_kind", "interpret",
                "hw_model"):
        assert key in payload["env"]
    assert payload["env"]["hw_model"] == "TPU-v5e"


def test_write_json_mesh_files_do_not_clobber_baseline(tmp_path):
    recs = [{"kernel": "demo", "engine": "vector", "size": 8,
             "dtype": "float32", "ref_us_per_call": 1.0}]
    base = write_json("demo", recs, out_dir=str(tmp_path))
    mesh = write_json("demo", recs, out_dir=str(tmp_path), mesh=2)
    assert base.endswith("BENCH_demo.json")
    assert mesh.endswith("BENCH_demo_mesh2.json")
    assert base != mesh


def test_write_serving_json_mesh_files_do_not_clobber_baseline(tmp_path):
    from benchmarks.common import write_serving_json

    recs = [{"kernel": "demo", "engine": "vector"}]
    base = write_serving_json("demo", recs, out_dir=str(tmp_path))
    mesh = write_serving_json("demo", recs, out_dir=str(tmp_path),
                              mesh=2)
    assert base.endswith("BENCH_serve_demo.json")
    assert mesh.endswith("BENCH_serve_demo_mesh2.json")
    assert base != mesh


# -- compare gate summary table ---------------------------------------------

def _raw_record(**overrides):
    rec = {
        "kernel": "scale", "engine": "vector", "size": 1024,
        "dtype": "float32", "ref_us_per_call": 100.0, "max_err": 0.0,
        "intensity": 0.125, "memory_bound": True,
        "engine_auto": "vector", "mxu_ceiling": 1.0,
    }
    rec.update(overrides)
    return rec


def _write_set(path, records, kernel="scale"):
    payload = {"schema": 2, "kernel": kernel,
               "env": {"hw_model": "TPU-v5e"}, "records": records}
    path.write_text(json.dumps(payload))


def test_compare_summary_table_counts_per_kernel(tmp_path):
    from benchmarks.compare import gate

    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    _write_set(base / "BENCH_scale.json",
               [_raw_record(), _raw_record(engine="matrix")])
    _write_set(base / "BENCH_triad.json",
               [_raw_record(kernel="triad")], kernel="triad")
    # scale: one point 3x slower + one dropped; triad: a claim violation
    _write_set(cand / "BENCH_scale.json",
               [_raw_record(ref_us_per_call=300.0)])
    _write_set(cand / "BENCH_triad.json",
               [_raw_record(kernel="triad", mxu_ceiling=1.9)],
               kernel="triad")
    result = gate(str(base), str(cand))
    assert len(result.failures) == 3  # every failure, not just the first
    kinds = sorted((f.kind, f.kernel) for f in result.failures)
    assert kinds == [("claim", "triad"), ("missing", "scale"),
                     ("perf", "scale")]

    table = result.summary_table()
    assert table[0].split() == ["kernel", "compared", "missing", "perf",
                                "goodput", "config", "claims", "status"]
    rows = {line.split()[0]: line.split() for line in table[1:]}
    assert rows["scale"] == ["scale", "1", "1", "1", "0", "0", "0", "FAIL"]
    assert rows["triad"] == ["triad", "1", "0", "0", "0", "0", "1", "FAIL"]


def test_compare_summary_table_marks_clean_kernels(tmp_path):
    from benchmarks.compare import gate

    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    for d in (base, cand):
        _write_set(d / "BENCH_scale.json", [_raw_record()])
        _write_set(d / "BENCH_triad.json",
                   [_raw_record(kernel="triad", ref_us_per_call=1.0
                                if d is base else 10.0)],
                   kernel="triad")
    result = gate(str(base), str(cand))
    rows = {line.split()[0]: line.split()
            for line in result.summary_table()[1:]}
    assert rows["scale"][-1] == "pass"   # blast radius is visible:
    assert rows["triad"][-1] == "FAIL"   # clean kernels listed too


def test_compare_main_exits_nonzero_with_table(tmp_path, capsys):
    from benchmarks.compare import main

    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    _write_set(base / "BENCH_scale.json",
               [_raw_record(), _raw_record(engine="matrix")])
    _write_set(cand / "BENCH_scale.json", [_raw_record()])
    assert main([str(base), str(cand)]) == 1
    err = capsys.readouterr().err
    assert "per-kernel summary" in err
    assert "FAIL" in err and "status" in err


def test_compare_main_passes_identical(tmp_path, capsys):
    from benchmarks.compare import main

    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    for d in (base, cand):
        _write_set(d / "BENCH_scale.json", [_raw_record()])
    assert main([str(base), str(cand)]) == 0
    assert "gate passed" in capsys.readouterr().out


def test_bench_env_records_the_interpret_decision():
    """A record says how its kernels really ran: the backend decides,
    never a hard-coded flag."""
    import jax

    from repro.core.dispatch import use_interpreter
    env = bench_env(hw_model="TPU-v5e")
    assert env["interpret"] is use_interpreter()
    assert env["interpret"] is (jax.default_backend() != "tpu")
    assert env["device_kind"] == jax.devices()[0].device_kind


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache
    goes to the fixed <checkout>/.jax_cache, never a temporary path."""
    import jax

    from repro.launch import compile_cache as cc
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_enable_compilation_cache)
    try:
        monkeypatch.setenv(cc.CACHE_ENV, "/somewhere/else")
        jax.config.update("jax_compilation_cache_dir", None)
        assert cc.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(cc.CACHE_ENV)
        path = cc.enable_compile_cache()
        assert path == str(cc.CHECKOUT / ".jax_cache")
        assert (cc.CHECKOUT / "chip_smoke.py").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_enable_compilation_cache", saved[1])
