"""Every cell run end to end at a small size on the CPU, past the look
for a chip: sound runs are correct; the control and each fault the cell
can have make ``correct`` come out false.

Run by path: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""
import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from bench import calibrate, run
from bench.common import BENCH, ROOT, load_json
from bench.tests import small

sys.path.insert(0, str(ROOT / "src"))

CELLS = [w["name"] for w in small.spec()["workloads"]]
KERNEL_CELLS = [c for c in CELLS if c.startswith("kernels.")]
LM_CELLS = [c for c in CELLS if not c.startswith("kernels.")]
SEED = 2**31 + 97


def execute(cell, files=None, seconds=0.2, control=None):
    return run.execute(cell, SEED, seconds, False, spec=small.spec(),
                       files=files or small.files(cell), device_check=False,
                       control=control)


def test_every_name_has_its_files():
    spec = small.spec()
    for c in spec["configs"]:
        path = ROOT / c["file"]
        assert path.is_file() and path.with_suffix(".py").is_file()
        driver = load_json(path)["driver"]
        assert (BENCH / "drivers" / f"{driver}.py").is_file()
    for w in spec["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
    for m in spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = execute(cell)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks" and r["attempted"] > 0
    assert set(r["metrics"]) >= {"setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    files = small.files(cell, control=True)
    r = execute(cell, files, seconds=0.0, control=files["limits"]["control"])
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", CELLS[:1])
def test_calibrate_reads_program_and_control(cell):
    files = small.files(cell)
    out = calibrate.readings(cell, [SEED], [SEED], 0.0, files=files)
    limits = files["limits"]["checks"]
    assert set(out["summary"]) == set(limits)
    assert all(out["summary"][n]["lower"] <= limits[n]["limit"]
               < out["summary"][n]["upper"] for n in limits), out["summary"]


def _patch_dispatch(monkeypatch, fault):
    from repro.core import dispatch

    real = dispatch.Dispatcher.run

    def broken(self, op, *args, **kwargs):
        return fault(real(self, op, *args, **kwargs), args)
    monkeypatch.setattr(dispatch.Dispatcher, "run", broken)


def _first_array(args):
    return next(a for a in args if isinstance(a, jax.Array) and a.ndim)


KERNEL_FAULTS = {
    # an answer altered where it is produced
    "answer_altered": lambda out, args: out.at[(0,) * out.ndim].add(1.0),
    # the step returns its state (its input) unchanged
    "state_unchanged": lambda out, args: _first_array(args),
    # half of the work left out
    "half_left_out": lambda out, args: out.at[out.shape[0] // 2:].set(0.0),
}


@pytest.mark.parametrize("fault", sorted(KERNEL_FAULTS))
@pytest.mark.parametrize("cell", KERNEL_CELLS)
def test_kernel_fault_is_not_correct(cell, fault, monkeypatch):
    _patch_dispatch(monkeypatch, KERNEL_FAULTS[fault])
    assert not execute(cell)["correct"]


def _token_altered(engine_cls, monkeypatch):
    real = engine_cls.generate

    def broken(self, batch, gen=None):
        res = real(self, batch, gen)
        return res.__class__(**{**res.__dict__,
                                "tokens": res.tokens.at[:, 1].add(1)})
    monkeypatch.setattr(engine_cls, "generate", broken)


def _row_altered(engine_cls, monkeypatch):
    real = engine_cls.generate

    def broken(self, batch, gen=None):
        res = real(self, batch, gen)
        return res.__class__(**{**res.__dict__,
                                "tokens": res.tokens.at[-1, 1].add(1)})
    monkeypatch.setattr(engine_cls, "generate", broken)


def _state_unchanged(engine_cls, monkeypatch):
    real = engine_cls.decode_step

    def broken(self, tokens, caches, index):
        logits, _ = real(self, tokens, caches, index)
        return logits, caches
    monkeypatch.setattr(engine_cls, "decode_step", broken)


def _half_left_out(engine_cls, monkeypatch):
    real = engine_cls.generate

    def broken(self, batch, gen=None):
        res = real(self, batch, gen)
        half = res.tokens.shape[0] // 2
        tokens = res.tokens.at[half:].set(res.tokens[:half])
        return res.__class__(**{**res.__dict__, "tokens": tokens})
    monkeypatch.setattr(engine_cls, "generate", broken)


LM_FAULTS = {"token_altered": _token_altered,
             "one_row_altered": _row_altered,
             "state_unchanged": _state_unchanged,
             "half_left_out": _half_left_out}


@pytest.mark.parametrize("fault", sorted(LM_FAULTS))
@pytest.mark.parametrize("cell", LM_CELLS)
def test_lm_fault_is_not_correct(cell, fault, monkeypatch):
    from repro.models.engine import DecodeEngine

    LM_FAULTS[fault](DecodeEngine, monkeypatch)
    assert not execute(cell)["correct"]


def test_cpu_run_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_unknown_device_kind_is_an_error(monkeypatch):
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(run.BenchError, match="TPU v99"):
        run.check_device(1, load_json(BENCH / "peaks.json"))


def test_too_few_chips_is_an_error(monkeypatch):
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(run.BenchError, match="4 chips"):
        run.check_device(4, load_json(BENCH / "peaks.json"))


def test_result_line_is_strict_json():
    r = execute(KERNEL_CELLS[0])
    line = json.dumps(r)
    json.loads(line, parse_constant=lambda c: pytest.fail(c))
    assert np.isfinite(r["metrics"]["kernel_gbs"]["value"])
