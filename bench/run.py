"""Run one benchmark cell once, on the chips of the machine it starts on.

    python -m bench.run --workload NAME --seed N --seconds S --trace 0|1

Everything is found by name from ``BENCHMARK.json``: the cell's entry
names its configuration (``bench/configs/<config>.json`` and, beside it,
the plain reference ``<config>.py``) and its traffic
(``bench/traffic/<traffic>.json``); the configuration names its driver
(``bench/drivers/<driver>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``; the limits of the correctness check are
in ``bench/limits/<cell>.json``; the peaks in ``bench/peaks.json``.

A run: check the device (a TPU whose ``device_kind`` the peak table
knows, with the chips the cell asks for), set up (inputs or weights from
the seed, every shape compiled or loaded from the compile cache and run
once), measure a window of ``--seconds``, read the peak device memory,
free the program's state, and check what the window produced against
the reference.  With ``--trace 1`` the window runs under the profiler
and the per-layer metrics are printed instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with a trace
``breakdown``), and last ``checks``, each compared number with its
limit.  The checks are also the last lines of standard error.  Any
failure before that line exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

from bench.common import BENCH, ROOT, load_json, load_module  # noqa: E402

#: host spans the drivers open, which the trace reduction keeps
SPAN_PREFIXES = ("window", "call:", "batch")
TRACE_DIR = ROOT / ".bench_trace"


class BenchError(RuntimeError):
    """The run cannot measure: wrong device, unknown name."""


def cell_spec(spec: dict, workload: str) -> dict:
    """The files of one cell: its entry, configuration, traffic, limits."""
    wl = {w["name"]: w for w in spec["workloads"]}.get(workload)
    if wl is None:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    cfg_file = ROOT / cfg["file"]
    return {"workload": wl,
            "config": load_json(cfg_file),
            "reference": cfg_file.with_suffix(".py"),
            "traffic": load_json(BENCH / "traffic" / f"{wl['traffic']}.json"),
            "limits": load_json(BENCH / "limits" / f"{workload}.json")}


def reported(spec: dict, workload: str):
    """(end-to-end, per-layer) metrics of ``workload``: those that list
    it, and those that list no cells (a per-layer one then goes wherever
    the metric it moves is reported)."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and ("workloads" in m or m["moves"] in names)]
    return e2e, per_layer


def cache_dir() -> Path:
    """The persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` where
    it is set, else a fixed directory in the checkout."""
    return Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or ROOT / ".jax_cache")


def configure_jax() -> None:
    """The persistent compile cache at ``cache_dir()``, for every program
    however fast it compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache_dir()))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_device(chips: int, peaks: dict):
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {len(devices)} "
                         f"{devices[0].platform} device(s)")
    if kind not in peaks:
        raise BenchError(f"device_kind {kind!r} is not in bench/peaks.json "
                         f"(known: {sorted(peaks)})")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips], peaks[kind]


def _finite(x: float) -> Optional[float]:
    return x if math.isfinite(x) else None


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            spec: Optional[dict] = None, files: Optional[dict] = None,
            device_check: bool = True, control: Optional[str] = None) -> dict:
    """One run of ``workload``; returns the result line as a dict.

    ``files`` replaces what ``cell_spec`` reads (a test runs a cell at a
    small size this way); ``device_check=False`` runs on any backend;
    ``control`` puts the reference computed in that precision in the
    program's place for the check (a test shows it is not correct).
    """
    import jax

    spec = spec or load_json(ROOT / "BENCHMARK.json")
    files = files or cell_spec(spec, workload)
    wl = files["workload"]
    peaks = load_json(BENCH / "peaks.json")
    if device_check:
        devices, peak = check_device(wl["chips"], peaks)
    else:
        devices, peak = jax.devices()[:wl["chips"]], peaks["TPU v5 lite"]
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.pop("REPRO_TUNED_JSON", None)  # static tiles, never tuned

    driver = load_module(BENCH / "drivers" / f"{files['config']['driver']}.py")
    cell = driver.Cell(files["config"], files["traffic"], seed,
                       load_module(files["reference"]))
    cell.setup()
    setup_s = time.perf_counter() - T_START

    trace_dir = TRACE_DIR / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        out = cell.window(seconds, jax.profiler.TraceAnnotation)
    finally:
        if trace:
            jax.profiler.stop_trace()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    cell.release()
    readings = cell.check(control)

    limits = files["limits"]["checks"]
    checks = {name: {"value": _finite(v), "limit": limits[name]["limit"]}
              for name, v in readings.items()}
    correct = (set(readings) == set(limits) and all(
        math.isfinite(v) and v <= limits[n]["limit"]
        for n, v in readings.items()))

    e2e, per_layer = reported(spec, workload)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"]}
    if not trace:
        values = dict(out["metrics"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]} for m in e2e}
        result["device"] = device
    else:
        from bench import trace_reduce

        path = next(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
        red = trace_reduce.reduce_trace(str(path), SPAN_PREFIXES)
        window = red.window("window")
        ctx = {"reduced": red, "window": window, "work": out["work"],
               "peak": peak}
        result["metrics"] = {}
        for m in per_layer:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device.update(busy_s=red.busy_ns(window) / 1e9,
                      window_s=window.dur / 1e9)
        result["device"] = device
        result["breakdown"] = trace_reduce.breakdown(red, window)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        configure_jax()
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] is not None and c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
