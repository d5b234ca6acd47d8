"""Shared benchmark utilities: timing, CSV rows, JSON artifacts.

Record files written here are the input to the claims-report pipeline
(``repro.report``): schema-versioned ``runs/BENCH_<kernel>.json`` with
environment metadata, consumed by ``python -m benchmarks.run report``
and the ``benchmarks/compare.py`` regression gate.
"""
from __future__ import annotations

import csv
import json
import os
import sys
from typing import List, Optional, TextIO

import jax

# canonical implementation lives in the library; re-exported here for
# all existing benchmark/test consumers
from repro.core.timing import Timing, time_fn

__all__ = ["SCHEMA_VERSION", "SERVING_SCHEMA_VERSION", "Timing",
           "bench_env", "emit", "time_fn", "write_json",
           "write_serving_json"]

#: Version of the BENCH_<kernel>.json file format.  Schema 1 was a bare
#: list of records; schema 2 wraps the records with environment
#: metadata (jax version, device kind, interpret flag, hardware model);
#: schema 3 added a per-record tile-config field (written while the
#: repo carried a tile autotuner; no longer written); schema 4 is
#: the *serving* record format (see SERVING_SCHEMA_VERSION); schema 5
#: adds the mesh fields — per-record ``mesh_shape`` (the requested
#: mesh, e.g. ``[2]``) and ``shard_spec`` (the ShardPlan the point ran
#: under plus its traffic accounting), both null for single-device
#: sweep points; schema 6 adds the per-record ``mesh_exec`` field — the
#: *measured* real-mesh execution evidence from a ``--real`` sweep
#: (``repro.sharding.executor.MeshExecutor``: shard_map wall time over
#: N actual XLA devices, the ppermute halo exchange's own collective
#: time, the virtual-clock analogue, their skew, and the real-mesh
#: max_err), null for single-device and virtual-mesh sweep points;
#: schema 7 adds the per-record ``trace`` field — the ``repro.obs``
#: tracer's reconciliation block (span counts and medians from the
#: timing iterations plus the roofline gauge derived from the record's
#: own traffic/time/hardware), verified record-by-record by the
#: ``trace_reconciliation`` claim.
SCHEMA_VERSION = 7

#: Version of the serving record file format (``BENCH_serve_*.json``):
#: schema 4 marks a ``"kind": "serving"`` set whose records are
#: latency-percentile/goodput session summaries from
#: ``repro.serving.metrics.serving_record``; schema 5 adds the
#: per-record ``trace`` field (virtual-clock span counts vs. the
#: session log's own accounting — serving files are told apart from
#: bench schema 5 by their ``"kind": "serving"`` marker, not the
#: number).
SERVING_SCHEMA_VERSION = 5


def emit(rows: List[dict], out: Optional[TextIO] = None) -> None:
    """Write ``name,us_per_call,derived`` CSV rows (RFC-4180 quoted).

    Fields containing commas, quotes, or newlines are quoted/escaped by
    the ``csv`` module so derived fields can never corrupt the row
    structure.
    """
    writer = csv.writer(out if out is not None else sys.stdout,
                        lineterminator="\n")
    for r in rows:
        writer.writerow([r["name"], r.get("us_per_call", ""),
                         r.get("derived", "")])


def bench_env(hw_model: str = "") -> dict:
    """Environment metadata recorded alongside every schema-2 record set.

    ``interpret`` is the decision the Pallas launches of this process
    take (:func:`repro.core.dispatch.use_interpreter`), so a chip run is
    never labelled as interpreted.
    """
    import numpy

    from repro.core.dispatch import use_interpreter

    device = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "numpy": numpy.__version__,
        "device": device.platform,
        "device_kind": device.device_kind,
        "interpret": use_interpreter(),
        "hw_model": hw_model,
    }


def _write_record_file(filename: str, kernel: str, schema: int,
                       records: List[dict], out_dir: str,
                       env: Optional[dict],
                       extra: Optional[dict] = None) -> str:
    """The one serialization convention every record file shares."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, filename)
    payload = {
        "schema": schema,
        "kernel": kernel,
        "env": env if env is not None else {},
        "records": records,
        **(extra or {}),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def write_json(kernel: str, records: List[dict], out_dir: str = "runs",
               env: Optional[dict] = None, mesh: int = 1) -> str:
    """Write machine-readable per-kernel records to BENCH_<kernel>.json.

    Schema 7: ``{"schema": 7, "kernel": ..., "env": {...}, "records":
    [...]}`` with one record per (engine, size, dtype) sweep point
    (including its ``mesh_shape``/``shard_spec`` when swept under a
    mesh, and its observability ``trace`` block) so the perf
    trajectory is diffable across PRs and auditable by the
    ``repro.report`` claim checks.  Mesh sweeps (``mesh > 1``) land in
    ``BENCH_<kernel>_mesh<N>.json`` beside the single-device baseline
    instead of clobbering it — the compare gate joins the two kinds of
    points on distinct keys.
    """
    name = (f"BENCH_{kernel}.json" if mesh <= 1
            else f"BENCH_{kernel}_mesh{mesh}.json")
    return _write_record_file(name, kernel, SCHEMA_VERSION, records,
                              out_dir, env)


def write_serving_json(kernel: str, records: List[dict],
                       out_dir: str = "runs",
                       env: Optional[dict] = None, mesh: int = 1) -> str:
    """Write one kernel's serving sessions to BENCH_serve_<kernel>.json.

    Schema 5: ``{"schema": 5, "kind": "serving", "kernel": ..., "env":
    {...}, "records": [...]}`` with one record per (engine, workload,
    size, dtype) session, consumed by ``repro.report`` (serving claim
    checks + REPORT.md serving section) and gated on p99/goodput by
    ``benchmarks/compare.py --kind serving``.  Mesh sessions
    (``mesh > 1``) land in ``BENCH_serve_<kernel>_mesh<N>.json`` beside
    the single-device baseline instead of clobbering it, mirroring the
    bench-sweep convention.
    """
    name = (f"BENCH_serve_{kernel}.json" if mesh <= 1
            else f"BENCH_serve_{kernel}_mesh{mesh}.json")
    return _write_record_file(name, kernel, SERVING_SCHEMA_VERSION,
                              records, out_dir, env,
                              extra={"kind": "serving"})
