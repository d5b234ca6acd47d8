"""Generic registry-driven kernel benchmark: kernel x engine x size x dtype.

Replaces the per-kernel ``bench_*`` modules: every ``EngineOp`` in
``repro.kernels.registry`` is swept over its advertised sizes and
dtypes.  Per sweep point we check interpret-mode correctness of each
engine variant against the oracle, time the XLA-CPU reference (the
hardware-relative signal available in this container -- interpret-mode
Pallas wall time would measure the emulator, so per-engine records
share one ``ref_us_per_call``), and report the analytic v5e
memory-floor time plus the paper's matrix-engine ceiling from the
memoized Advice.  CSV rows go to stdout; the same records land in
``runs/BENCH_<kernel>.json`` for cross-PR perf tracking.

``--mesh N`` sweeps the same points under an N-way data-axis mesh
(``repro.sharding``): every engine variant executes shard by shard —
so the correctness column proves halo exchange and head/row splits
reproduce the oracle — and each record carries ``mesh_shape`` plus a
``shard_spec`` with the plan's traffic accounting (per-shard bytes,
aggregate vs. unsharded bytes, worst per-shard intensity), which the
claims layer verifies against the paper's per-device ceiling
(Eq. 23/24 survives aggregation: per-shard bandwidth still sets the
roof).

``--mesh N --real`` additionally runs every sweep point through
``repro.sharding.executor.MeshExecutor`` — one ``shard_map`` step over
N actual XLA host devices — and attaches a schema-6 ``mesh_exec``
block per record: *measured* mesh wall time, the halo exchange's own
measured collective time (the ``ppermute`` ring probe; 0 when the
plan wires no bytes), the virtual-clock analogue restated on the same
XLA-native math, and the real-vs-virtual skew the compare gate
tracks.  The virtual executor still supplies the per-engine
correctness column; the mesh numbers are execution evidence, shared
across a point's engine records like ``ref_us_per_call``.
"""
from __future__ import annotations

import contextlib
import statistics
from typing import Iterable, List, Optional

import numpy as np

from repro.core.dispatch import DEFAULT_DISPATCHER
from repro.kernels import registry
from repro.obs.counters import roofline_sample
from repro.obs.trace import capture as trace_capture
from repro.obs.trace import write_chrome_trace
from repro.sharding import ShardedExecutor, traffic
from repro.sharding.executor import MeshExecutor

from .common import bench_env, emit, time_fn, write_json


def _shard_spec_field(op, plan, args, kw, hw) -> dict:
    """The schema-5 ``shard_spec`` evidence for one mesh sweep point.

    The plan's compact spec plus its Eq. 2 traffic accounting: the
    worst shard's bytes and intensity (what sets the per-shard roof),
    the aggregate bytes all shards move vs. the unsharded total (the
    halo / replication overhead the claims layer bounds), and the
    per-shard analytic memory-floor time on the v5e model.
    """
    t = traffic(op, plan, args, kw)
    return {
        **plan.spec.to_json(),
        "total_bytes": t["total_bytes"],
        "agg_bytes": t["agg_bytes"],
        "wire_bytes": t["wire_bytes"],
        "shard_bytes": t["shard_bytes"],
        "shard_intensity": t["shard_intensity"],
        "pred_shard_us_v5e": round(
            t["shard_bytes"] / hw.mem_bw * 1e6, 3),
    }


def records_for(op, mesh: int = 1, real: bool = False) -> List[dict]:
    """One record per (engine, size, dtype) for a registered kernel.

    With ``mesh > 1`` each engine variant runs through the sharded
    executor instead of a single launch; ``max_err`` then certifies
    the *sharded* result against the oracle.  With ``real`` the point
    additionally executes on a real N-device mesh and every record
    carries the measured ``mesh_exec`` evidence.
    """
    rng = np.random.default_rng(0)
    hw = DEFAULT_DISPATCHER.hw
    sharded = ShardedExecutor(mesh) if mesh > 1 else None
    mesh_exec = MeshExecutor(mesh) if (real and mesh > 1) else None
    recs = []
    for size in op.bench_sizes:
        for dtype in op.dtypes:
            args, kw = op.make_inputs(rng, size, dtype)
            advice = DEFAULT_DISPATCHER.advise(op, *args, **kw)
            traits = op.traits(*args, **kw)
            want = np.asarray(op.reference(*args, **kw), np.float32)
            # the tracer observes the same samples the Timing reports:
            # time_fn emits one span per iteration after the loop, so
            # the per-record trace block reconciles against
            # ref_us_per_call with only rounding slack
            with trace_capture() as view:
                t = time_fn(lambda: op.reference(*args, **kw),
                            label="ref_call", layer="bench",
                            kernel=op.name, size=size, dtype=dtype)
            ref_spans = [e for e in view.events if e.name == "ref_call"]
            ref_round = round(t.median_us, 1)
            span_median = statistics.median(
                e.dur_us for e in ref_spans)
            pred_us = traits.traffic_bytes / hw.mem_bw * 1e6
            plan = (sharded.plan(op, *args, **kw)
                    if sharded is not None else None)
            # engine-invariant: the split and its byte accounting
            # depend only on the call shape, so slice + re-derive the
            # per-shard traits once per (size, dtype), not per engine
            shard_field = (_shard_spec_field(op, plan, args, kw, hw)
                           if plan is not None else None)
            mesh_field = None
            mesh_trace = None
            if mesh_exec is not None:
                # one real shard_map execution per point, shared by the
                # engine records (mesh bodies are XLA-native reference
                # math, engine-independent — same policy as
                # ref_us_per_call); mesh_max_err certifies the real
                # halo exchange / head split against the oracle
                mrun = mesh_exec.run(op, *args, plan=plan, **kw)
                mesh_err = float(np.max(np.abs(
                    np.asarray(mrun.out, np.float32) - want)))
                with trace_capture() as mview:
                    mesh_field = mesh_exec.measure(op, *args, plan=plan,
                                                   **kw)
                mesh_field["mesh_max_err"] = mesh_err
                steps = [e for e in mview.events
                         if e.name == "mesh_step"]
                mesh_trace = {
                    "spans": len(steps),
                    "span_median_us": round(statistics.median(
                        e.dur_us for e in steps), 3),
                    "mesh_wall_us": mesh_field["mesh_wall_us"],
                }
            for engine in sorted(op.engines):
                if sharded is not None:
                    run = sharded.run(op, *args, engine=engine,
                                      plan=plan, **kw)
                    got = np.asarray(run.out, np.float32)
                else:
                    got = np.asarray(op(*args, engine=engine, **kw),
                                     np.float32)
                err = float(np.max(np.abs(got - want)))
                recs.append({
                    "kernel": op.name,
                    "engine": engine,
                    "size": size,
                    "dtype": dtype,
                    # one shared timing per (size, dtype): the oracle's
                    # XLA-CPU wall time, NOT the engine variant's
                    "ref_us_per_call": ref_round,
                    "iqr_us": round(t.iqr_us, 1),
                    "iters": t.iters,
                    # the tracer's independent account of the same
                    # measurement; the roofline gauge is derived from
                    # the *recorded* (rounded) median so the
                    # trace_reconciliation claim re-derives it exactly
                    "trace": {
                        "clock": "wall",
                        "spans": len(ref_spans),
                        "span_median_us": round(span_median, 3),
                        "roofline": roofline_sample(
                            traits, hw, engine, dtype,
                            ref_round).as_attrs(),
                        **({"mesh": mesh_trace}
                           if mesh_trace is not None else {}),
                    },
                    "max_err": err,
                    "intensity": traits.intensity,
                    "memory_bound": advice.memory_bound,
                    "engine_auto": advice.engine,
                    "pred_us_v5e": round(pred_us, 3),
                    "mxu_ceiling": advice.max_speedup_matrix,
                    "mesh_shape": [mesh] if mesh > 1 else None,
                    "shard_spec": shard_field,
                    "mesh_exec": mesh_field,
                })
    return recs


def rows(names: Optional[Iterable[str]] = None,
         json_dir: Optional[str] = "runs",
         mesh: int = 1,
         real: bool = False,
         trace_out: Optional[str] = None) -> List[dict]:
    """Sweep the registry; optionally export the full span timeline.

    With *trace_out* the whole sweep runs under an enabled tracer
    (dispatch/launch spans, timing iterations, mesh steps) and the
    collected events are written as Chrome-trace JSON — the per-record
    reconciliation captures nest inside this outer one, so the export
    sees everything they saw.
    """
    mesh = max(1, int(mesh))
    # the dispatcher plans shard specs onto its memoized Advice for the
    # sweep's mesh width (restored after: rows() must not leak mesh
    # state into later in-process callers)
    prior_mesh = DEFAULT_DISPATCHER.mesh_shards
    prior_mode = DEFAULT_DISPATCHER.mesh_mode
    DEFAULT_DISPATCHER.set_mesh(mesh, "mesh" if real else "virtual")
    try:
        wanted = set(names) if names is not None else None
        overlap = None
        if real and mesh > 1:
            # once per sweep: §4.1's lesson measured on the live mesh
            # (ring weight-gather vs serialized all_gather matmul),
            # recorded in every file's env block
            overlap = MeshExecutor(mesh).overlap_probe()
        out = []
        with contextlib.ExitStack() as stack:
            # enable the process tracer for the whole sweep only when
            # an export was asked for; the per-record reconciliation
            # captures enable it around their own timing either way
            sweep_view = (stack.enter_context(trace_capture())
                          if trace_out is not None else None)
            for op in registry.all_ops():
                if wanted is not None and op.name not in wanted:
                    continue
                recs = records_for(op, mesh=mesh, real=real)
                if json_dir:
                    env = bench_env(hw_model=DEFAULT_DISPATCHER.hw.name)
                    if mesh > 1:
                        env["mesh_shape"] = [mesh]
                        env["mesh_exec_mode"] = ("mesh" if real
                                                 else "virtual")
                    if overlap is not None:
                        env["collective_overlap"] = overlap
                    write_json(op.name, recs, json_dir, env=env,
                               mesh=mesh)
                out.extend(_csv_rows(recs, mesh))
        if sweep_view is not None:
            write_chrome_trace(trace_out, sweep_view.events,
                               meta={"source": "benchmarks.bench_kernels",
                                     "mesh": mesh,
                                     "real": bool(real)})
        return out
    finally:
        DEFAULT_DISPATCHER.set_mesh(prior_mesh, prior_mode)


def _csv_rows(recs: List[dict], mesh: int) -> List[dict]:
    """The stdout CSV projection of one kernel's sweep records."""
    out = []
    for r in recs:
        spec = r.get("shard_spec")
        shard = "" if not spec else (
            f";shards={spec['num_shards']};halo={spec['halo']};"
            f"agg/total={spec['agg_bytes'] / spec['total_bytes']:.3f}")
        mex = r.get("mesh_exec")
        if mex:
            shard += (f";mesh_wall_us={mex['mesh_wall_us']};"
                      f"coll_us={mex['collective_us']};"
                      f"skew={mex['skew']}")
        name = f"{r['kernel']}/{r['engine']}/n={r['size']}/{r['dtype']}"
        if mesh > 1:
            name += f"/mesh={mesh}"
        out.append({
            "name": name,
            "us_per_call": f"{r['ref_us_per_call']:.1f}",
            "derived": (f"pred_us_v5e={r['pred_us_v5e']};"
                        f"I={r['intensity']:.4f};"
                        f"auto={r['engine_auto']};"
                        f"mxu_ceiling={r['mxu_ceiling']:.4f}x;"
                        f"err={r['max_err']:.2e}" + shard),
        })
    return out


def main():
    emit(rows())


if __name__ == "__main__":
    main()
