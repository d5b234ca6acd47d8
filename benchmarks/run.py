"""Benchmark harness.

Theory modules reproduce the paper's analytic tables; every *kernel*
benchmark is discovered from ``repro.kernels.registry`` and swept by the
one generic driver in ``bench_kernels`` -- there is no per-kernel module
list to maintain.

  bounds         -- Table 1 + Eq. 14/23/24 (theory)
  roofline       -- Fig. 2 (two-ceiling roofline placements)
  kernels        -- every registered kernel x engine x size x dtype
  sweep          -- alias for ``kernels`` (the name the mesh walkthrough
                    in docs/sharding.md uses)
  <kernel name>  -- one registered kernel (e.g. ``scale``, ``triad``)
  serve          -- request-level serving sessions (loadgen ->
                    continuous batching -> latency percentiles; see
                    ``benchmarks.serve`` for its flags)
  report         -- regenerate REPORT.md + docs/benchmarks/ from runs/

Prints ``name,us_per_call,derived`` CSV rows; kernel sweeps also write
``runs/BENCH_<kernel>.json`` (override the directory with ``--out DIR``
to produce a candidate set for ``benchmarks/compare.py``).

``--mesh N`` sweeps under an N-way data-axis mesh (``repro.sharding``):
engine variants execute shard by shard (halo exchange included), and
each schema-6 record carries ``mesh_shape`` + ``shard_spec`` with the
plan's traffic accounting for the shard claims in ``repro.report``.
Mesh records land in ``BENCH_<kernel>_mesh<N>.json`` beside the
single-device baseline.

``--mesh N --real`` forces the host platform to expose N actual XLA
devices (``repro.launch.mesh.host_device_count``, which must win the
race with JAX backend creation — hence it runs first thing in
``main``) and executes every sweep point through shard_map on the
real mesh too, attaching measured ``mesh_exec`` evidence (wall /
collective / virtual-analogue µs + skew) to each record and a
``collective_overlap`` probe (§4.1's overlapped-vs-serialized ring
matmul, measured) to the file's env block.

``--trace out.json`` runs the sweep under the ``repro.obs`` tracer and
exports every span (dispatch, launches with roofline counters, timing
iterations, mesh steps) as Chrome-trace JSON loadable in Perfetto /
``chrome://tracing`` and validated by ``python -m repro.obs.trace``.

``--verbose`` raises the structured logger (``repro.obs.log``) to info
so the quiet-by-default diagnostics print to stderr.
"""
from __future__ import annotations

import sys
from typing import List, Optional

from repro.kernels import registry

from . import bench_bounds, bench_kernels, bench_roofline
from .common import emit

THEORY = {
    "bounds": bench_bounds,
    "roofline": bench_roofline,
}


def _report(argv: List[str]) -> None:
    """`report` subcommand: runs/ records -> verified REPORT.md + pages."""
    from repro.report import write_report

    runs_dir = argv[0] if argv else "runs"
    for path in write_report(runs_dir=runs_dir):
        print(f"wrote {path}")


def _take_flag(argv: List[str], flag: str, what: str) -> Optional[str]:
    """Pop ``flag VALUE`` out of argv, returning VALUE (or None)."""
    if flag not in argv:
        return None
    i = argv.index(flag)
    try:
        value = argv[i + 1]
    except IndexError:
        raise SystemExit(f"{flag} requires {what}")
    del argv[i:i + 2]
    return value


def main(argv: Optional[List[str]] = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if argv and argv[0] == "serve":
        # the serving driver has its own argparse surface (workload,
        # rate, duration, mesh, ...)
        from . import serve
        raise SystemExit(serve.main(argv[1:]))
    out_dir, out_given = "runs", "--out" in argv
    taken = _take_flag(argv, "--out", "a directory argument")
    if taken is not None:
        out_dir = taken
    mesh_arg = _take_flag(argv, "--mesh", "a shard-count argument")
    trace_out = _take_flag(argv, "--trace", "an output path argument")
    real = "--real" in argv
    if real:
        argv.remove("--real")
    if "--verbose" in argv:
        argv.remove("--verbose")
        from repro.obs.log import LOG
        LOG.configure(level="info")
    try:
        mesh = 1 if mesh_arg is None else int(mesh_arg)
    except ValueError:
        raise SystemExit(f"--mesh requires an integer, got {mesh_arg!r}")
    if mesh < 1:
        raise SystemExit(f"--mesh must be >= 1, got {mesh}")
    if real:
        if mesh < 2:
            raise SystemExit("--real requires --mesh N with N >= 2")
        # must precede the first JAX computation: XLA only honors
        # --xla_force_host_platform_device_count at backend creation
        from repro.launch.mesh import host_device_count
        host_device_count(mesh)
    if argv and argv[0] == "report":
        if mesh_arg is not None:
            raise SystemExit("--mesh only applies to kernel sweeps")
        if real:
            raise SystemExit("--real only applies to kernel sweeps")
        if trace_out is not None:
            raise SystemExit("--trace only applies to kernel sweeps")
        # `report runs-ci` and `report --out runs-ci` both read runs-ci
        if out_given and len(argv) > 1:
            raise SystemExit("report: pass the records dir positionally "
                             "or via --out, not both")
        _report(argv[1:] or ([out_dir] if out_given else []))
        return
    kernel_names = set(registry.names())
    which = argv or (sorted(THEORY) + ["kernels"])
    sweeps = any(k in ("kernels", "sweep") or k in kernel_names
                 for k in which)
    if out_given and not sweeps:
        raise SystemExit("--out only applies to kernel sweeps or report")
    if mesh_arg is not None and not sweeps:
        raise SystemExit("--mesh only applies to kernel sweeps")
    if real and not sweeps:
        raise SystemExit("--real only applies to kernel sweeps")
    if trace_out is not None and not sweeps:
        raise SystemExit("--trace only applies to kernel sweeps")
    print("name,us_per_call,derived")
    for key in which:
        if key in THEORY:
            emit(THEORY[key].rows())
        elif key in ("kernels", "sweep"):
            emit(bench_kernels.rows(json_dir=out_dir, mesh=mesh,
                                    real=real, trace_out=trace_out))
        elif key in kernel_names:
            emit(bench_kernels.rows([key], json_dir=out_dir, mesh=mesh,
                                    real=real, trace_out=trace_out))
        else:
            raise SystemExit(
                f"unknown benchmark {key!r}; have "
                f"{sorted(THEORY) + ['kernels', 'report', 'serve', 'sweep'] + sorted(kernel_names)}")


if __name__ == "__main__":
    main()
