"""Serving benchmark: latency-percentile sessions over the dispatcher.

``python -m benchmarks.run serve`` drives the request-level serving
subsystem (``repro.serving``) against registered kernel families: one
session per (kernel, engine, workload), each replaying the same seeded
traffic through the continuous-batching scheduler with the engine
forced to the vector and then the matrix variant (plus whatever
``engine='auto'`` resolves to via the memoized Advice — recorded so the
claims layer can re-check §6 routing under load).

Each kernel's sessions land in ``<out>/BENCH_serve_<kernel>.json``
(schema 4) for ``python -m benchmarks.run report`` and the
``benchmarks/compare.py --kind serving`` p99/goodput gate; a summary
table prints per session.

``--chaos SPEC`` routes each kernel session through the elastic
runtime (:class:`~repro.serving.elastic.ElasticSession`): the seeded
spec (``fail@T[:SHARD]`` / ``resize@T:WIDTH`` tokens) injects shard
failures and mesh resizes mid-session, the session re-dispatches and
re-shards without dropping or corrupting a request, and the record
grows an ``events`` block (failure/resize log, availability, chaos
vs. fault-free checksums) that the ``elastic_integrity`` claim and the
``compare.py`` availability gate verify.  Chaos needs the replayable
virtual clock and an open-loop workload, so it composes with
``--mesh`` but refuses ``--real``, ``--workload closed``, and
``--workload lm``.

``--slo-route`` serves every session through
:class:`~repro.serving.router.RoutedKernelBatchExecutor`: the
:class:`~repro.serving.router.SLORouter` picks the shard width at each
batch dequeue from queue depth + SLO headroom, starting at width 1 on
the virtual clock.  Routed records carry no marker of their own, so
write them to their own ``--out`` directory.

``--trace-out PATH`` exports the sweep's virtual-clock span timeline
(admissions, queue waits, batch launches; chaos injections and
redispatches under ``--chaos``) as Chrome-trace JSON — ``--trace``
names a *workload input* file, ``--trace-out`` the observability
export.  Records always carry the compact ``trace`` reconciliation
block either way (the ``trace_reconciliation`` claim checks it).

``--workload lm`` switches from kernel families to whole-model decode:
each ``--config`` architecture (smoke-sized for execution, full-sized
for the analytics) is served through the scan-over-layers
:class:`~repro.models.engine.DecodeEngine` with registry-dispatched
flash-decode attention, once per forced engine.  The records key as
``lm-<config>`` and additionally carry the prefill/decode phase split
and the per-op model-scale ``verdict`` payload the ``model_verdict``
claim checks — the paper's Eq. 23/24 ceiling accounted op by op over a
real model's decode step.
"""
from __future__ import annotations

import argparse
from typing import List, Optional

from repro.core.dispatch import DEFAULT_DISPATCHER
from repro.kernels import registry
from repro.serving import (WORKLOADS, BatchPolicy, PoissonLoadGen, SLO,
                           SessionConfig, run_session)

from .common import bench_env, write_serving_json

#: Families swept by default: the elementwise suite the batcher packs
#: into fused launches (fast enough for PR CI); ``--kernels all`` sweeps
#: every registered family through the per-request fallback too.
DEFAULT_KERNELS = ("scale", "triad", "axpy")

#: Engines each session config is served under.  'auto' is not swept
#: separately: its resolution is recorded as ``engine_auto`` on every
#: record, and on memory-bound families it coincides with 'vector'.
ENGINES = ("vector", "matrix")


def _parse(argv: Optional[List[str]]) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="benchmarks.run serve", description=__doc__.splitlines()[0])
    p.add_argument("--workload", default="poisson",
                   choices=tuple(WORKLOADS) + ("lm",),
                   help="traffic model, or 'lm' for whole-model decode "
                        "sessions (default poisson)")
    p.add_argument("--rate", type=float, default=None,
                   help="offered rate knob, requests/s "
                        "(default 64; lm: 8)")
    p.add_argument("--duration", type=float, default=None,
                   help="session horizon in virtual seconds "
                        "(default 2; lm: 1)")
    p.add_argument("--kernels", default=None,
                   help="comma-separated families, or 'all' "
                        f"(default {','.join(DEFAULT_KERNELS)})")
    p.add_argument("--config", default="deepseek_7b",
                   help="comma-separated model configs for --workload "
                        "lm (underscores ok, unique prefixes ok; "
                        "default deepseek_7b)")
    p.add_argument("--prompt-len", type=int, default=8,
                   help="lm: prompt tokens per request (default 8)")
    p.add_argument("--gen", type=int, default=4,
                   help="lm: decode tokens per request (default 4)")
    p.add_argument("--size", type=int, default=65536,
                   help="per-request elements (default 65536)")
    p.add_argument("--dtype", default="float32",
                   help="request dtype (default float32)")
    p.add_argument("--seed", type=int, default=0,
                   help="loadgen seed; sessions replay exactly (default 0)")
    p.add_argument("--mesh", type=int, default=1,
                   help="data-axis mesh width: every launch splits into "
                        "this many shards and batches are charged the "
                        "shard-parallel compute time (default 1)")
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="inject failures/resizes via the elastic "
                        "runtime: comma-separated 'fail@T[:SHARD]' and "
                        "'resize@T:WIDTH' tokens (virtual seconds); "
                        "records grow an events block the "
                        "elastic_integrity claim verifies")
    p.add_argument("--real", action="store_true",
                   help="execute sharded batches on a real N-device "
                        "host mesh (shard_map + measured wall time) "
                        "instead of the virtual max-over-shards clock; "
                        "requires --mesh N >= 2")
    p.add_argument("--max-batch", type=int, default=None,
                   help="continuous-batching size trigger "
                        "(default 8; lm: 4)")
    p.add_argument("--max-wait-ms", type=float, default=20.0,
                   help="continuous-batching age trigger (default 20)")
    p.add_argument("--slo-ms", type=float, default=None,
                   help="end-to-end latency SLO "
                        "(default 50; lm: 30000 — interpret-mode decode "
                        "steps are wall-time slow)")
    p.add_argument("--trace", default=None,
                   help="JSON trace path (required for --workload trace)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="export the sessions' span timeline (virtual "
                        "clock: admits, queues, batches, chaos "
                        "injections, redispatches, resizes) as "
                        "Chrome-trace JSON; --trace names the "
                        "*workload input*, this names the "
                        "observability output")
    p.add_argument("--slo-route", action="store_true",
                   help="pick each session's shard width from queue "
                        "depth + SLO headroom "
                        "(repro.serving.router.SLORouter), starting at "
                        "width 1 on the virtual clock")
    p.add_argument("--out", default="runs",
                   help="record directory (default runs)")
    return p.parse_args(argv)


def _resolve_configs(spec: str) -> List[str]:
    """Resolve a ``--config`` list against the architecture registry.

    Accepts the registry's dash-separated names, underscore spellings
    (CLI-friendly: ``deepseek_7b``), and unique prefixes."""
    from repro.configs import ARCHS
    out = []
    for raw in (s.strip() for s in spec.split(",") if s.strip()):
        name = raw.replace("_", "-")
        if name in ARCHS:
            out.append(name)
            continue
        matches = sorted(k for k in ARCHS if k.startswith(name))
        if len(matches) == 1:
            out.append(matches[0])
        elif not matches:
            raise SystemExit(f"unknown model config {raw!r}; have "
                             f"{sorted(ARCHS)}")
        else:
            raise SystemExit(f"ambiguous model config {raw!r}: {matches}")
    return out


def _serve_lm(args: argparse.Namespace) -> int:
    """The ``--workload lm`` sweep: one decode-engine session per
    (model config, forced engine), smoke-sized execution with
    full-size analytics (the model-scale verdict)."""
    from repro.configs import get_arch, reduced
    from repro.serving.lm import LMDecodeExecutor

    configs = _resolve_configs(args.config)
    policy = BatchPolicy(max_batch=args.max_batch,
                         max_wait_s=args.max_wait_ms / 1e3)
    slo = SLO(latency_ms=args.slo_ms)
    env = bench_env(hw_model=DEFAULT_DISPATCHER.hw.name)
    print("kernel,engine,workload,completed,p50_ms,p99_ms,goodput_rps,"
          "slo_attainment")
    for name in configs:
        full = get_arch(name)
        kernel = f"lm-{full.name}"
        records = []
        for engine in ENGINES:
            executor = LMDecodeExecutor(
                reduced(full), max_batch=args.max_batch,
                prompt_len=args.prompt_len, max_gen=args.gen,
                seed=args.seed, engine=engine, verdict_cfg=full)
            # the lm source is built here, not via make_loadgen: the
            # record's workload field says 'lm' while the arrivals are
            # plain seeded Poisson traffic over the decode kernel
            source = PoissonLoadGen(kernel=kernel, rate_rps=args.rate,
                                    size=args.gen, dtype=args.dtype,
                                    seed=args.seed)
            cfg = SessionConfig(
                kernel=kernel, workload="lm", engine=engine,
                rate_rps=args.rate, duration_s=args.duration,
                size=args.gen, dtype=args.dtype, seed=args.seed,
                policy=policy, slo=slo)
            _, summary, record = run_session(cfg, executor=executor,
                                             source=source)
            records.append(record)
            print(f"{kernel},{record['engine']},lm,"
                  f"{summary.completed},{summary.p50_ms:.3f},"
                  f"{summary.p99_ms:.3f},{summary.goodput_rps:.3f},"
                  f"{summary.slo_attainment:.4f}")
        path = write_serving_json(kernel, records, args.out, env=env)
        print(f"# wrote {path}")
    return 0


def _run_traced(args: argparse.Namespace, fn) -> int:
    """Run *fn* (the session sweep) under the obs tracer if asked.

    With ``--trace-out`` every session's virtual-clock spans — plus the
    chaos instants for ``--chaos`` runs — are collected across the
    whole sweep and exported as one Chrome-trace file; the sessions'
    own per-record reconciliation captures nest inside this one.
    """
    if not args.trace_out:
        return fn()
    from repro.obs.trace import capture as trace_capture
    from repro.obs.trace import write_chrome_trace
    with trace_capture() as view:
        status = fn()
    write_chrome_trace(args.trace_out, view.events,
                       meta={"source": "benchmarks.serve",
                             "workload": args.workload,
                             "chaos": args.chaos or "",
                             "seed": args.seed,
                             "mesh": args.mesh})
    print(f"# wrote {args.trace_out}")
    return status


def main(argv: Optional[List[str]] = None) -> int:
    args = _parse(argv)
    lm = args.workload == "lm"
    # per-workload defaults: interpret-mode decode steps cost wall
    # seconds, so lm sessions default to lighter traffic and an SLO
    # that measures attainment instead of guaranteeing zero goodput
    if args.rate is None:
        args.rate = 8.0 if lm else 64.0
    if args.duration is None:
        args.duration = 1.0 if lm else 2.0
    if args.max_batch is None:
        args.max_batch = 4 if lm else 8
    if args.slo_ms is None:
        args.slo_ms = 30000.0 if lm else 50.0
    if args.slo_route:
        # the router owns the mesh width on the virtual clock
        if lm:
            raise SystemExit("--slo-route is not supported for "
                             "--workload lm (kernel sessions only)")
        if args.chaos:
            raise SystemExit("--slo-route composes with the standard "
                             "session, not --chaos (the elastic "
                             "session owns the width there)")
        if args.real or args.mesh > 1:
            raise SystemExit("--slo-route owns the mesh width (the "
                             "router grows and shrinks it): drop "
                             "--mesh/--real")
    injector = None
    if args.chaos:
        # validate the adversary up front: the elastic runtime needs a
        # replayable clock (virtual mesh) and replayable arrivals
        # (open-loop traffic) so the fault-free checksum leg is exact
        if lm:
            raise SystemExit("--chaos is not supported for --workload "
                             "lm (kernel sessions only)")
        if args.real:
            raise SystemExit("--chaos requires the virtual clock: drop "
                             "--real (a measured mesh wall is not "
                             "bit-replayable against the fault-free leg)")
        if args.workload == "closed":
            raise SystemExit("--chaos requires an open-loop workload "
                             "(poisson/bursty/trace): closed-loop "
                             "arrivals react to completions and cannot "
                             "replay fault-free")
        from repro.serving import ChaosInjector
        try:
            injector = ChaosInjector(args.chaos)
        except ValueError as err:
            raise SystemExit(f"bad --chaos spec: {err}")
    if lm:
        return _run_traced(args, lambda: _serve_lm(args))
    if args.workload == "trace" and not args.trace:
        raise SystemExit("--workload trace requires --trace PATH")
    if args.real:
        if args.mesh < 2:
            raise SystemExit("--real requires --mesh N with N >= 2")
        # must win the race with JAX backend creation (XLA reads
        # --xla_force_host_platform_device_count exactly once)
        from repro.launch.mesh import host_device_count
        host_device_count(args.mesh)
    explicit = args.kernels is not None and args.kernels != "all"
    names = (tuple(args.kernels.split(",")) if explicit
             else registry.names() if args.kernels == "all"
             else DEFAULT_KERNELS)
    unknown = sorted(set(names) - set(registry.names()))
    if unknown:
        raise SystemExit(f"unknown kernel(s) {unknown}; have "
                         f"{sorted(registry.names())}")
    trace = None
    if args.workload == "trace":
        # parse the trace once; it names its own kernels, so reconcile
        # with the sweep list up front instead of crashing mid-sweep on
        # the first family the trace doesn't cover
        from repro.serving import TraceLoadGen, load_trace
        trace = load_trace(args.trace)
        available = {r.kernel for r in trace.requests}
        if explicit:
            missing = sorted(set(names) - available)
            if missing:
                raise SystemExit(
                    f"trace {args.trace!r} holds no requests for "
                    f"kernel(s) {missing} (has {sorted(available)})")
        else:
            names = tuple(k for k in names if k in available)
            if not names:
                raise SystemExit(
                    f"trace {args.trace!r} covers no registered kernel "
                    f"(has {sorted(available)})")
    policy = BatchPolicy(max_batch=args.max_batch,
                         max_wait_s=args.max_wait_ms / 1e3)
    slo = SLO(latency_ms=args.slo_ms)
    env = bench_env(hw_model=DEFAULT_DISPATCHER.hw.name)
    if args.mesh > 1:
        env["mesh_shape"] = [args.mesh]
        env["mesh_exec_mode"] = "mesh" if args.real else "virtual"
    print("kernel,engine,workload,completed,p50_ms,p99_ms,goodput_rps,"
          "slo_attainment")

    def _sweep() -> int:
        for kernel in names:
            records = []
            # per-kernel view of the once-parsed trace (None for the
            # synthetic workloads: run_session builds those generators)
            source = None if trace is None else TraceLoadGen(
                requests=[r for r in trace.requests
                          if r.kernel == kernel])
            for engine in ENGINES:
                cfg = SessionConfig(
                    kernel=kernel, workload=args.workload, engine=engine,
                    rate_rps=args.rate, duration_s=args.duration,
                    size=args.size, dtype=args.dtype, seed=args.seed,
                    policy=policy, slo=slo, trace_path=args.trace,
                    num_shards=args.mesh, real_mesh=args.real,
                    slo_route=args.slo_route)
                if injector is not None:
                    from repro.serving import ElasticSession
                    session = ElasticSession(cfg, injector=injector)
                    _, summary, record = session.run()
                else:
                    _, summary, record = run_session(cfg, source=source)
                records.append(record)
                print(f"{kernel},{record['engine']},{args.workload},"
                      f"{summary.completed},{summary.p50_ms:.3f},"
                      f"{summary.p99_ms:.3f},{summary.goodput_rps:.3f},"
                      f"{summary.slo_attainment:.4f}")
            path = write_serving_json(kernel, records, args.out, env=env,
                                      mesh=args.mesh)
            print(f"# wrote {path}")
        return 0

    return _run_traced(args, _sweep)


if __name__ == "__main__":
    raise SystemExit(main())
