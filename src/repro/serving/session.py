"""One-call serving sessions: loadgen → scheduler → metrics → record.

The orchestration layer every serving consumer shares — the
``python -m benchmarks.run serve`` driver, the ``repro.launch.serve``
launcher, and ``examples/serve_lm.py`` all call :func:`run_session`
with a workload name and an executor and get back the session log, its
latency summary, and the schema-4 record dict ready for
``benchmarks/common.write_serving_json``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

from ..obs.trace import capture as trace_capture
from .batcher import KernelBatchExecutor
# re-exported here so the fault-tolerance surface is reachable from the
# session module (the orchestration layer callers already import):
# checkpoint_session snapshots a session, redispatch_failed_shard is
# the mid-batch recovery primitive the elastic loop applies
from .elastic import checkpoint_session, redispatch_failed_shard
from .loadgen import LoadGen, make_loadgen
from .metrics import ServingSummary, serving_record, summarize
from .scheduler import (BatchPolicy, ContinuousBatchingScheduler,
                        ServingLog, trace_payload)
from .slo import SLO, DEFAULT_SLO

__all__ = ["SessionConfig", "checkpoint_session",
           "redispatch_failed_shard", "run_session"]


@dataclasses.dataclass(frozen=True)
class SessionConfig:
    """Everything one serving session needs beyond its executor."""

    kernel: str
    workload: str = "poisson"
    engine: str = "auto"         # session engine flag ('auto'|'vpu'|'mxu')
    rate_rps: float = 64.0
    duration_s: float = 2.0
    size: int = 65536
    dtype: str = "float32"
    seed: int = 0
    policy: BatchPolicy = dataclasses.field(default_factory=BatchPolicy)
    slo: SLO = DEFAULT_SLO
    trace_path: Optional[str] = None
    num_shards: int = 1          # mesh shards per launch (1 = no mesh)
    # execute sharded launches on real devices (MeshExecutor, measured
    # wall time) instead of the virtual clock's modeled max-over-shards
    real_mesh: bool = False
    # SLO-aware routing: shard width from queue depth and SLO
    # headroom (repro.serving.router.SLORouter), starting at width 1
    slo_route: bool = False


def run_session(cfg: SessionConfig, executor=None,
                source: Optional[LoadGen] = None,
                ) -> Tuple[ServingLog, ServingSummary, Dict]:
    """Run one serving session and reduce it to a schema-4 record.

    Builds the workload's seeded generator (or uses a caller-supplied
    *source* — e.g. a trace parsed once for a multi-kernel sweep),
    drives the continuous-batching scheduler against *executor*
    (default: a :class:`~repro.serving.batcher.KernelBatchExecutor`
    honoring the session's engine flag), and joins the executor's
    memoized Advice (Eq. 2 intensity, Eq. 4 boundedness, the
    Eq. 17/23/24 ceiling, §6 auto-routing) onto the summary.
    """
    restore_mesh = None
    if executor is None and cfg.slo_route:
        if cfg.num_shards != 1 or cfg.real_mesh:
            raise ValueError(
                "slo_route owns the mesh width (the router grows and "
                "shrinks it); start from num_shards=1, virtual clock")
        from .router import RoutedKernelBatchExecutor, SLORouter
        executor = RoutedKernelBatchExecutor(
            engine=cfg.engine, max_batch=cfg.policy.max_batch,
            seed=cfg.seed, router=SLORouter(slo_ms=cfg.slo.latency_ms))
        # the router mutates the global dispatcher's mesh width; put
        # it back so later sessions start from the configured state
        restore_mesh = executor.dispatcher
    elif executor is None:
        executor = KernelBatchExecutor(engine=cfg.engine,
                                       max_batch=cfg.policy.max_batch,
                                       seed=cfg.seed,
                                       num_shards=cfg.num_shards,
                                       real_mesh=cfg.real_mesh)
    if source is None:
        source = make_loadgen(cfg.workload, cfg.kernel,
                              rate_rps=cfg.rate_rps, size=cfg.size,
                              dtype=cfg.dtype, seed=cfg.seed,
                              trace_path=cfg.trace_path)
    scheduler = ContinuousBatchingScheduler(executor, cfg.policy)
    try:
        with trace_capture() as view:
            log = scheduler.run(source, cfg.duration_s)
    finally:
        if restore_mesh is not None:
            restore_mesh.set_mesh(1)
    trace = trace_payload(view.events, log)
    summary = summarize(log, cfg.slo)
    advice = executor.advice_for(cfg.kernel, cfg.size, cfg.dtype)
    # an idle session still records the engine it *would* have run:
    # the forced one when forced (so vector/matrix records keep
    # distinct join keys), what 'auto' resolves to otherwise
    from ..core.dispatch import normalize_engine
    forced = normalize_engine(cfg.engine)
    engines = {r.engine for r in log.results} or \
        {forced if forced is not None else advice.engine}
    engine = engines.pop() if len(engines) == 1 else "mixed"
    # model-backed executors (LMDecodeExecutor) contribute the model
    # name, the prefill/decode phase split, and the per-op model-scale
    # verdict the model_verdict claim checks; kernel executors don't
    extras = (executor.record_extras()
              if hasattr(executor, "record_extras") else {})
    record = serving_record(
        summary, kernel=cfg.kernel, engine=engine,
        engine_auto=advice.engine, workload=cfg.workload,
        rate_rps=cfg.rate_rps, size=cfg.size, dtype=cfg.dtype,
        seed=cfg.seed, intensity=advice.intensity,
        memory_bound=advice.memory_bound,
        mxu_ceiling=advice.max_speedup_matrix,
        max_batch=cfg.policy.max_batch,
        max_wait_ms=cfg.policy.max_wait_s * 1e3,
        num_shards=cfg.num_shards,
        mesh_exec_mode=(("mesh" if cfg.real_mesh else "virtual")
                        if cfg.num_shards > 1 else None),
        model=extras.get("model"), phases=extras.get("phases"),
        verdict=extras.get("verdict"), trace=trace)
    return log, summary, record
