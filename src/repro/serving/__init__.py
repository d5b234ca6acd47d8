"""Request-level serving subsystem over the engine dispatcher.

The paper's verdict — matrix engines cannot meaningfully accelerate
memory-bound kernels — is established per call; this package checks it
**in steady state under load**, where decode/SpMV/stencil-shaped work
arrives as a request stream.  The layers:

* :mod:`repro.serving.requests` — typed requests/results on a virtual
  serving clock.
* :mod:`repro.serving.loadgen` — seeded, replayable traffic generators
  (Poisson open-loop, bursty on/off, closed-loop, JSON traces).
* :mod:`repro.serving.scheduler` — admission queue + continuous
  batching (size/age triggers, oldest-first fairness).
* :mod:`repro.serving.batcher` — padding-aware packing of elementwise
  families through the dispatch layer's tile shapes, engine selection
  via memoized Advice (§6 routing off the hot path).
* :mod:`repro.serving.lm` — the LM decode executor (prefill + batched
  greedy decode), the memory-bound regime the advisor classifies.
* :mod:`repro.serving.metrics` / :mod:`repro.serving.slo` — latency
  percentiles with queue/compute split, goodput and SLO attainment,
  emitted as schema-4 records for ``repro.report`` and the
  ``benchmarks/compare.py`` p99/goodput gate.
* :mod:`repro.serving.session` — the one-call session driver.
* :mod:`repro.serving.router` — the SLO-aware control plane: shard
  width from queue depth and SLO headroom (``serve --slo-route``).
* :mod:`repro.serving.elastic` — the elastic, fault-tolerant session:
  mesh resizes under load (``Dispatcher.set_mesh`` +
  ``runtime/elastic.mesh_transition_plan``), bit-exact re-dispatch of
  a failed shard's ShardPlan ranges, checkpoint/restore through
  ``runtime/checkpoint.AsyncCheckpointer``, and the seeded
  fault/resize injector — evidence for the ``elastic_integrity``
  claim.

Entry points: ``python -m benchmarks.run serve`` (record-producing
sweeps; ``--chaos`` for fault injection) and
``python -m repro.launch.serve`` (LM serving demo).
"""
from .batcher import KernelBatchExecutor
from .elastic import (ChaosEvent, ChaosInjector, ElasticKernelExecutor,
                      ElasticSession, checkpoint_session,
                      redispatch_failed_shard)
from .loadgen import (WORKLOADS, BurstyLoadGen, ClosedLoopLoadGen, LoadGen,
                      PoissonLoadGen, TraceLoadGen, load_trace,
                      make_loadgen, save_trace)
from .lm import LMDecodeExecutor, decode_traits
from .metrics import (ServingSummary, format_summary, percentile,
                      serving_record, summarize)
from .requests import LM_DECODE, Request, RequestResult
from .router import RoutedKernelBatchExecutor, RouterDecision, SLORouter
from .scheduler import (BatchExecution, BatchPolicy,
                        ContinuousBatchingScheduler, ServingLog)
from .session import SessionConfig, run_session
from .slo import DEFAULT_SLO, SLO

__all__ = [
    "BatchExecution", "BatchPolicy", "BurstyLoadGen", "ChaosEvent",
    "ChaosInjector", "ClosedLoopLoadGen", "ContinuousBatchingScheduler",
    "DEFAULT_SLO", "ElasticKernelExecutor", "ElasticSession",
    "KernelBatchExecutor", "LMDecodeExecutor", "LM_DECODE", "LoadGen",
    "PoissonLoadGen", "Request", "RequestResult",
    "RoutedKernelBatchExecutor", "RouterDecision", "SLO", "SLORouter",
    "ServingLog", "ServingSummary", "SessionConfig", "TraceLoadGen", "WORKLOADS",
    "checkpoint_session", "decode_traits", "format_summary", "load_trace",
    "make_loadgen", "percentile", "redispatch_failed_shard", "run_session",
    "save_trace", "serving_record", "summarize",
]
