"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

LM inference under traffic: seeded requests from the serving
subsystem's load generators are queued, continuously batched, and
decoded against a KV cache (``repro.serving.lm.LMDecodeExecutor``),
with the advisor's memory-bound analysis of the decode step printed up
front (the paper's §6 technique applied to LM inference) and the
session's latency percentiles (queue/compute split), goodput, and SLO
attainment printed at the end.

``--reduced`` (default) serves the smoke-size config;
``--no-reduced`` serves the architecture at its published widths, and
``--layers N`` cuts its depth to N layers (the only cut: widths, heads
and vocabulary stay published).

This launcher serves LM decode only.  For kernel-family sessions under
injected shard failures and mesh resizes (the elastic runtime — see
docs/runtime.md), use ``python -m benchmarks.run serve --chaos SPEC``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional, Tuple

import jax.numpy as jnp

from ..configs import ARCHS, get_arch, reduced
from ..core.dispatch import DEFAULT_DISPATCHER
from ..models.config import ModelConfig
from ..obs.log import LOG
from ..serving import (BatchPolicy, LMDecodeExecutor, SLO, SessionConfig,
                       format_summary, run_session)
from ..serving.lm import decode_traits
from ..serving.requests import LM_DECODE
from .compile_cache import enable_compile_cache


def serving_configs(arch: str, *, smoke: bool = True,
                    layers: Optional[int] = None
                    ) -> Tuple[ModelConfig, ModelConfig]:
    """(published, served) configs for one architecture.

    ``smoke`` serves ``reduced(...)``; otherwise the published widths.
    ``layers`` keeps the first N layers of the served config.
    """
    full = get_arch(arch)
    cfg = reduced(full) if smoke else full
    if layers is not None:
        if not 1 <= layers <= cfg.n_layers:
            raise ValueError(f"--layers must be in [1, {cfg.n_layers}] "
                             f"for {cfg.name}, got {layers}")
        cfg = dataclasses.replace(cfg, n_layers=layers,
                                  name=f"{cfg.name}-{layers}L")
    return full, cfg


def decode_advice(full: ModelConfig):
    """The §6 Advice for a production-size decode step (batch 128, 32k)."""
    return DEFAULT_DISPATCHER.advise_traits(decode_traits(full, 128, 32768))


def make_executor(cfg: ModelConfig, full: ModelConfig, *, batch: int,
                  prompt_len: int, gen: int, seed: int = 0
                  ) -> LMDecodeExecutor:
    """The f32 LM decode executor the launcher serves through."""
    return LMDecodeExecutor(cfg, max_batch=batch, prompt_len=prompt_len,
                            max_gen=gen, dtype=jnp.float32, seed=seed,
                            verdict_cfg=full)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the smoke-size config (--no-reduced for "
                         "the published widths)")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve only the first N layers (depth cut)")
    ap.add_argument("--batch", type=int, default=4,
                    help="continuous-batching capacity (max batch)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16,
                    help="tokens generated per request")
    ap.add_argument("--workload", default="poisson",
                    choices=("poisson", "bursty", "closed"))
    ap.add_argument("--rate", type=float, default=16.0,
                    help="offered rate knob, requests/s")
    ap.add_argument("--duration", type=float, default=2.0,
                    help="session horizon, virtual seconds")
    ap.add_argument("--slo-ms", type=float, default=1000.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    LOG.configure(level="info")   # launcher mains narrate by default
    enable_compile_cache()

    full, cfg = serving_configs(args.arch, smoke=args.reduced,
                                layers=args.layers)
    # dispatch layer: the production-size decode step is memory-bound
    LOG.info("advisor", arch=full.name, advice=decode_advice(full))

    # the model-scale verdict: what fraction of a full-size decode
    # step the Eq. 23/24 memory-bound ceiling governs, op by op
    from ..models.advisor_map import model_verdict
    v = model_verdict(full, args.batch, args.prompt_len + args.gen)
    LOG.info("model verdict", model=v.model,
             memory_bound_time_frac=f"{v.memory_bound_time_frac:.1%}",
             memory_bound_bytes_frac=f"{v.memory_bound_bytes_frac:.1%}",
             memory_bound_ops=sum(1 for o in v.ops if o.memory_bound),
             ops=len(v.ops))

    executor = make_executor(cfg, full, batch=args.batch,
                             prompt_len=args.prompt_len, gen=args.gen,
                             seed=args.seed)
    session = SessionConfig(
        kernel=LM_DECODE, workload=args.workload, rate_rps=args.rate,
        duration_s=args.duration, size=args.gen, seed=args.seed,
        policy=BatchPolicy(max_batch=args.batch, max_wait_s=0.05),
        slo=SLO(latency_ms=args.slo_ms))
    t0 = time.perf_counter()
    _, summary, _ = run_session(session, executor)
    wall = time.perf_counter() - t0
    for line in format_summary(summary):
        print(line)
    print(f"(wall time {wall:.2f}s)")


if __name__ == "__main__":
    main()
