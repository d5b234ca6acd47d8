"""Canonical wall-time measurement: median + IQR over warmed iterations.

The benchmark harness (``benchmarks.common`` re-exports these names)
measures ``ref_us_per_call`` with it.

When the :mod:`repro.obs` tracer is enabled, every timed iteration is
also emitted as a wall-clock span *after* the measurement loop, with
the exact start/duration that produced the sample — the span IS the
sample (zero instrumentation inside the timed region), which is what
lets the ``trace_reconciliation`` claim check span medians against
``ref_us_per_call`` with only rounding tolerance.
"""
from __future__ import annotations

import math
import time
from typing import Callable, List, NamedTuple, Tuple

import jax

from ..obs.trace import TRACER

__all__ = ["Timing", "time_fn"]


class Timing(NamedTuple):
    """One timing measurement: median + spread + the raw samples.

    ``samples_us`` is appended (defaulted) so tuple-unpacking readers
    of the original ``(median_us, iqr_us, iters)`` triple keep
    working; it holds the per-iteration wall times in chronological
    order, for distribution views (trace spans, histograms).
    """

    median_us: float  # median wall time per call, microseconds
    iqr_us: float     # interquartile range (q75 - q25), microseconds
    iters: int        # timed iterations behind the statistics
    samples_us: Tuple[float, ...] = ()  # raw per-iteration times, in order


def _quantile(sorted_times: List[float], q: float) -> float:
    """Linear-interpolated quantile of an ascending-sorted sample."""
    idx = q * (len(sorted_times) - 1)
    lo, hi = math.floor(idx), math.ceil(idx)
    frac = idx - lo
    return sorted_times[lo] * (1.0 - frac) + sorted_times[hi] * frac


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5,
            label: str = "iteration", layer: str = "timing",
            **span_attrs) -> Timing:
    """Wall-time statistics in microseconds (XLA-CPU; relative signal only).

    Returns median + IQR + iteration count + raw samples so consumers
    can see measurement spread, not just a point estimate.  *label* /
    *layer* / extra keywords only name the spans emitted when the obs
    tracer is on; they never affect the measurement.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((t0, time.perf_counter() - t0))
    if TRACER.enabled:
        # emitted after the loop so tracing adds zero overhead inside
        # any timed region; each span carries its sample verbatim
        for i, (t0, dt) in enumerate(samples):
            TRACER.emit(label, layer=layer, start_s=t0, dur_s=dt,
                        iter=i, **span_attrs)
    times = sorted(dt for _, dt in samples)
    median = _quantile(times, 0.5) * 1e6
    iqr = (_quantile(times, 0.75) - _quantile(times, 0.25)) * 1e6
    return Timing(median_us=median, iqr_us=iqr, iters=iters,
                  samples_us=tuple(dt * 1e6 for _, dt in samples))
