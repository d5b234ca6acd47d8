"""The tile-configuration search: enumerate, time, keep the winner.

For one (kernel family, engine, dtype) the tuner builds the family's
candidate grid from its declared ``tile_space`` (cross product of
per-parameter values, static defaults first), times each candidate,
and returns a :class:`~repro.tuning.cache.TunedEntry` carrying the
winner plus the default's time so consumers can render the delta.

Timing sources:

* ``'proxy'`` (default) — the family's ``tune_proxy``: a pure-XLA
  reproduction of its tiling pipeline (see :mod:`repro.tuning.proxy`).
  Real compiled wall time, portable to CPU-only containers.
* ``'pallas'`` — the family's actual engine entry point.  Only
  meaningful with ``interpret=False`` on real hardware; with
  ``interpret=True`` the resulting entry is tagged
  ``'pallas-interpret'`` and the cache refuses to persist it
  (:class:`~repro.tuning.cache.InterpretTimingError`).

Candidates that fail to run (e.g. a block size a particular input
cannot satisfy) are skipped, not fatal: an autotuner that crashes on
an invalid corner of its own search space has failed at its one job.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, List, Mapping, Optional

import numpy as np

from ..core.dispatch import use_interpreter
from .cache import (SOURCE_PALLAS, SOURCE_PALLAS_INTERPRET, SOURCE_PROXY,
                    TunedEntry)

__all__ = ["CandidateTiming", "candidates", "default_params", "tune_op"]


@dataclasses.dataclass(frozen=True)
class CandidateTiming:
    """One timed candidate: its params, median wall time, and any note."""

    params: Mapping[str, int]
    median_us: float
    note: str = ""


def default_params(op) -> Dict[str, int]:
    """The family's static tile defaults (what untuned dispatch uses)."""
    return {k: int(v) for k, v in dict(op.tile_defaults).items()}


def candidates(op, budget: Optional[int] = None) -> List[Dict[str, int]]:
    """The candidate grid: cross product of ``op.tile_space`` values.

    The static default config always comes first (its timing anchors
    the tuned-vs-default delta), and *budget* caps the total number of
    candidates — the default is never the one dropped.
    """
    space = dict(op.tile_space)
    default = default_params(op)
    grid = [default]
    if space:
        names = sorted(space)
        for combo in itertools.product(*(space[n] for n in names)):
            cfg = {n: int(v) for n, v in zip(names, combo)}
            if cfg != default and cfg not in grid:
                grid.append(cfg)
    if budget is not None:
        grid = grid[:max(1, int(budget))]
    return grid


def _default_timer() -> Callable:
    """The canonical median+IQR timer (``repro.core.timing.time_fn``).

    One implementation shared with the benchmark harness (which
    re-exports it as ``benchmarks.common.time_fn``), so tuned-vs-default
    deltas and ``ref_us_per_call`` carry the same statistics.
    """
    from ..core.timing import time_fn
    return time_fn


def _time_candidate(op, engine: str, params: Mapping[str, int],
                    args: tuple, kwargs: dict, *, source: str,
                    interpret: bool, timer: Callable) -> float:
    if source == "proxy":
        if op.tune_proxy is None:
            raise ValueError(f"kernel {op.name!r} declares no tune_proxy; "
                             "cannot time candidates off-hardware")
        fn = lambda: op.tune_proxy(params, *args, **kwargs)  # noqa: E731
    else:
        engine_fn = op.engines[engine]
        fn = lambda: engine_fn(*args, interpret=interpret,  # noqa: E731
                               **{**kwargs, **params})
    return float(timer(fn).median_us)


def tune_op(op, *, engine: str, dtype: str = "float32",
            size: Optional[int] = None, budget: int = 8,
            source: str = "proxy", interpret: Optional[bool] = None,
            hw_model: str = "", seed: int = 0,
            timer: Optional[Callable] = None,
            verbose: Optional[Callable[[str], Any]] = None,
            ) -> Optional[TunedEntry]:
    """Search one (kernel, engine, dtype) and return the winning entry.

    Returns None when the family declares no tunable space.  *size*
    defaults to the family's largest ``bench_sizes`` entry — the
    bandwidth regime the sweep cares about.  The returned entry's
    ``source`` records how candidates were timed; interpret-mode Pallas
    timings produce a ``'pallas-interpret'`` entry that the cache will
    refuse (persisting them would launder emulator noise into tile
    policy).
    """
    if source not in ("proxy", "pallas"):
        raise ValueError(f"unknown timing source {source!r}; expected "
                         "'proxy' or 'pallas'")
    if not op.tile_space:
        return None
    if size is None:
        if not op.bench_sizes:
            raise ValueError(f"kernel {op.name!r} has no bench_sizes; "
                             "pass size= explicitly")
        size = max(op.bench_sizes)
    interpret = use_interpreter(interpret)
    timer = timer or _default_timer()
    rng = np.random.default_rng(seed)
    args, kwargs = op.make_inputs(rng, size, dtype)

    timings: List[CandidateTiming] = []
    for params in candidates(op, budget):
        try:
            us = _time_candidate(op, engine, params, args, kwargs,
                                 source=source, interpret=interpret,
                                 timer=timer)
        except Exception as exc:  # invalid corner of the space: skip
            timings.append(CandidateTiming(params, float("inf"),
                                           f"skipped: {exc}"))
            if verbose:
                verbose(f"{op.name}/{engine}/{dtype} {params}: "
                        f"skipped ({exc})")
            continue
        timings.append(CandidateTiming(params, us))
        if verbose:
            verbose(f"{op.name}/{engine}/{dtype} {params}: {us:.1f} us")

    ok = [t for t in timings if t.median_us != float("inf")]
    if not ok:
        raise RuntimeError(
            f"{op.name}/{engine}/{dtype}: every candidate failed "
            f"({[t.note for t in timings]})")
    best = min(ok, key=lambda t: t.median_us)
    default_us = ok[0].median_us if ok[0].params == default_params(op) \
        else best.median_us
    entry_source = SOURCE_PROXY if source == "proxy" else (
        SOURCE_PALLAS_INTERPRET if interpret else SOURCE_PALLAS)
    return TunedEntry(
        kernel=op.name, engine=engine, dtype=dtype,
        hw_model=hw_model, params=best.params, best_us=best.median_us,
        default_us=default_us, size=int(size), source=entry_source,
        budget=int(budget))
