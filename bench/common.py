"""Helpers the harness, the drivers and the references share."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Sequence

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file of the benchmark by its path (names may hold
    dots and dashes, which ``import`` cannot)."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                               .parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_key(seed: int, *stream: int):
    """A JAX key for ``seed`` (any non-negative int, also past 32 bits)
    and an optional stream."""
    import jax

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    for s in stream:
        key = jax.random.fold_in(key, s)
    return key


def host_rng(seed: int, *stream: int) -> np.random.Generator:
    """A NumPy generator for ``seed`` and an optional stream."""
    return np.random.default_rng([seed, *stream])


def percentile(values: Sequence[float], q: float) -> float:
    """``numpy.percentile`` with linear interpolation (the arithmetic of
    ``repro.serving.metrics.percentile``, copied)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def round_to(x, precision: str):
    """``x`` (float32) as a lower precision would hold it.

    ``bfloat16``: rounded to bfloat16.  ``high``: kept as the two
    bfloat16 terms (hi + lo) that a three-pass float32 product reads,
    about 16 bits of mantissa.
    """
    import jax.numpy as jnp

    x = jnp.asarray(x, jnp.float32)
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "bfloat16":
        return hi
    if precision == "high":
        return hi + (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")
