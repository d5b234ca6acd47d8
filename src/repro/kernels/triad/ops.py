"""Public STREAM Triad op, registered as an ``EngineOp``."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ...core.intensity import triad as triad_traits
from ...tuning.proxy import tiled_elementwise
from ..elementwise_tuning import ELEMENTWISE_TILE_DEFAULTS, ELEMENTWISE_TILE_SPACE
from ..registry import EngineOp, register
from .ref import triad_ref
from .triad import triad_matrix, triad_vector

__all__ = ["TRIAD_OP", "triad"]


def _traits(b, c, q):
    del c, q
    return triad_traits(b.size, dsize=b.dtype.itemsize)


def _make_inputs(rng: np.random.Generator, size: int, dtype: str = "float32"):
    b = jnp.asarray(rng.standard_normal(size), dtype)
    c = jnp.asarray(rng.standard_normal(size), dtype)
    return (b, c, 1.5), {}


def _proxy_body(scalars, b, c):
    return (b + scalars[0] * c).astype(b.dtype)


def _tune_proxy(params, b, c, q):
    """Pure-XLA tiled a = b + q*c for off-hardware candidate timing."""
    return tiled_elementwise(_proxy_body, (b, c), (q,), **params)


TRIAD_OP = register(EngineOp(
    name="triad",
    traits=_traits,
    engines={"vector": triad_vector, "matrix": triad_matrix},
    reference=triad_ref,
    make_inputs=_make_inputs,
    bench_sizes=(2**18, 2**20, 2**22),
    dtypes=("float32", "bfloat16"),
    test_size=300_000,
    doc="STREAM Triad a = b + q*c; I = 2/(3D), memory-bound everywhere",
    tile_space=ELEMENTWISE_TILE_SPACE,
    tile_defaults=ELEMENTWISE_TILE_DEFAULTS,
    tune_proxy=_tune_proxy,
))


def triad(b: jnp.ndarray, c: jnp.ndarray, q, *, engine: str = "auto",
          interpret: Optional[bool] = None) -> jnp.ndarray:
    """a = b + q * c for arbitrary same-shaped b, c."""
    return TRIAD_OP(b, c, q, engine=engine, interpret=interpret)
