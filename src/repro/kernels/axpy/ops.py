"""Public AXPY op, registered as an ``EngineOp``."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ...core.intensity import axpy as axpy_traits
from ...tuning.proxy import tiled_elementwise
from ..elementwise_tuning import ELEMENTWISE_TILE_DEFAULTS, ELEMENTWISE_TILE_SPACE
from ..registry import EngineOp, register
from .axpy import axpy_matrix, axpy_vector
from .ref import axpy_ref

__all__ = ["AXPY_OP", "axpy"]


def _traits(a, x, y):
    del a, y
    return axpy_traits(x.size, dsize=x.dtype.itemsize)


def _make_inputs(rng: np.random.Generator, size: int, dtype: str = "float32"):
    x = jnp.asarray(rng.standard_normal(size), dtype)
    y = jnp.asarray(rng.standard_normal(size), dtype)
    return (0.75, x, y), {}


def _proxy_body(scalars, x, y):
    return (scalars[0] * x + y).astype(x.dtype)


def _tune_proxy(params, a, x, y):
    """Pure-XLA tiled y = a*x + y for off-hardware candidate timing."""
    return tiled_elementwise(_proxy_body, (x, y), (a,), **params)


AXPY_OP = register(EngineOp(
    name="axpy",
    traits=_traits,
    engines={"vector": axpy_vector, "matrix": axpy_matrix},
    reference=axpy_ref,
    make_inputs=_make_inputs,
    bench_sizes=(2**18, 2**20, 2**22),
    dtypes=("float32", "bfloat16"),
    test_size=300_000,
    doc="AXPY y = a*x + y; I = 2/(3D), memory-bound everywhere",
    tile_space=ELEMENTWISE_TILE_SPACE,
    tile_defaults=ELEMENTWISE_TILE_DEFAULTS,
    tune_proxy=_tune_proxy,
))


def axpy(a, x: jnp.ndarray, y: jnp.ndarray, *, engine: str = "auto",
         interpret: Optional[bool] = None) -> jnp.ndarray:
    """y = a * x + y for arbitrary same-shaped x, y."""
    return AXPY_OP(a, x, y, engine=engine, interpret=interpret)
