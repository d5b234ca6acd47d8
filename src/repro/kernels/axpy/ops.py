"""Public AXPY op, registered as an ``EngineOp``."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ...core.intensity import axpy as axpy_traits
from ..registry import EngineOp, register
from .axpy import axpy_matrix, axpy_vector
from .ref import axpy_ref

__all__ = ["AXPY_OP", "axpy"]


def _traits(a, x, y, *, block_rows=None, lanes=None):
    del a, y, block_rows, lanes
    return axpy_traits(x.size, dsize=x.dtype.itemsize)


def _make_inputs(rng: np.random.Generator, size: int, dtype: str = "float32"):
    x = jnp.asarray(rng.standard_normal(size), dtype)
    y = jnp.asarray(rng.standard_normal(size), dtype)
    return (0.75, x, y), {}


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
))


def axpy(a, x: jnp.ndarray, y: jnp.ndarray, *, engine: str = "auto",
         interpret: Optional[bool] = None) -> jnp.ndarray:
    """y = a * x + y for arbitrary same-shaped x, y."""
    return AXPY_OP(a, x, y, engine=engine, interpret=interpret)
