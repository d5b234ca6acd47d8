"""Public STREAM Triad op, registered as an ``EngineOp``."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ...core.intensity import triad as triad_traits
from ..registry import EngineOp, register
from .ref import triad_ref
from .triad import triad_matrix, triad_vector

__all__ = ["TRIAD_OP", "triad"]


def _traits(b, c, q, *, block_rows=None, lanes=None):
    del c, q, block_rows, lanes
    return triad_traits(b.size, dsize=b.dtype.itemsize)


def _make_inputs(rng: np.random.Generator, size: int, dtype: str = "float32"):
    b = jnp.asarray(rng.standard_normal(size), dtype)
    c = jnp.asarray(rng.standard_normal(size), dtype)
    return (b, c, 1.5), {}


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
))


def triad(b: jnp.ndarray, c: jnp.ndarray, q, *, engine: str = "auto",
          interpret: Optional[bool] = None) -> jnp.ndarray:
    """a = b + q * c for arbitrary same-shaped b, c."""
    return TRIAD_OP(b, c, q, engine=engine, interpret=interpret)
