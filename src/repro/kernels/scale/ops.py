"""Public SCALE op, registered as an ``EngineOp`` (paper Fig. 6)."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ...core.intensity import scale as scale_traits
from ..registry import EngineOp, register
from .ref import scale_ref
from .scale import scale_matrix, scale_vector

__all__ = ["SCALE_OP", "scale"]


def _traits(b, q, *, block_rows=None, lanes=None):
    del q, block_rows, lanes
    return scale_traits(b.size, dsize=b.dtype.itemsize)


def _make_inputs(rng: np.random.Generator, size: int, dtype: str = "float32"):
    b = jnp.asarray(rng.standard_normal(size), dtype)
    return (b, 1.5), {}


SCALE_OP = register(EngineOp(
    name="scale",
    traits=_traits,
    engines={"vector": scale_vector, "matrix": scale_matrix},
    reference=scale_ref,
    make_inputs=_make_inputs,
    bench_sizes=(2**18, 2**20, 2**22),
    dtypes=("float32", "bfloat16"),
    test_size=300_000,
    doc="STREAM SCALE a = q*b; I = 1/(2D), memory-bound everywhere",
))


def scale(b: jnp.ndarray, q, *, engine: str = "auto",
          interpret: Optional[bool] = None) -> jnp.ndarray:
    """a = q * b for arbitrary-shaped b.

    engine: 'auto' (paper §6 advisor -> VPU, since I=1/(2D) is far below
    machine balance), 'vpu', or 'mxu' (paper Fig.-5 A = B(qI)).
    """
    return SCALE_OP(b, q, engine=engine, interpret=interpret)
