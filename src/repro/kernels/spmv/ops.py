"""Public SpMV op (block-ELL), registered as an ``EngineOp``.

SpMV takes no tile kwarg: its (bm, bn) blocking is baked into the
BlockEll *data layout* by ``dense_to_bell``, so retiling is done where
the layout is built.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ...core.intensity import spmv_bell as bell_traits
from ..registry import EngineOp, register
from .ref import BlockEll, bell_matvec_ref, dense_to_bell
from .spmv import bell_spmv_bell

__all__ = ["SPMV_OP", "spmv", "BlockEll", "dense_to_bell"]


def _traits(bell: BlockEll, x):
    del x
    nbr, mb, bm, bn = bell.blocks.shape
    m, n = bell.shape
    return bell_traits(m, n, nbr * mb, bm, bn,
                       dsize=bell.blocks.dtype.itemsize)


def _make_inputs(rng: np.random.Generator, size: int, dtype: str = "float32"):
    """size = row count; a ~5%-dense random matrix with 2x wider columns."""
    m = max(8, (size // 8) * 8)
    n = max(128, (2 * size // 128) * 128)
    a = rng.standard_normal((m, n)).astype(dtype)
    a = a * (rng.random((m, n)) < 0.05)
    bell = dense_to_bell(np.asarray(a), bm=8, bn=128)
    x = jnp.asarray(rng.standard_normal(n), dtype)
    return (bell, x), {}


SPMV_OP = register(EngineOp(
    name="spmv",
    traits=_traits,
    engines={
        "vector": functools.partial(bell_spmv_bell, engine="vector"),
        "matrix": functools.partial(bell_spmv_bell, engine="matrix"),
    },
    reference=bell_matvec_ref,
    make_inputs=_make_inputs,
    bench_sizes=(256, 512),
    test_size=128,
    doc="block-ELL SpMV y = A x; I ~ 1/(2D) per stored element",
    # mesh split: contiguous block-row ranges with x replicated per
    # shard (no halo — block-rows are independent; the replicated x
    # read is the honest aggregate-traffic cost the shard claims check)
    shard_kind="rowblock",
))


def spmv(bell: BlockEll, x: jnp.ndarray, *, engine: str = "auto",
         interpret: Optional[bool] = None) -> jnp.ndarray:
    """y = A x, A in block-ELL.

    'auto' consults the paper's advisor with the format's true traits;
    block-ELL SpMV intensity is ~1/(2D) per stored block element, far
    below machine balance, so auto -> vector engine.
    """
    return SPMV_OP(bell, x, engine=engine, interpret=interpret)
