"""Pallas TPU SpMV on block-ELL, one kernel body per engine (paper §5.2).

TPU adaptation of the DASP-vs-cuSPARSE comparison (DESIGN.md §2.4): warp
MMA-fragment packing has no TPU analogue, so both engines consume the
*same* TPU-native layout -- block-ELL with scalar-prefetched block-column
indices (the idiomatic Pallas sparse pattern) -- and differ only in the
per-block compute:

  vector engine: broadcast-multiply + lane reduction    (cuSPARSE role)
  matrix engine: ``dot((bm,bn),(bn,))`` matvec on the MXU (DASP role)

The MXU path drives the systolic array with a matvec, i.e. 1/128 of its
columns -- the TPU version of the paper's 1/8-utilization observation.

Grid: (block_rows, max_blocks); x blocks are fetched by the prefetched
block-column id, and the output block accumulates across the second grid
axis (revisited output block, initialized at j == 0).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.dispatch import use_interpreter
from .ref import BlockEll


def _spmv_vpu_kernel(cols_ref, blocks_ref, x_ref, y_ref):
    del cols_ref  # consumed by the index maps
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    a = blocks_ref[0, 0]          # (bm, bn)
    xb = x_ref[0]                 # (1, bn)
    y_ref[0] += jnp.sum(a * xb, axis=1, keepdims=True).astype(y_ref.dtype)


def _spmv_mxu_kernel(cols_ref, blocks_ref, x_ref, y_ref):
    del cols_ref
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    a = blocks_ref[0, 0]          # (bm, bn)
    xb = x_ref[0]                 # (1, bn)
    # matvec on the systolic array: (bm,bn) . (1,bn)^T -> (bm,1)
    y_ref[0] += jax.lax.dot_general(
        a, xb, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("engine", "interpret"))
def bell_spmv(blocks: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray,
              *, engine: str = "vector",
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """y = A x for A in block-ELL; returns (n_block_rows, bm).

    ``x`` is viewed as (n/bn, 1, bn) and ``y`` as (n_block_rows, bm, 1)
    so every block's last two dimensions equal the array's -- the TPU
    block-shape rule -- whatever bm is.
    """
    nbr, mb, bm, bn = blocks.shape
    assert x.shape[0] % bn == 0
    x3 = x.reshape(-1, 1, bn)
    kernel = _spmv_vpu_kernel if engine == "vector" else _spmv_mxu_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nbr, mb),
        in_specs=[
            pl.BlockSpec((1, 1, bm, bn), lambda i, j, cols: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, j, cols: (cols[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, 1), lambda i, j, cols: (i, 0, 0)),
    )
    y = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nbr, bm, 1), x.dtype),
        interpret=use_interpreter(interpret),
    )(cols, blocks, x3)
    return y.reshape(nbr, bm)


def bell_spmv_bell(bell: BlockEll, x: jnp.ndarray, *, engine: str = "vector",
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    y = bell_spmv(bell.blocks, bell.cols, x, engine=engine,
                  interpret=interpret)
    return y.reshape(-1)[:bell.shape[0]]
