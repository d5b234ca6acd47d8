"""Pallas TPU kernel bodies for AXPY, one per engine.

AXPY (``y = a*x + y``) sits at the same roofline position as Triad
(I = 2/(3D)): two loads, one store, one FMA per element.

Matrix engine: ``Y' = X (aI) + Y I`` -- the identity-matmul trick again,
burning systolic-array cycles on what the VPU does in one FMA.  Per the
paper's Eq. 23 ceiling this cannot help, which is the point.

All padding/tiling comes from the shared dispatch-layer wrapper.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ...core.dispatch import elementwise_call, mxu_dot


def _axpy_vpu_kernel(a_ref, x_ref, y_ref, o_ref):
    o_ref[...] = (a_ref[0, 0] * x_ref[...] + y_ref[...]).astype(o_ref.dtype)


def _axpy_mxu_kernel(a_ref, x_ref, y_ref, o_ref):
    bn = x_ref.shape[-1]
    eye = jnp.eye(bn, dtype=x_ref.dtype)
    ai = (a_ref[0, 0] * eye).astype(x_ref.dtype)
    o_ref[...] = (
        mxu_dot(x_ref[...], ai) + mxu_dot(y_ref[...], eye)
    ).astype(o_ref.dtype)


def axpy_vector(a, x: jnp.ndarray, y: jnp.ndarray, *,
                interpret: Optional[bool] = None, block_rows: int = None,
                lanes: int = None) -> jnp.ndarray:
    return elementwise_call(_axpy_vpu_kernel, (x, y), (a,),
                            interpret=interpret, block_rows=block_rows,
                            lanes=lanes)


def axpy_matrix(a, x: jnp.ndarray, y: jnp.ndarray, *,
                interpret: Optional[bool] = None, block_rows: int = None,
                lanes: int = None) -> jnp.ndarray:
    return elementwise_call(_axpy_mxu_kernel, (x, y), (a,),
                            interpret=interpret, block_rows=block_rows,
                            lanes=lanes)
