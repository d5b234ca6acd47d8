"""Pallas TPU kernel bodies for STREAM Triad, one per engine.

Triad (``a = b + q*c``) is the canonical STREAM kernel with a fused
multiply-add: I = 2/(3D), still far below every machine balance in the
paper's Table 1, so the engines differ only in how they waste the MXU.

Matrix engine: the Fig.-5 identity trick extended to two terms,
``A = B I + C (qI)`` -- two systolic-array matmuls per tile, each using
1/bn of the MXU's lanes.  The theory says the extra flops are free
(memory-bound either way) and the measurement agrees.

All padding/tiling comes from the shared dispatch-layer wrapper.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ...core.dispatch import elementwise_call, mxu_dot


def _triad_vpu_kernel(q_ref, b_ref, c_ref, o_ref):
    o_ref[...] = (b_ref[...] + q_ref[0, 0] * c_ref[...]).astype(o_ref.dtype)


def _triad_mxu_kernel(q_ref, b_ref, c_ref, o_ref):
    bn = b_ref.shape[-1]
    eye = jnp.eye(bn, dtype=b_ref.dtype)
    qi = (q_ref[0, 0] * eye).astype(c_ref.dtype)
    o_ref[...] = (
        mxu_dot(b_ref[...], eye) + mxu_dot(c_ref[...], qi)
    ).astype(o_ref.dtype)


def triad_vector(b: jnp.ndarray, c: jnp.ndarray, q, *,
                 interpret: Optional[bool] = None, block_rows: int = None,
                 lanes: int = None) -> jnp.ndarray:
    return elementwise_call(_triad_vpu_kernel, (b, c), (q,),
                            interpret=interpret, block_rows=block_rows,
                            lanes=lanes)


def triad_matrix(b: jnp.ndarray, c: jnp.ndarray, q, *,
                 interpret: Optional[bool] = None, block_rows: int = None,
                 lanes: int = None) -> jnp.ndarray:
    return elementwise_call(_triad_mxu_kernel, (b, c), (q,),
                            interpret=interpret, block_rows=block_rows,
                            lanes=lanes)
