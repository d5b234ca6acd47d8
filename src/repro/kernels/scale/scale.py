"""Pallas TPU kernel bodies for STREAM SCALE, one per engine (paper §5.1).

Vector engine (VPU): the natural elementwise kernel -- one load, one
multiply, one store per element.

Matrix engine (MXU): the paper's Fig.-5 formulation ``A = B (qI)`` --
each (bm, bn) tile of B is multiplied by the scaled identity ``q*I_bn``
with a real ``dot`` so the systolic array does the work.  Only 1/bn of
the MXU's lanes do useful work (the GPU paper wastes 1/8 on an 8x4 DMMA
tile; a 128x128 MXU wastes 1/128) -- which, per the theory, is *still*
irrelevant for this kernel because I = 1/(2D) << B.

Tiling, padding, and block-spec construction live in the shared
``repro.core.dispatch.elementwise_call`` wrapper; this module is only
the per-tile bodies plus their engine entry points.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ...core.dispatch import (ELEMENTWISE_BLOCK_ROWS, ELEMENTWISE_LANES,
                              elementwise_call, mxu_dot)

# retained names: the (rows, 1024)-wide layout both engines share
LANES = ELEMENTWISE_LANES
BLOCK_ROWS = ELEMENTWISE_BLOCK_ROWS


def _scale_vpu_kernel(q_ref, b_ref, o_ref):
    o_ref[...] = (q_ref[0, 0] * b_ref[...]).astype(o_ref.dtype)


def _scale_mxu_kernel(q_ref, b_ref, o_ref):
    bn = b_ref.shape[-1]
    eye = jnp.eye(bn, dtype=b_ref.dtype)
    qi = (q_ref[0, 0] * eye).astype(b_ref.dtype)  # q * I, built in VMEM
    o_ref[...] = mxu_dot(b_ref[...], qi).astype(o_ref.dtype)


def scale_vector(b: jnp.ndarray, q, *, interpret: Optional[bool] = None,
                 block_rows: int = None, lanes: int = None) -> jnp.ndarray:
    return elementwise_call(_scale_vpu_kernel, (b,), (q,),
                            interpret=interpret, block_rows=block_rows,
                            lanes=lanes)


def scale_matrix(b: jnp.ndarray, q, *, interpret: Optional[bool] = None,
                 block_rows: int = None, lanes: int = None) -> jnp.ndarray:
    return elementwise_call(_scale_mxu_kernel, (b,), (q,),
                            interpret=interpret, block_rows=block_rows,
                            lanes=lanes)
