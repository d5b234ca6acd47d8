"""Pallas flash-decode kernel: single-token GQA attention over a KV cache.

This is the LM-serving op the paper's framework classifies: a GEMV-shaped,
memory-bound kernel (I ~ 1 flop/byte vs machine balance 240).  Per the
advisor there is nothing the MXU can do here -- the win is *streaming*:
the cache is read exactly once, in (block_s x Dh) VMEM tiles, with an
online-softmax accumulator carried across the KV-block grid axis.

Grid: (B * KH, S / block_s).  Each program handles one (batch, kv-head)
pair's G query rows against one KV block; accumulator state lives in the
output ref (revisited across the second grid axis, initialized at j == 0)
plus small VMEM scratch for (m, l).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.dispatch import mxu_dot, use_interpreter

NEG_INF = -1e30


def _flash_decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, block_s: int,
                         engine: str):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # (G, Dh)
    k = k_ref[0].astype(jnp.float32)          # (block_s, Dh)
    v = v_ref[0].astype(jnp.float32)          # (block_s, Dh)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))

    if engine == "matrix":
        s = mxu_dot(q, k.T) * scale
    else:  # vector engine: broadcast-multiply + lane reduction, no MXU
        s = jnp.sum(q[:, None, :] * k[None, :, :], axis=-1) * scale
    pos = j * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < kvlen_ref[0], s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]   # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                    # (G, block_s)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    if engine == "matrix":
        pv = mxu_dot(p, v)
    else:
        pv = jnp.sum(p[:, :, None] * v[None, :, :], axis=1)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "engine", "interpret"))
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 kv_len, *, block_s: int = 512, engine: str = "matrix",
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, KH, G, Dh); k,v: (B, S, KH, Dh); kv_len scalar int32.

    ``engine`` picks the per-block compute: 'matrix' drives the MXU with
    (G, Dh) x (Dh, block_s) dots; 'vector' does the same contraction as
    broadcast-multiply + reductions on the VPU.  Either way the cache is
    streamed exactly once -- the only lever the paper leaves.

    Returns (B, KH, G, Dh)."""
    b, kh, g, dh = q.shape
    s = k.shape[1]
    assert s % block_s == 0, (s, block_s)
    qf = q.reshape(b * kh, g, dh)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * kh, s, dh)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * kh, s, dh)
    kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (1,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * kh, s // block_s),
        in_specs=[
            pl.BlockSpec((1, g, dh), lambda i, j, kvl: (i, 0, 0)),
            pl.BlockSpec((1, block_s, dh), lambda i, j, kvl: (i, j, 0)),
            pl.BlockSpec((1, block_s, dh), lambda i, j, kvl: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, dh), lambda i, j, kvl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_flash_decode_kernel, block_s=block_s,
                          engine=engine),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kh, g, dh), q.dtype),
        interpret=use_interpreter(interpret),
    )(kvl, qf, kf, vf)
    return out.reshape(b, kh, g, dh)
