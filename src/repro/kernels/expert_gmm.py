"""Grouped matrix product over the experts a layer holds (Pallas).

``expert_gmm(lhs, rhs, tile_expert, tiles, tm)`` computes, for every row
tile ``i < tiles`` of ``lhs``, ``lhs[i*tm:(i+1)*tm] @ rhs[tile_expert[i]]``.
The rows are token-slots sorted by expert, each expert's run padded to a
whole number of ``tm``-row tiles (``repro.models.moe`` lays them out), so
a tile belongs to one expert.  The grid is ``(tiles, N / tn, K / tk)``
with ``tiles`` read at run time: only the tiles the routing filled run,
and each expert's weights are read once per tile of its rows.  Rows past
``tiles * tm`` are not written.

The kernel is ``expert_gmm`` in a profiler trace.  Its gradient runs the
same kernel on the transposed weights for ``lhs`` and one product per
tile, summed by expert, for ``rhs``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.dispatch import use_interpreter

KERNEL_NAME = "expert_gmm"


def _kernel(tile_expert_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tile(dim: int) -> int:
    """A 512 block where it divides ``dim``, else the whole dimension."""
    return 512 if dim % 512 == 0 else dim


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _gmm(lhs, rhs, tile_expert, tiles, *, tm: int,
         interpret: Optional[bool] = None):
    m, k = lhs.shape
    n = rhs.shape[2]
    tk, tn = _tile(k), _tile(n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles, n // tn, k // tk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kk, te: (i, kk)),
            pl.BlockSpec((None, tk, tn), lambda i, j, kk, te: (te[i], kk, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, te: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel, grid_spec=grid_spec, name=KERNEL_NAME,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=use_interpreter(interpret),
    )(tile_expert, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def expert_gmm(lhs, rhs, tile_expert, tiles, tm: int,
               interpret: Optional[bool] = None):
    """lhs (M, K), rhs (E, K, N), tile_expert int32 (M / tm,), tiles int32
    scalar -> (M, N) in ``lhs.dtype``, accumulated in float32; rows from
    ``tiles * tm`` on are left unwritten."""
    return _gmm(lhs, rhs, tile_expert, tiles, tm=tm, interpret=interpret)


def _fwd(lhs, rhs, tile_expert, tiles, tm, interpret):
    out = _gmm(lhs, rhs, tile_expert, tiles, tm=tm, interpret=interpret)
    return out, (lhs, rhs, tile_expert, tiles)


def _bwd(tm, interpret, res, g):
    lhs, rhs, tile_expert, tiles = res
    filled = (jnp.arange(lhs.shape[0]) // tm < tiles)[:, None]
    g = jnp.where(filled, g, 0).astype(lhs.dtype)
    d_lhs = _gmm(g, jnp.swapaxes(rhs, 1, 2), tile_expert, tiles, tm=tm,
                 interpret=interpret)
    d_lhs = jnp.where(filled, d_lhs, 0)
    x = jnp.where(filled, lhs, 0).reshape(-1, tm, lhs.shape[1])
    per_tile = jnp.einsum("tmk,tmn->tkn", x, g.reshape(-1, tm, g.shape[1]),
                          preferred_element_type=jnp.float32)
    seg = jnp.where(jnp.arange(per_tile.shape[0]) < tiles, tile_expert,
                    rhs.shape[0])
    d_rhs = jax.ops.segment_sum(per_tile, seg, num_segments=rhs.shape[0])
    return d_lhs, d_rhs.astype(rhs.dtype), None, None


expert_gmm.defvjp(_fwd, _bwd)
