"""Pallas flash-decode kernel: single-token GQA attention over a KV cache.

This is the LM-serving op the paper's framework classifies: a GEMV-shaped,
memory-bound kernel (I ~ 1 flop/byte vs machine balance 240).  Per the
advisor there is nothing the MXU can do here -- the win is *streaming*:
the live cache is read exactly once, where it lies, with an
online-softmax accumulator carried across the KV-block grid axis.

Grid: (B, cdiv(S, block_s)).  K and V stay in the cache's own
(B, S, KH, Dh) layout: each program streams one batch row's
(1, block_s, KH, Dh) block -- every KV head of that row over ``block_s``
positions -- so KH and Dh are whole dimensions of the block (the TPU
tiling rule holds for any KH) and no relayout of the cache precedes the
call.  q and the output move as (KH, G, Dh) blocks of their batch row.
The (m, l, acc) accumulators live in float32 VMEM scratch, initialised
at j == 0 and written to the output at the last block.

Only the live cache is streamed: the prefetched ``kv_len`` clamps the
K/V block index to the last live block, so the blocks past it repeat
that index (the pipeline skips a fetch whose index repeats) and a
``pl.when`` skips their compute.  Positions ``>= kv_len`` of the last
live block are masked, in the scores and in V, since a block that runs
past S reads padding.

``block_s`` is capped so the double-buffered K and V blocks fit
``VMEM_BLOCK_BUDGET`` at the given KH, Dh and itemsize; a requested or
tuned ``block_s`` may lower the length, never raise it past that cap.
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

#: VMEM bytes the double-buffered K and V blocks may take: half of the
#: 16 MiB a kernel is given by default, leaving the rest to the
#: accumulators and the compute's temporaries.
VMEM_BLOCK_BUDGET = 8 * 2**20

#: Positions the vector engine takes at a time (the loop over a block's
#: chunks is unrolled, so the scheduler overlaps one chunk's products
#: with the last one's softmax update).
CHUNK = 8


def _padded(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def block_len(block_s: int, s: int, kh: int, dh: int,
              itemsize: int) -> int:
    """Positions a K/V block holds: ``block_s`` lowered to S and to the
    VMEM cap, a multiple of CHUNK where S allows.

    The cap: two buffers each of K and V blocks within
    VMEM_BLOCK_BUDGET, every (KH, Dh) slab padded to the (sublane, lane)
    tile of the dtype."""
    sublanes = 8 * max(1, 4 // itemsize)
    slab = _padded(kh, sublanes) * _padded(dh, 128) * itemsize
    cap = max(CHUNK, VMEM_BLOCK_BUDGET // (4 * slab))
    bs = min(int(block_s), cap, s)
    return bs - bs % CHUNK if bs >= CHUNK else bs


def _vector_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *, start,
                  kv_len, block_s, masked):
    """Broadcast-multiply and reductions on the VPU, CHUNK positions at a
    time.  Rows are (G, KH, ...): per query row g the scores are
    (CHUNK, KH, 1) and the accumulator (KH, Dh), whole (8, 128) tiles."""
    rows = range(q_ref.shape[0])
    qs = [q_ref[g] for g in rows]                         # (KH, Dh) each
    carry = [(m_ref[g], l_ref[g], acc_ref[g]) for g in rows]
    for off in range(0, block_s, CHUNK):
        n = min(CHUNK, block_s - off)
        k = k_ref[0, off:off + n].astype(jnp.float32)     # (n, KH, Dh)
        v = v_ref[0, off:off + n].astype(jnp.float32)
        if masked:
            pos = start + off + jax.lax.broadcasted_iota(
                jnp.int32, (n, 1, 1), 0)
            live = pos < kv_len
            v = jnp.where(live, v, 0.0)
        for g, (q, (m, l, acc)) in enumerate(zip(qs, carry)):
            s = jnp.sum(q[None] * k, axis=-1, keepdims=True)
            if masked:
                s = jnp.where(live, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=0))         # (KH, 1)
            p = jnp.exp(s - m_new[None])                  # (n, KH, 1)
            corr = jnp.exp(m - m_new)
            carry[g] = (m_new, l * corr + p.sum(axis=0),
                        acc * corr + jnp.sum(p * v, axis=0))
    for g, (m, l, acc) in enumerate(carry):
        m_ref[g], l_ref[g], acc_ref[g] = m, l, acc


def _matrix_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *, start,
                  kv_len, block_s, masked):
    """MXU dots for each KV head: (G, Dh) x (Dh, block_s) scores, then
    (G, block_s) x (block_s, Dh) into the accumulator.  Rows are
    (KH, G, ...).  A 32-bit block whose KH fills whole sublane tiles
    gives each head's rows by one strided load; other blocks give eight
    heads (or all KH) at a time, since a packed dtype has no strided
    load and a dynamic offset into its KH axis must be tile-aligned."""
    kh, _, dh = q_ref.shape
    if k_ref.dtype.itemsize == 4 and kh % 8 == 0:
        group = 1
        k2 = k_ref.reshape(block_s * kh, dh)   # head h: rows h, h + KH, ..
        v2 = v_ref.reshape(block_s * kh, dh)

        def heads(h0):
            rows = pl.ds(h0, block_s, stride=kh)
            return [(k2[rows, :], v2[rows, :])]
    else:
        group = 8 if kh % 8 == 0 else kh

        def heads(h0):
            ks = k_ref[0, :, pl.ds(h0, group), :].astype(jnp.float32)
            vs = v_ref[0, :, pl.ds(h0, group), :].astype(jnp.float32)
            return [(ks[:, u, :], vs[:, u, :]) for u in range(group)]
    if masked:
        def live(shape, axis):
            pos = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            return start + pos < kv_len

    def step(i, _):
        h0 = pl.multiple_of(i * group, group)
        for u, (k, v) in enumerate(heads(h0)):            # (block_s, Dh)
            h = h0 + u
            s = mxu_dot(q_ref[h], k.T)                    # (G, block_s)
            if masked:
                s = jnp.where(live((1, block_s), 1), s, NEG_INF)
                v = jnp.where(live((block_s, 1), 0), v, 0.0)
            m_prev, l_prev = m_ref[h], l_ref[h]           # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_prev * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + mxu_dot(p, v)
            m_ref[h] = m_new
        return _

    jax.lax.fori_loop(0, kh // group, step, 0)


def _flash_decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, io_ref,
                         m_ref, l_ref, acc_ref, *q_rows, block_s: int):
    """``io_ref`` stages the scaled q and the output in float32 as
    (KH, G, Dh), the matrix engine's rows.  The vector engine keeps its
    rows as (G, KH, ...), q among them (``q_rows``), and copies across."""
    j = pl.program_id(1)
    kv_len = kvlen_ref[0]
    start = j * block_s
    vector = bool(q_rows)
    g_rows = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        scale = 1.0 / jnp.sqrt(jnp.asarray(q_ref.shape[-1], jnp.float32))
        io_ref[...] = q_ref[...].astype(jnp.float32) * scale
        if vector:
            for g in range(g_rows):
                q_rows[0][g] = io_ref[:, g, :]
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    block = _vector_block if vector else _matrix_block
    refs = (*(q_rows or (io_ref,)), k_ref, v_ref, m_ref, l_ref, acc_ref)
    kw = dict(start=start, kv_len=kv_len, block_s=block_s)

    @pl.when(start + block_s <= kv_len)
    def _live_block():
        block(*refs, masked=False, **kw)

    @pl.when(jnp.logical_and(start < kv_len, kv_len < start + block_s))
    def _last_live_block():
        block(*refs, masked=True, **kw)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        if vector:
            for g in range(g_rows):
                io_ref[:, g, :] = out[g]
            out = io_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "engine", "interpret"))
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 kv_len, *, block_s: int = 512, engine: str = "matrix",
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, KH, G, Dh); k,v: (B, S, KH, Dh); kv_len scalar int32.

    ``engine`` picks the per-block compute: 'matrix' drives the MXU with
    a (G, Dh) x (Dh, block_s) dot for each KV head; 'vector' does the
    same contraction as broadcast-multiply + reductions on the VPU.
    Either way the live cache is streamed exactly once, in its own
    layout -- the only lever the paper leaves.  ``block_s`` (positions
    a block) is lowered by ``block_len`` to S and to the VMEM cap of
    this KH, Dh and dtype.

    Returns (B, KH, G, Dh)."""
    b, kh, g, dh = q.shape
    s = k.shape[1]
    bs = block_len(block_s, s, kh, dh, k.dtype.itemsize)
    kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (1,))

    def kv_block(i, j, kvl):
        last = jnp.maximum(kvl[0] - 1, 0) // bs
        return i, jnp.minimum(j, last), 0, 0

    vector = engine != "matrix"
    rows = (g, kh) if vector else (kh, g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, pl.cdiv(s, bs)),
        in_specs=[
            pl.BlockSpec((None, kh, g, dh), lambda i, j, kvl: (i, 0, 0, 0)),
            pl.BlockSpec((1, bs, kh, dh), kv_block),
            pl.BlockSpec((1, bs, kh, dh), kv_block),
        ],
        out_specs=pl.BlockSpec((None, kh, g, dh),
                               lambda i, j, kvl: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kh, g, dh), jnp.float32),
            pltpu.VMEM((*rows, 1), jnp.float32),
            pltpu.VMEM((*rows, 1), jnp.float32),
            pltpu.VMEM((*rows, dh), jnp.float32),
        ] + [pltpu.VMEM((*rows, dh), jnp.float32)] * vector,
    )
    return pl.pallas_call(
        functools.partial(_flash_decode_kernel, block_s=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dh), q.dtype),
        interpret=use_interpreter(interpret),
    )(kvl, q, k, v)
