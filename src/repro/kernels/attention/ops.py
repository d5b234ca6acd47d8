"""Public decode-attention op, registered as an ``EngineOp``.

Single-token GQA attention is GEMV-shaped: I ~= 2*G/D flop/byte over the
KV cache, memory-bound by ~100x on v5e at production sizes.  The advisor
(and the paper) say the only lever is streaming the cache once, which
both engine variants do -- they differ only in whether the per-block
contraction drives the MXU or the VPU.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ...core.intensity import KernelTraits
from ..registry import EngineOp, register
from .flash_decode import flash_decode
from .ref import decode_attention_ref

__all__ = ["ATTENTION_OP", "decode_attention"]

#: Static KV-block length, in cache positions a block (each holds every
#: KV head of one batch row).  The kernel lowers it, or a requested
#: ``block_s``, to S and to its VMEM cap for the shape and dtype
#: (``flash_decode.block_len``).
DEFAULT_BLOCK_S = 512


def _traits(q, k, v, kv_len, *, block_s=None):
    del v, kv_len, block_s
    b, kh, g, dh = q.shape
    s = k.shape[1]
    work = 4.0 * b * kh * g * s * dh
    traffic = 2.0 * b * s * kh * dh * k.dtype.itemsize
    return KernelTraits("flash_decode", work, traffic)


def _engine_fn(engine: str):
    def call(q, k, v, kv_len, *, block_s=None,
             interpret: Optional[bool] = None):
        return flash_decode(q, k, v, kv_len,
                            block_s=block_s or DEFAULT_BLOCK_S,
                            engine=engine, interpret=interpret)
    return call


def _reference(q, k, v, kv_len, *, block_s=None):
    del block_s
    return decode_attention_ref(q, k, v, kv_len)


def _make_inputs(rng: np.random.Generator, size: int, dtype: str = "float32"):
    """size = KV-cache length; a small GQA decode step against it."""
    b, kh, g, dh = 1, 2, 4, 64
    q = jnp.asarray(rng.standard_normal((b, kh, g, dh)), dtype)
    k = jnp.asarray(rng.standard_normal((b, size, kh, dh)), dtype)
    v = jnp.asarray(rng.standard_normal((b, size, kh, dh)), dtype)
    return (q, k, v, size - size // 8), {}


ATTENTION_OP = register(EngineOp(
    name="attention",
    traits=_traits,
    engines={"vector": _engine_fn("vector"), "matrix": _engine_fn("matrix")},
    reference=_reference,
    make_inputs=_make_inputs,
    bench_sizes=(256, 512),
    dtypes=("float32", "bfloat16"),
    test_size=256,
    doc="flash-decode GQA attention over a KV cache; I ~= 2G/D",
    # mesh split: KV heads are independent (each attends to its own
    # cache slice), so head-sharding is exact with no exchange
    shard_kind="head",
))


def decode_attention(q, k, v, kv_len, *, engine: str = "auto",
                     block_s: int = None, interpret: Optional[bool] = None):
    """Single-token GQA attention against a KV cache.

    Intensity ~= (4 flops per cache element) / (2 bytes per element) --
    memory-bound by ~100x on v5e; 'auto' therefore routes to the vector
    variant, with the MXU formulation one flag away (and, per the paper,
    no faster).  ``block_s=None`` means the static default of 512; the
    kernel lowers it to S and to its VMEM cap.
    """
    return ATTENTION_OP(q, k, v, kv_len, engine=engine, block_s=block_s,
                        interpret=interpret)
