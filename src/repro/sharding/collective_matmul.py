"""Collective (latency-hiding) matmuls: overlap communication with compute.

The paper's §4.1 lesson -- fully-overlapped communication is free --
applied to tensor-parallel matmuls.  Two primitives:

``weight_gathered_matmul``: y = x @ w with w row-sharded over the TP axis
(the FSDP/ZeRO-3 layer shape).  Rather than ``x @ all_gather(w)`` (a
standalone collective the MXU waits on), weight shards rotate around a
``ppermute`` ring; every hop's dot is independent of the in-flight
transfer, so XLA's scheduler hides the ring behind the p partial matmuls.

``rowparallel_matmul``: y = x @ w with the *contraction* dim sharded
(Megatron row-parallel).  Partial products ring-accumulate chunk-by-chunk
(reduce-scatter schedule) instead of a monolithic all-reduce, then the
result chunks are exchanged -- each hop overlaps the next chunk's dot.

Numerics are validated against the unsharded reference in
tests/test_distributed.py on 8 host devices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["rowparallel_matmul", "weight_gathered_matmul"]


def weight_gathered_matmul(x: jnp.ndarray, w: jnp.ndarray, mesh,
                           axis: str = "model") -> jnp.ndarray:
    """y = x @ w; x replicated over `axis`, w sharded on dim 0.

    Returns y replicated.  Equivalent to ``x @ all_gather(w)`` with the
    gather pipelined against p partial matmuls.
    """
    p = mesh.shape[axis]
    assert w.shape[0] % p == 0, (w.shape, p)
    kloc = w.shape[0] // p

    def body(xl, wl):
        idx = jax.lax.axis_index(axis)

        def cols(owner):
            start = owner * kloc
            return jax.lax.dynamic_slice_in_dim(xl, start, kloc, axis=-1)

        acc = cols(idx) @ wl                    # hop 0: local pairing
        wf = wl
        fwd = [(j, (j + 1) % p) for j in range(p)]
        for s in range(1, p):
            wf = jax.lax.ppermute(wf, axis, fwd)   # now rows of (idx - s)
            owner = (idx - s) % p
            acc = acc + cols(owner) @ wf           # overlaps next ppermute
        return acc

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P(axis, None)),
                         out_specs=P(), check_vma=False)(x, w)


def rowparallel_matmul(x: jnp.ndarray, w: jnp.ndarray, mesh,
                       axis: str = "model") -> jnp.ndarray:
    """y = x @ w; x sharded on its last (contraction) dim, w on dim 0.

    Implemented as partial-product + ring accumulation (the explicit
    reduce-then-broadcast schedule XLA uses for psum, written out so each
    hop can overlap neighbouring compute).  Returns y replicated.
    """
    def body(xl, wl):
        part = xl.reshape(-1, xl.shape[-1]) @ wl
        out = jax.lax.psum(part, axis)
        return out.reshape(*xl.shape[:-1], wl.shape[-1])

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(*([None] * (x.ndim - 1)), axis),
                                   P(axis, None)),
                         out_specs=P(), check_vma=False)(x, w)
