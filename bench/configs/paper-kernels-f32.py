"""Plain references of the paper-kernels configuration.

Straightforward ``jax.numpy`` in float32 at HIGHEST matmul precision,
one function per kernel kind, each ``ref(x, entry, scalar)`` with the
inputs ``x`` the benchmark made.  Nothing here imports the program.
"""
import jax
import jax.numpy as jnp

from bench.kernels.stencil import taps


def scale(x, entry, q):
    return jnp.float32(q) * x["b"]


def triad(x, entry, q):
    return x["b"] + jnp.float32(q) * x["c"]


def axpy(x, entry, a):
    return jnp.float32(a) * x["x"] + x["y"]


def flash_decode(x, entry, _):
    q, k, v = x["q"], x["k"], x["v"]
    s = jnp.einsum("bhgd,bshd->bhgs", q, k,
                   precision="highest") / jnp.sqrt(jnp.float32(q.shape[-1]))
    live = jnp.arange(k.shape[1]) < entry["kv_len"]
    s = jnp.where(live, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", p, v, precision="highest")


def spmv_bell(x, entry, _):
    bn = entry["bn"]
    xg = x["x"].reshape(-1, bn)[x["cols"]]              # (nbr, mb, bn)
    y = jnp.einsum("ijab,ijb->ia", x["blocks"], xg, precision="highest")
    return y.reshape(-1)


def stencil(x, entry, _):
    """``steps`` applications with zero boundary conditions."""
    offsets, weights = taps(entry)
    r = entry["radius"]
    u = x["u"]
    for _ in range(entry["steps"]):
        up = jnp.pad(u, r)
        acc = jnp.zeros_like(u)
        for off, w in zip(offsets, weights):
            sl = tuple(slice(r + o, r + o + n) for o, n in zip(off, u.shape))
            acc = acc + jnp.float32(w) * up[sl]
        u = acc
    return u


REFERENCES = {"scale": scale, "triad": triad, "axpy": axpy,
              "flash_decode": flash_decode, "spmv_bell": spmv_bell,
              "stencil": stencil}
