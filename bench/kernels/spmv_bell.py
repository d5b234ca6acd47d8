"""Block-ELL SpMV through the program's registered ``spmv`` op.

The matrix is made on the device: each block-row holds
``blocks_per_row`` dense (bm, bn) blocks at distinct block columns drawn
uniformly from ``block_cols`` (a block density of
blocks_per_row / block_cols).
"""
import jax
import jax.numpy as jnp

OP = "spmv"


def make(key, entry, dtype):
    kb, kc, kx = jax.random.split(key, 3)
    nbr = entry["rows"] // entry["bm"]
    mb, bm, bn = entry["blocks_per_row"], entry["bm"], entry["bn"]
    order = jnp.argsort(jax.random.uniform(kc, (nbr, entry["block_cols"])),
                        axis=1)
    return {"blocks": jax.random.normal(kb, (nbr, mb, bm, bn), dtype),
            "cols": jnp.sort(order[:, :mb], axis=1).astype(jnp.int32),
            "x": jax.random.normal(kx, (entry["block_cols"] * bn,), dtype)}


def scalar(rng):
    return None


def run(op, x, entry, engine, _):
    from repro.kernels.spmv.ref import BlockEll

    shape = (entry["rows"], entry["block_cols"] * entry["bn"])
    return op(BlockEll(x["blocks"], x["cols"], shape), x["x"], engine=engine)
