"""AXPY through the program's registered ``axpy`` op."""
import jax

OP = "axpy"


def make(key, entry, dtype):
    kx, ky = jax.random.split(key)
    n = entry["n"]
    return {"x": jax.random.normal(kx, (n,), dtype),
            "y": jax.random.normal(ky, (n,), dtype)}


def scalar(rng):
    return float(rng.uniform(0.5, 2.0))


def run(op, x, entry, engine, a):
    return op(a, x["x"], x["y"], engine=engine)
