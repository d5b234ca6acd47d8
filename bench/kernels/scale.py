"""STREAM Scale through the program's registered ``scale`` op."""
import jax

OP = "scale"


def make(key, entry, dtype):
    return {"b": jax.random.normal(key, (entry["n"],), dtype)}


def scalar(rng):
    return float(rng.uniform(0.5, 2.0))


def run(op, x, entry, engine, q):
    return op(x["b"], q, engine=engine)
