"""STREAM Triad through the program's registered ``triad`` op."""
import jax

OP = "triad"


def make(key, entry, dtype):
    kb, kc = jax.random.split(key)
    n = entry["n"]
    return {"b": jax.random.normal(kb, (n,), dtype),
            "c": jax.random.normal(kc, (n,), dtype)}


def scalar(rng):
    return float(rng.uniform(0.5, 2.0))


def run(op, x, entry, engine, q):
    return op(x["b"], x["c"], q, engine=engine)
