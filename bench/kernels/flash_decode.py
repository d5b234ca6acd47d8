"""Decode attention through the program's registered ``attention`` op."""
import jax

OP = "attention"


def make(key, entry, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    b, kh, g, dh, s = (entry[k] for k in ("b", "kh", "g", "dh", "s"))
    return {"q": jax.random.normal(kq, (b, kh, g, dh), dtype),
            "k": jax.random.normal(kk, (b, s, kh, dh), dtype),
            "v": jax.random.normal(kv, (b, s, kh, dh), dtype)}


def scalar(rng):
    return None


def run(op, x, entry, engine, _):
    return op(x["q"], x["k"], x["v"], entry["kv_len"], engine=engine)
