"""Table-3 stencils through the program's registered ``stencil`` op.

The benchmark states each stencil's taps (``taps``) and hands the
program a ``StencilSpec`` built from them.
"""
import itertools

import jax

OP = "stencil"


def taps(entry):
    """(offsets, weights) of a star (``wing``, ``center``) or a
    separable box (``w1d``) stencil, center first for a star."""
    nd, r = len(entry["grid"]), entry["radius"]
    if entry["shape"] == "star":
        offsets, weights = [(0,) * nd], [entry["center"]]
        for ax in range(nd):
            for d in range(1, r + 1):
                for sign in (-1, 1):
                    off = [0] * nd
                    off[ax] = sign * d
                    offsets.append(tuple(off))
                    weights.append(entry["wing"][d - 1])
        return tuple(offsets), tuple(weights)
    w1d = entry["w1d"]
    offsets, weights = [], []
    for off in itertools.product(range(-r, r + 1), repeat=nd):
        w = 1.0
        for d in off:
            w *= w1d[d + r]
        offsets.append(off)
        weights.append(w)
    return tuple(offsets), tuple(weights)


def make(key, entry, dtype):
    return {"u": jax.random.normal(key, tuple(entry["grid"]), dtype)}


def scalar(rng):
    return None


def program_spec(entry):
    from repro.kernels.stencil.defs import StencilSpec

    nd, r = len(entry["grid"]), entry["radius"]
    offsets, weights = taps(entry)
    if entry["shape"] == "star":
        axis = tuple(entry["wing"][abs(d) - 1] if d else 0.0
                     for d in range(-r, r + 1))
        axis_weights, center = (axis,) * nd, entry["center"]
    else:
        axis_weights, center = (tuple(entry["w1d"]),) * nd, 0.0
    return StencilSpec(entry["name"], nd, r, entry["shape"], offsets,
                       weights, axis_weights, center)


def run(op, x, entry, engine, _):
    return op(x["u"], program_spec(entry), steps=entry["steps"],
              engine=engine)
