"""STREAM Scale a = q*b: one multiply, one load and one store per
element (paper Eq. 2: W = n, Q = 2nD)."""


def count(entry: dict, dsize: int):
    n = entry["n"]
    return float(n), 2.0 * n * dsize
