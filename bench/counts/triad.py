"""STREAM Triad a = b + q*c: two loads, one store, a multiply-add per
element (W = 2n, Q = 3nD)."""


def count(entry: dict, dsize: int):
    n = entry["n"]
    return 2.0 * n, 3.0 * n * dsize
