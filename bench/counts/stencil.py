"""|S|-point stencil, t fused steps, over N grid points (paper Eq. 12-13):
W = 2 t |S| N, Q = 2 D N (one load and one store per point)."""
import math


def points(entry: dict) -> int:
    nd, r = len(entry["grid"]), entry["radius"]
    if entry["shape"] == "star":
        return 1 + 2 * r * nd
    return (2 * r + 1) ** nd


def count(entry: dict, dsize: int):
    n = math.prod(entry["grid"])
    return 2.0 * entry["steps"] * points(entry) * n, 2.0 * dsize * n
