"""Single-token attention over a KV cache of ``kv_len`` live positions.

W = 4 B KH G kv_len Dh (scores and the weighted sum).  Q is what the
step must read and write: the live K and V, q and the output.
"""


def count(entry: dict, dsize: int):
    b, kh, g, dh = entry["b"], entry["kh"], entry["g"], entry["dh"]
    kv = entry["kv_len"]
    flops = 4.0 * b * kh * g * kv * dh
    return flops, 2.0 * b * kv * kh * dh * dsize + 2.0 * b * kh * g * dh * dsize
