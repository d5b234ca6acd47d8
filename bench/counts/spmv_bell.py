"""Block-ELL SpMV y = A x with ``blocks_per_row`` dense (bm, bn) blocks
per block-row: W = 2 nnzb bm bn, Q = nnzb (bm bn D + 4) + (m + n) D
(each block, its 4-byte column index, x and y once)."""


def count(entry: dict, dsize: int):
    m, bm, bn = entry["rows"], entry["bm"], entry["bn"]
    n = entry["block_cols"] * bn
    nnzb = (m // bm) * entry["blocks_per_row"]
    flops = 2.0 * nnzb * bm * bn
    return flops, float(nnzb * (bm * bn * dsize + 4) + (m + n) * dsize)
