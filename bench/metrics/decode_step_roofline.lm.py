"""Share of the HBM roofline the decode steps reach, in %.

The counted bytes of the window's decode steps (every weight a step
reads, the held experts' whole, and the live latent cache) over the HBM
bandwidth, over the device time of the ``jit_decode_step`` program's
operations in the window's ``batch`` spans (the union of their
intervals, clipped to the window: a loop and the operations inside it
count once).  A driver that counts no step bytes reads nothing.
"""
from collections import defaultdict

from bench.trace_reduce import _union_length


def read(ctx):
    red, window, peak = ctx["reduced"], ctx["window"], ctx["peak"]
    nbytes = ctx["work"].get("decode_step_bytes")
    per_dev = defaultdict(list)
    for o in red.ops:
        if o.module.startswith("jit_decode_step") and \
                o.span.startswith("batch"):
            start, end = max(o.start, window.start), min(o.end, window.end)
            if end > start:
                per_dev[o.device].append((start, end))
    if not nbytes or not per_dev:
        return None
    busy = sum(_union_length(iv) for iv in per_dev.values())
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / (busy / 1e9)
