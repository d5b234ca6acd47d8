"""Share of the roofline the flash-decode kernel reaches inside the
decode steps, in %.

Over the ``batch`` spans of the traced window: the least time of every
step's attention (the live bfloat16 K/V, q and the output over the HBM
bandwidth, or its FLOPs over the peak, whichever is larger) summed, over
the device time of the Pallas operations those batches ran.  Flash-decode
is the only Pallas kernel of the decode path.
"""


def read(ctx):
    red, window, peak = ctx["reduced"], ctx["window"], ctx["peak"]
    ops = red.ops_in(window, pallas=True, span_prefix="batch")
    if not ops:
        return None
    batches = sum(1 for s in red.spans if s.name == "batch"
                  and window.start <= s.start <= window.end)
    flops, nbytes = ctx["work"]["flash_decode"]
    least = batches * max(nbytes / peak["hbm_bytes_per_s"],
                          flops / peak["peak_flops"])
    return 100.0 * least / (sum(o.dur for o in ops) / 1e9)
