"""Share of the roofline the Pallas kernel bodies reach, in %.

Over every ``call:<kernel>`` span of the traced window: the least time
each call could take (the larger of its counted bytes over the HBM
bandwidth and its counted FLOPs over the peak) summed, over the device
time of the Pallas operations those calls ran.  A call whose kernel ran
no Pallas operation adds nothing.
"""


def read(ctx):
    red, window, peak = ctx["reduced"], ctx["window"], ctx["peak"]
    least = kernel = 0.0
    for span, (flops, nbytes) in ctx["work"]["calls"].items():
        ops = [o for o in red.ops_in(window, pallas=True) if o.span == span]
        if not ops:
            continue
        calls = sum(1 for s in red.spans if s.name == span
                    and window.start <= s.start <= window.end)
        least += calls * max(nbytes / peak["hbm_bytes_per_s"],
                             flops / peak["peak_flops"])
        kernel += sum(o.dur for o in ops) / 1e9
    return 100.0 * least / kernel if kernel > 0 else None
