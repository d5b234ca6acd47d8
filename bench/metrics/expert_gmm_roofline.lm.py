"""Share of the roofline the held-expert grouped product reaches, in %.

Over the ``batch`` spans of the traced window: the least time of every
expert-kernel call (the larger of its bytes over the HBM bandwidth and
its FLOPs over the peak: the held experts' bfloat16 weights once and
each token-slot's rows in and out, 6 d f FLOPs a slot, the slots from
the program's load counters), summed, over the device time of the
kernel's Pallas operations (``expert_gmm``).  The decode steps of a
batch are summed per layer: each of them is bound by its bytes.  A
program without the kernel or the counters reads nothing.
"""

KERNEL = "expert_gmm"


def read(ctx):
    red, window, peak = ctx["reduced"], ctx["window"], ctx["peak"]
    calls = ctx["work"].get("expert_gmm")
    ops = [o for o in red.ops_in(window, pallas=True, span_prefix="batch")
           if o.op.startswith(KERNEL)]
    if not calls or not ops:
        return None
    least = sum(max(nbytes / peak["hbm_bytes_per_s"],
                    flops / peak["peak_flops"]) for flops, nbytes in calls)
    return 100.0 * least / (sum(o.dur for o in ops) / 1e9)
