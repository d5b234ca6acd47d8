"""Model FLOP/s utilisation of the window, in %: the FLOPs that the
prefills and decode steps of the window's batches need (counted from
the configuration's sizes; the head at the last prompt position only)
over the window times the chip's peak."""


def read(ctx):
    window = ctx["window"]
    return 100.0 * ctx["work"]["flops"] / (window.dur / 1e9
                                           * ctx["peak"]["peak_flops"])
