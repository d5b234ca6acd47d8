"""Share of the device's busy time spent outside Pallas kernels, in %:
the dispatch wrapper's pads, reshapes, relayout copies and slices.
Over the traced window, 1 - (Pallas busy time / all busy time)."""


def read(ctx):
    red, window = ctx["reduced"], ctx["window"]
    busy = red.busy_ns(window)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - red.busy_ns(window, pallas=True) / busy)
