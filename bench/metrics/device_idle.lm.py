"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of operation intervals / window)."""


def read(ctx):
    window = ctx["window"]
    return 100.0 * (1.0 - ctx["reduced"].busy_ns(window) / window.dur)
