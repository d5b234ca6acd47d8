"""Share of the traced window in which device 0 ran nothing while the
dispatch layer was on the host, in %: idle time inside the program's
``dispatch.route`` (engine resolution, tile lookup) and
``dispatch.launch`` (the wrapper's eager ops, the Pallas enqueue) spans,
over the window."""
from bench import program_trace as pt


def read(ctx):
    red, window = pt.program_trace(ctx), ctx["window"]
    if red is None or not pt.open_during(red, window, "dispatch."):
        return None
    return pt.idle_share(red, window, ("dispatch.",))
