"""Share of the traced window in which device 0 ran nothing while the
decode engine's host loop ran, in %: idle time inside the program's
``engine.generate`` spans, outside its ``engine.wait`` spans (the host
waiting for the device) and ``host.gc`` (a garbage collection), over
the window."""
from bench import program_trace as pt


def read(ctx):
    red, window = pt.program_trace(ctx), ctx["window"]
    if red is None or not pt.open_during(red, window, "engine.generate"):
        return None
    return pt.idle_share(red, window, ("engine.generate",),
                         ("engine.wait", "host.gc"))
