"""Share of the traced window in which device 0 ran nothing while
Python's garbage collector ran, in %: idle time inside the program's
``host.gc`` spans, over the window.  Read only from a program whose
decode engine opens spans (it is the one that annotates collections);
a window without a collection reads 0."""
from bench import program_trace as pt


def read(ctx):
    red, window = pt.program_trace(ctx), ctx["window"]
    if red is None or not pt.open_during(red, window, "engine.generate"):
        return None
    return pt.idle_share(red, window, ("host.gc",))
