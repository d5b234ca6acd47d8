"""Share of the device's busy time in the ``batch`` spans spent outside
the decode engine's two programs, in %: the eager programs around them
(the argmax of each step, the prefill's cache padding, the tokens'
concatenation), over all busy time there, on device 0.  The programs
are told by name, ``jit_prefill`` and ``jit_decode_step``; a program
that names them otherwise reads nothing."""
from bench import program_trace as pt

PROGRAMS = ("jit_prefill", "jit_decode_step")


def read(ctx):
    red, window = ctx["reduced"], ctx["window"]
    ops = [o for o in red.ops_in(window, span_prefix="batch")
           if o.device == 0]
    if not any(o.module in PROGRAMS for o in ops):
        return None
    glue = [o for o in ops if o.module not in PROGRAMS]
    return 100.0 * pt.length(pt.union(glue)) / pt.length(pt.union(ops))
