"""The program's own spans in the run's trace, for the per-layer metrics
that read them.

The program opens profiler spans named ``<layer>.<name>``:
``dispatch.route`` and ``dispatch.launch`` around every kernel call,
``engine.generate``, ``engine.prefill``, ``engine.step`` and
``engine.wait`` in ``DecodeEngine.generate``, and ``host.gc`` around
every Python garbage collection.  The harness reduces the trace with
its own span names only, so :func:`program_trace` reads the program's
spans from the same file, the newest ``.xplane.pb`` under
``.bench_trace/``, where ``bench/run.py`` writes, and puts them beside
the device operations the harness reduced.  Only the host plane is read
again, once per run (a module-level cache), and the spans are taken only
if the file's ``window`` span is the one the run reduced, to the
nanosecond.  (A second ``reduce_trace`` would give the same operations
and cost tens of seconds more: it looks up the innermost span of every
operation, and a decode window holds thousands of step spans.)

The rest is interval arithmetic on the host clock: the stretches of the
window in which device 0 ran nothing, and the parts of them that some
program spans cover.  A metric reads nothing (``None``) from a program
that opens no such span.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench.common import ROOT
from bench.trace_reduce import _HOST_PLANE, Reduced, Span, _merge

PREFIXES = ("window", "engine.", "dispatch.", "host.")
TRACE_DIR = ROOT / ".bench_trace"

Interval = Tuple[float, float]
_CACHE: Dict[Tuple[str, int], List[Span]] = {}


def program_trace(ctx) -> Optional[Reduced]:
    """The harness's reduction (``ctx["reduced"]``) with the program's
    spans, and ``window``, in place of the harness's; None when the
    newest trace is not the one whose window ``ctx`` holds."""
    paths = sorted(TRACE_DIR.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    if not paths:
        return None
    key = (str(paths[-1]), paths[-1].stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE[key] = _host_spans(key[0])
    spans = _CACHE[key]
    window = [s for s in spans if s.name == "window"]
    want = ctx["window"]
    if len(window) != 1 or (window[0].start, window[0].end) != (
            want.start, want.end):
        return None
    return dataclasses.replace(ctx["reduced"], spans=spans)


def _host_spans(path: str) -> List[Span]:
    """The host spans of ``path`` whose names start with ``PREFIXES``."""
    from jax.profiler import ProfileData

    return [Span(e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name == _HOST_PLANE
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIXES)]


def union(items: Iterable) -> List[Interval]:
    """The union of the intervals of spans or device operations."""
    return _merge([(x.start, x.end) for x in items])


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """Where two unions (sorted, disjoint intervals) overlap."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        start, end = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if end > start:
            out.append((start, end))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of union ``a`` that union ``b`` does not cover."""
    if not a:
        return []
    rest, t = [], a[0][0]
    for start, end in b:
        if start > t:
            rest.append((t, start))
        t = max(t, end)
    rest.append((t, max(t, a[-1][1])))
    return intersect(a, rest)


def length(iv: Sequence[Interval]) -> float:
    return sum(end - start for start, end in iv)


def idle(red: Reduced, window: Span) -> List[Interval]:
    """The stretches of ``window`` in which device 0 ran nothing."""
    busy = union(o for o in red.ops_in(window) if o.device == 0)
    return subtract([(window.start, window.end)], busy)


def open_during(red: Reduced, window: Span, *prefixes: str
                ) -> List[Interval]:
    """The union, clipped to ``window``, of the program spans whose
    names start with one of ``prefixes``."""
    spans = [s for s in red.spans if s.name.startswith(prefixes)]
    return intersect(union(spans), [(window.start, window.end)])


def idle_share(red: Reduced, window: Span, inside: Sequence[str],
               outside: Sequence[str] = ()) -> float:
    """% of ``window`` with device 0 idle while a span named with one of
    ``inside`` is open and none named with one of ``outside``."""
    held = intersect(idle(red, window), open_during(red, window, *inside))
    if outside:
        held = subtract(held, open_during(red, window, *outside))
    return 100.0 * length(held) / window.dur
