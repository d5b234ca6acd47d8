"""Reduce one profiler trace to what the per-layer metrics read.

``reduce_trace(path, span_prefixes)`` reads a ``.xplane.pb`` with
``jax.profiler.ProfileData`` (nothing but JAX) and returns a
:class:`Reduced`:

* device operations: every event of each TPU plane's ``XLA Ops`` line,
  with its HLO text and whether it is a Pallas kernel (its HLO names
  ``custom_call_target="tpu_custom_call"``);
* program executions: the ``XLA Modules`` events, each joined to the
  host event that enqueued it through their common ``run_id`` (an
  operation whose execution the trace dropped is placed by its time);
* the benchmark's own host spans (``jax.profiler.TraceAnnotation``
  events whose names start with one of ``span_prefixes``).

The device clock of a TPU trace runs apart from the host's by about a
millisecond.  The offset is taken as the least ``device start - host
enqueue`` over all program executions (a program cannot start before it
is enqueued), and every device time is moved onto the host clock with
it.  A device operation belongs to the span that was open on the host
when its program was enqueued, which does not depend on the offset.

Busy time is the union of the device-operation intervals; an idle gap is
a stretch of the window in which no operation ran, labelled with the
benchmark span and the innermost host event open at its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

PALLAS_MARK = 'custom_call_target="tpu_custom_call"'
_OP_NAME = re.compile(r"^%?([^\s=]+)")
_MODULE_NAME = re.compile(r"^([^(]+)")
_HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Span:
    """A host interval: a benchmark span or any host event."""

    name: str
    start: float  # ns, host clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass(frozen=True)
class DeviceOp:
    """One device operation, on the host clock."""

    device: int
    op: str       # the HLO instruction's name, e.g. "fusion.12"
    hlo: str      # the whole HLO text the trace gives
    module: str   # the program it ran in, e.g. "jit__elementwise_grid"
    span: str     # the benchmark span open when its program was enqueued
    start: float
    end: float

    @property
    def pallas(self) -> bool:
        return PALLAS_MARK in self.hlo

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Reduced:
    """A trace reduced to device operations and benchmark spans."""

    ops: List[DeviceOp]
    spans: List[Span]
    host_events: List[Span]
    devices: int
    clock_offset_ns: float

    def window(self, name: str = "window") -> Span:
        """The benchmark span ``name`` (the measured window)."""
        for s in self.spans:
            if s.name == name:
                return s
        raise KeyError(f"no span {name!r} in the trace; spans: "
                       f"{sorted({s.name for s in self.spans})}")

    def ops_in(self, window: Span, pallas: Optional[bool] = None,
               span_prefix: str = "") -> List[DeviceOp]:
        """Operations clipped to ``window``, optionally only Pallas ones
        (or only others) and only those of spans named ``span_prefix``."""
        out = []
        for o in self.ops:
            if pallas is not None and o.pallas != pallas:
                continue
            if not o.span.startswith(span_prefix):
                continue
            start, end = max(o.start, window.start), min(o.end, window.end)
            if end > start:
                out.append(dataclasses.replace(o, start=start, end=end))
        return out

    def busy_ns(self, window: Span, pallas: Optional[bool] = None) -> float:
        """Union of operation intervals in ``window``, averaged over
        devices."""
        per_dev: Dict[int, List[Tuple[float, float]]] = defaultdict(list)
        for o in self.ops_in(window, pallas):
            per_dev[o.device].append((o.start, o.end))
        total = sum(_union_length(iv) for iv in per_dev.values())
        return total / max(self.devices, 1)

    def idle_gaps(self, window: Span, labelled: int = 200
                  ) -> List[Tuple[str, float]]:
        """(label, ns) of every stretch of ``window`` with no operation
        on device 0.  The ``labelled`` longest are labelled by what the
        host was doing at their middle, the rest "shorter gaps"."""
        iv = sorted((o.start, o.end) for o in self.ops_in(window)
                    if o.device == 0)
        gaps, t = [], window.start
        for start, end in _merge(iv):
            if start > t:
                gaps.append((t, start))
            t = max(t, end)
        if window.end > t:
            gaps.append((t, window.end))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self.host_label((a + b) / 2) if i < labelled
                 else "shorter gaps", b - a)
                for i, (a, b) in enumerate(gaps)]

    def host_label(self, t: float) -> str:
        """The innermost benchmark span and host event open at ``t``."""
        span = _innermost([s for s in self.spans if s.name != "window"], t)
        event = _innermost(self.host_events, t)
        return (f"{span.name if span else 'between spans'} / "
                f"{event.name if event else 'no host event'}")


def _innermost(spans: Sequence[Span], t: float) -> Optional[Span]:
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.dur < best.dur):
            best = s
    return best


def _merge(iv: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for start, end in sorted(iv):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def _union_length(iv: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in _merge(iv))


def _device_index(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def reduce_trace(path: str, span_prefixes: Sequence[str]) -> Reduced:
    """Read ``path`` (an ``.xplane.pb``) into a :class:`Reduced`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans: List[Span] = []
    host_events: List[Span] = []
    enqueued: Dict[int, float] = {}
    modules: List[Tuple[int, str, float, float, int]] = []
    raw_ops: List[Tuple[int, str, float, float]] = []
    for plane in data.planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == "XLA Modules":
                for e in line.events:
                    run = dict(e.stats or {}).get("run_id")
                    name = _MODULE_NAME.match(e.name).group(1)
                    modules.append((dev, name, e.start_ns, e.end_ns,
                                    int(run) if run is not None else -1))
            elif dev is not None and line.name == "XLA Ops":
                raw_ops.extend((dev, e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            elif plane.name == _HOST_PLANE:
                for e in line.events:
                    if e.name.startswith(tuple(span_prefixes)):
                        spans.append(Span(e.name, e.start_ns, e.end_ns))
                        continue
                    run = dict(e.stats or {}).get("run_id")
                    if run is not None:
                        run = int(run)
                        enqueued[run] = min(enqueued.get(run, e.start_ns),
                                            e.start_ns)
                    if line.name == "python" and e.duration_ns > 0:
                        host_events.append(Span(e.name, e.start_ns,
                                                e.end_ns))
    linked = [m[2] - enqueued[m[4]] for m in modules if m[4] in enqueued]
    offset = min(linked) if linked else 0.0

    # each op runs inside one program execution on its device
    by_dev: Dict[int, List[Tuple[float, float, str, int]]] = defaultdict(list)
    for dev, name, start, end, run in modules:
        by_dev[dev].append((start, end, name, run))
    for v in by_dev.values():
        v.sort()
    starts = {d: [m[0] for m in v] for d, v in by_dev.items()}
    inner = [s for s in spans if s.name != "window"]
    ops = []
    for dev, hlo, start, end in raw_ops:
        module, run = "?", -1
        i = bisect.bisect_right(starts.get(dev, []), start) - 1
        if i >= 0 and by_dev[dev][i][1] >= end:
            _, _, module, run = by_dev[dev][i]
        # the span open when its program was enqueued; for an op whose
        # program execution the trace dropped, the span open at its middle
        host_t = enqueued.get(run, (start + end) / 2 - offset)
        span = _innermost(inner, host_t)
        ops.append(DeviceOp(device=dev, op=_OP_NAME.match(hlo).group(1),
                            hlo=hlo, module=module,
                            span=span.name if span else "",
                            start=start - offset, end=end - offset))
    devices = len({m[0] for m in modules} | {o.device for o in ops})
    return Reduced(ops=ops, spans=spans, host_events=host_events,
                   devices=devices, clock_offset_ns=offset)


def breakdown(red: Reduced, window: Span, top: int = 10) -> Dict:
    """The ``breakdown`` of a result line: the device operations that
    took most time (by span and operation; an operation that holds
    others, such as a ``while`` loop, counts only through them) and the
    idle gaps by label, in seconds."""
    ops = sorted(red.ops_in(window), key=lambda o: (o.device, o.start))
    op_time: Dict[str, float] = defaultdict(float)
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt and nxt.device == o.device and nxt.start < o.end \
                and nxt.end <= o.end:
            continue
        op_time[f"{o.span or o.module}: {o.op}"] += o.dur
    gap_time: Dict[str, float] = defaultdict(float)
    for label, ns in red.idle_gaps(window):
        gap_time[label] += ns
    order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in order(op_time)],
            "idle_gaps": [[k, v / 1e9] for k, v in order(gap_time)]}
