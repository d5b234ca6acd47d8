"""SLO-aware shard-width routing for kernel serving sessions.

The static serving stack routes with one offline fact: the memoized §6
Advice (engine, from the Eq. 2 intensity vs. Eq. 4 machine balance).
Under live load two more signals exist that it does not see — queue
depth and SLO headroom — and :class:`SLORouter` turns them into the
decision a serving control plane owns: the **shard width**.  It grows
the mesh split when the queue is deep and the head request's SLO
headroom is thin, and shrinks it back when the queue drains.  Width
changes re-plan through ``Dispatcher.set_mesh`` so the memoized Advice
carries the right ShardSpecs — and Eq. 2 intensity is invariant under
the data split, so the *engine* decision is identical at every width.
:class:`RoutedKernelBatchExecutor` applies the router's width at every
batch dequeue (``serve --slo-route``).

What the router deliberately does **not** own: overriding the Advice
engine.  The paper's Eq. 23/24 ceiling makes any matrix-engine
"discovery" for memory-bound work a modeling error by construction,
so :meth:`SLORouter.decide` records the Advice engine it was handed
and routes width around it.

Every decision is appended to the router's log and emitted as a
``route`` trace instant on the virtual clock.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from ..core.dispatch import DEFAULT_DISPATCHER, normalize_engine
from ..obs.trace import TRACER
from ..sharding import ShardedExecutor
from .batcher import KernelBatchExecutor
from .requests import Request

__all__ = ["RoutedKernelBatchExecutor", "RouterDecision", "SLORouter"]


@dataclasses.dataclass(frozen=True)
class RouterDecision:
    """One routing decision at a batch dequeue.

    ``engine`` is the §6 Advice engine the router was handed — never
    overridden (see the module docstring); ``width`` is the mesh shard
    width the next launch runs at; ``reason`` names which rule fired
    (``grow`` / ``shrink`` / ``hold``).
    """

    clock_s: float      # virtual-clock dequeue time
    engine: str         # 'vector' | 'matrix' — the Advice engine
    width: int          # mesh shard width for the launch
    queue_depth: int    # admitted-but-unserved requests (incl. batch)
    headroom_ms: float  # slo_ms minus the head request's wait so far
    reason: str         # 'grow' | 'shrink' | 'hold'


class SLORouter:
    """Queue-depth + SLO-headroom policy for the shard width.

    The router owns width only — never the engine.  Eq. 2 intensity is
    invariant under the data split, so the §6 Advice engine it is
    handed stays correct at every width, and the Eq. 23/24 ceiling
    makes overriding it a modeling error.

    Deterministic and RNG-free (serving replay must reproduce it):
    width doubles when ``queue_depth >= grow_depth`` *and* headroom is
    below ``pressure_frac`` of the SLO, halves when the queue has
    drained to ``shrink_depth`` or fewer, and holds otherwise — the
    two thresholds are the hysteresis band that keeps the mesh from
    thrashing.
    """

    def __init__(self, *, slo_ms: float = 50.0, max_width: int = 4,
                 grow_depth: int = 16, shrink_depth: int = 2,
                 pressure_frac: float = 0.5):
        if slo_ms <= 0:
            raise ValueError(f"slo_ms must be > 0, got {slo_ms}")
        if max_width < 1:
            raise ValueError(f"max_width must be >= 1, got {max_width}")
        if shrink_depth >= grow_depth:
            raise ValueError(
                f"shrink_depth ({shrink_depth}) must be below "
                f"grow_depth ({grow_depth}) — the hysteresis band")
        self.slo_ms = float(slo_ms)
        self.max_width = int(max_width)
        self.grow_depth = int(grow_depth)
        self.shrink_depth = int(shrink_depth)
        self.pressure_frac = float(pressure_frac)
        self.width = 1
        self.decisions: List[RouterDecision] = []

    def decide(self, *, clock_s: float, engine: str, queue_depth: int,
               oldest_wait_ms: float) -> RouterDecision:
        """One routing decision from the dequeue-time signals.

        *engine* is the Advice engine for the batch about to launch —
        recorded, never changed.  Appends the decision to
        :attr:`decisions` and emits a ``route`` trace instant.
        """
        headroom_ms = self.slo_ms - float(oldest_wait_ms)
        width, reason = self.width, "hold"
        if (queue_depth >= self.grow_depth
                and headroom_ms < self.slo_ms * self.pressure_frac
                and width < self.max_width):
            width, reason = min(self.max_width, width * 2), "grow"
        elif queue_depth <= self.shrink_depth and width > 1:
            width, reason = max(1, width // 2), "shrink"
        self.width = width
        decision = RouterDecision(
            clock_s=float(clock_s), engine=engine, width=width,
            queue_depth=int(queue_depth), headroom_ms=headroom_ms,
            reason=reason)
        self.decisions.append(decision)
        TRACER.instant("route", layer="router", at_s=clock_s,
                       engine=engine, width=width,
                       depth=int(queue_depth),
                       headroom_ms=round(headroom_ms, 3), reason=reason)
        return decision


class RoutedKernelBatchExecutor(KernelBatchExecutor):
    """A :class:`KernelBatchExecutor` whose shard width the router sets.

    The scheduler's :meth:`on_dequeue` signals feed the
    :class:`SLORouter`, and a width change rebuilds the shard executor
    in place, dropping the plan/warm caches whose keys embed the old
    capacity.  Engine selection is inherited unchanged.  Starts at
    width 1 on the virtual clock.
    """

    def __init__(self, engine: str = "auto", *, router: SLORouter,
                 max_batch: int = 8, interpret: Optional[bool] = None,
                 seed: int = 0, dispatcher=None):
        super().__init__(engine, max_batch=max_batch,
                         interpret=interpret, seed=seed, num_shards=1,
                         real_mesh=False)
        self.router = router
        self.dispatcher = (dispatcher if dispatcher is not None
                           else DEFAULT_DISPATCHER)

    def on_dequeue(self, batch: List[Request], *, clock_s: float,
                   queue_depth: int) -> None:
        """The scheduler's pre-launch signal: route this batch.

        Resolves the batch's Advice engine (memoized — a dict hit in
        steady state), asks the router for a width, and applies a
        width change before the launch.
        """
        req = batch[0]
        advice = self.advice_for(req.kernel, req.size, req.dtype)
        engine = (advice.engine if self.engine == "auto"
                  else normalize_engine(self.engine))
        oldest_wait_ms = max(0.0, (clock_s - req.arrival_s) * 1e3)
        decision = self.router.decide(
            clock_s=clock_s, engine=engine, queue_depth=queue_depth,
            oldest_wait_ms=oldest_wait_ms)
        if decision.width != self.num_shards:
            self._set_width(decision.width)

    def _set_width(self, width: int) -> None:
        """Retarget the mesh width in place (the router's resize).

        Rebuilds the shard executor and drops the plan/warm/packed
        caches — their keys embed the old capacity — then re-plans the
        dispatcher's memoized Advice via ``set_mesh`` so ShardSpecs
        match the new width.  Canonical inputs survive: payloads are
        width-independent.
        """
        self.num_shards = max(1, int(width))
        self._shard_exec = (ShardedExecutor(self.num_shards,
                                            interpret=self.interpret)
                            if self.num_shards > 1 else None)
        self._plans.clear()
        self._warmed.clear()
        self._packed.clear()
        self.dispatcher.set_mesh(self.num_shards)
