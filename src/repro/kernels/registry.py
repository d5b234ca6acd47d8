"""Unified kernel registry: every kernel family is one ``EngineOp``.

A family registers its vector/matrix Pallas entry points together with
its ``KernelTraits`` factory, oracle, and input builder; the engine
routing, Advice memoization, and ``interpret`` threading then live in
``repro.core.dispatch`` -- so a new memory-bound workload costs its
kernel bodies plus one ``register()`` call, and every consumer
(benchmarks, tests, launchers) discovers it from here instead of
keeping a per-kernel module list.

    op = registry.get("scale")
    y = op(x, 2.5)                  # engine='auto': advisor-routed
    y = op(x, 2.5, engine="mxu")    # forced matrix engine
    advice = op.advice(x, 2.5)      # the memoized paper §6 decision
"""
from __future__ import annotations

import dataclasses
import importlib
import pkgutil
from typing import Any, Callable, Dict, Hashable, Mapping, Optional, Tuple

from ..core.dispatch import DEFAULT_DISPATCHER
from ..core.intensity import KernelTraits

__all__ = ["EngineOp", "all_ops", "discover", "get", "names", "register"]


@dataclasses.dataclass(frozen=True)
class EngineOp:
    """One kernel family: per-engine Pallas entry points + metadata.

    The unit of the paper's §3 workload study: each family ships both a
    vector-engine and a matrix-engine implementation so the §6 decision
    framework has a real choice to make.  ``engines`` map
    'vector'/'matrix' to ``fn(*args, interpret=..., **kw)``;
    ``traits``/``reference``/``make_inputs`` share the op's call
    signature so the dispatch layer, the generic benchmark driver, and
    the registry tests need no per-kernel knowledge.  ``traits`` ignores
    the tile kwargs an engine body accepts: a tile never moves a kernel
    on the roofline.
    """

    name: str
    traits: Callable[..., KernelTraits]
    engines: Mapping[str, Callable[..., Any]]
    reference: Callable[..., Any]
    # (rng, size, dtype) -> (args, kwargs) accepted by traits/engines/ref
    make_inputs: Callable[..., Tuple[tuple, dict]]
    bench_sizes: Tuple[int, ...] = ()
    dtypes: Tuple[str, ...] = ("float32",)
    test_size: int = 0
    cache_key: Optional[Callable[..., Hashable]] = None
    doc: str = ""
    # -- mesh sharding (see repro.sharding / docs/sharding.md) ----------
    # how this family splits across a data-axis mesh: 'data'
    # (flattened elementwise ranges), 'rowblock' (contiguous row /
    # block-row ranges, optionally with halo exchange), or 'head'
    # (KV-head ranges for decode attention)
    shard_kind: str = "data"
    # (*args, **kwargs) -> halo rows each rowblock shard must borrow
    # from its neighbours (e.g. t*r for a stencil at temporal depth t,
    # paper Eq. 13); None = no halo
    shard_halo: Optional[Callable[..., int]] = None

    def __call__(self, *args, engine: str = "auto",
                 interpret: Optional[bool] = None, **kwargs):
        """Launch via the default dispatcher ('auto' = paper §6 routing).

        A tile kwarg (``block_rows``, ``lanes``, ``block_s``) is passed
        to the engine body as given; omitted, the body uses its
        family's static default.
        """
        return DEFAULT_DISPATCHER.run(self, *args, engine=engine,
                                      interpret=interpret, **kwargs)

    def advice(self, *args, **kwargs):
        """The memoized §6 Advice (engine, boundedness, Eq. 23/24 ceiling)."""
        return DEFAULT_DISPATCHER.advise(self, *args, **kwargs)


_REGISTRY: Dict[str, EngineOp] = {}
_DISCOVERED = False


def register(op: EngineOp) -> EngineOp:
    """Register (or re-register, e.g. on module reload) one kernel op.

    Registration is the only wiring a new §3-style workload needs: the
    benchmark sweep, the claims report, and 'auto' routing discover it
    from here.
    """
    _REGISTRY[op.name] = op
    return op


def discover() -> None:
    """Import every ``repro.kernels.<family>.ops`` so registrations run.

    Families are found by scanning this package's subpackages -- adding
    a kernel means adding its directory, not editing a list here.
    """
    global _DISCOVERED
    if _DISCOVERED:
        return
    pkg = importlib.import_module(__package__)
    for mod in pkgutil.iter_modules(pkg.__path__):
        if not mod.ispkg:
            continue
        ops_module = f"{__package__}.{mod.name}.ops"
        try:
            importlib.import_module(ops_module)
        except ModuleNotFoundError as exc:
            if exc.name != ops_module:  # broken transitive import: surface it
                raise
            # family without a public ops module: nothing to register
    # only mark done once every family imported, so a failed import is
    # retried (not silently frozen into a partial registry)
    _DISCOVERED = True


def names() -> Tuple[str, ...]:
    """Sorted names of every registered kernel family (paper §3 suite)."""
    discover()
    return tuple(sorted(_REGISTRY))


def get(name: str) -> EngineOp:
    """Look up one registered kernel family by name (KeyError if absent)."""
    discover()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no kernel {name!r} registered; have {sorted(_REGISTRY)}"
        ) from None


def all_ops() -> Tuple[EngineOp, ...]:
    """Every registered op, name-sorted -- the benchmark/report sweep set."""
    discover()
    return tuple(_REGISTRY[k] for k in sorted(_REGISTRY))
