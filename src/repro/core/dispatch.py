"""Engine-dispatch runtime: one place that turns the paper's decision
framework into kernel launches.

Every kernel family used to hand-roll three things: (1) the advisor
lookup that routes memory-bound work to the vector engine, (2) the
flatten/pad/tile/unpad plumbing around ``pallas_call``, and (3) the
``interpret`` flag threading.  This module owns all three:

  * ``Dispatcher`` -- resolves ``engine='auto'|'vpu'|'mxu'`` against the
    advisor, memoizing one ``Advice`` per (kernel, shape, dtype,
    hardware) so steady-state dispatch is a dict hit, not a roofline
    re-derivation.
  * ``elementwise_call`` -- the shared flatten/pad/tile/unpad wrapper and
    block-spec construction for same-shape elementwise kernels (SCALE,
    STREAM Triad, AXPY, ...): a kernel family supplies only its per-tile
    Pallas bodies.
  * ``use_interpreter`` -- the one interpret-vs-compiled decision every
    Pallas launch resolves its ``interpret=None`` default through:
    compiled on a TPU backend, the Pallas interpreter elsewhere.

Kernel families register their bodies as an ``EngineOp`` in
``repro.kernels.registry``; ``DEFAULT_DISPATCHER.run`` is the single
path from a registered op + arguments to a Pallas launch.  The tile
shape is not decided here: each kernel family owns its static default
(lowered by the kernel's own VMEM budget where it has one), and a
caller may pass an explicit tile kwarg, which reaches the body as given.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Hashable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..obs.counters import roofline_sample
from ..obs.log import LOG
from ..obs.trace import TRACER
from .advisor import DEFAULT_ADVISOR, Advice, EngineAdvisor
from .intensity import KernelTraits

__all__ = [
    "DEFAULT_DISPATCHER", "Dispatcher", "default_cache_key",
    "elementwise_call", "mxu_dot", "normalize_engine", "use_interpreter",
    "ELEMENTWISE_BLOCK_ROWS", "ELEMENTWISE_LANES",
]

_ENGINE_ALIASES = {
    "mxu": "matrix", "matrix": "matrix",
    "vpu": "vector", "vector": "vector",
}


def use_interpreter(interpret: Optional[bool] = None) -> bool:
    """The one interpret-vs-compiled decision for every Pallas launch.

    ``None`` -- the default everywhere on the main path -- takes it from
    the backend: compiled Mosaic kernels on a TPU, the Pallas interpreter
    on any other backend.  An explicit bool is honoured as given (a test
    may force the interpreter).
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def mxu_dot(a, b):
    """``a @ b`` on the matrix engine, accumulated in f32.

    f32 operands contract at HIGHEST precision (the TPU default would
    round them to bf16), so the matrix-engine variants compute the same
    numbers as their vector twins; bf16 operands are native MXU inputs.
    """
    f32 = jnp.float32 in (a.dtype, b.dtype)
    return jax.lax.dot(a, b,
                       precision=jax.lax.Precision.HIGHEST if f32 else None,
                       preferred_element_type=jnp.float32)


def normalize_engine(engine: str) -> Optional[str]:
    """'auto' -> None (advisor decides); 'mxu'/'vpu' aliases -> canonical.

    The canonical names follow the paper's engine taxonomy (§2.1):
    'matrix' (tensor core / MXU) and 'vector' (CUDA core / VPU).
    """
    if engine == "auto":
        return None
    try:
        return _ENGINE_ALIASES[engine]
    except KeyError:
        raise ValueError(
            f"unknown engine {engine!r}; expected 'auto', "
            f"{sorted(set(_ENGINE_ALIASES))}") from None


def _probe(x: Any) -> Hashable:
    """Reduce one call argument to a hashable dispatch-cache component.

    Arrays contribute (shape, dtype) -- their values never change the
    roofline position.  Containers and (frozen or not) dataclasses such
    as BlockEll recurse field-wise so unhashable array members don't
    poison the key.
    """
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("arr", tuple(x.shape), str(x.dtype))
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        try:
            hash(x)
            return x
        except TypeError:
            return (type(x).__name__,) + tuple(
                _probe(getattr(x, f.name)) for f in dataclasses.fields(x))
    if isinstance(x, (tuple, list)):
        return tuple(_probe(e) for e in x)
    if isinstance(x, dict):
        return tuple((k, _probe(v)) for k, v in sorted(x.items()))
    try:
        hash(x)
        return x
    except TypeError:
        return ("repr", repr(x))


def default_cache_key(*args, **kwargs) -> Hashable:
    """Shape/dtype cache key for Advice memoization.

    Two calls share a key iff they share a roofline position (paper
    §2.3): array values never move a kernel on the roofline, only
    shapes, dtypes, and static parameters do.
    """
    return (_probe(args), _probe(kwargs))


def _dtype_of(args: tuple, kwargs: dict) -> Optional[str]:
    """The dtype string of the first array-ish call argument, if any
    (the traced launch's roofline counters are per dtype)."""
    for x in list(args) + list(kwargs.values()):
        if hasattr(x, "dtype") and hasattr(x, "shape"):
            return str(x.dtype)
    return None


_MESH_MODES = ("virtual", "mesh")


def _check_mesh_mode(mode: str) -> str:
    if mode not in _MESH_MODES:
        raise ValueError(
            f"mesh mode must be one of {_MESH_MODES}, got {mode!r}")
    return mode


class Dispatcher:
    """Advisor-backed engine router with a memoized Advice cache.

    Implements the paper's §6 takeaway as a runtime policy: classify by
    intensity vs. machine balance (Eq. 1/2/4), send memory-bound work to
    the vector engine, and memoize the resulting Advice so steady-state
    dispatch is a dict hit.
    """

    def __init__(self, advisor: Optional[EngineAdvisor] = None,
                 mesh_shards: int = 1, mesh_mode: str = "virtual"):
        self.advisor = advisor if advisor is not None else DEFAULT_ADVISOR
        self._mesh_shards = max(1, int(mesh_shards))
        self._mesh_mode = _check_mesh_mode(mesh_mode)
        self._cache: Dict[Hashable, Advice] = {}
        self._hits = 0
        self._misses = 0
        TRACER.watch_gc()

    @property
    def hw(self):
        """The advisor's HardwareSpec (paper Table 1 platform model)."""
        return self.advisor.hw

    @property
    def mesh_shards(self) -> int:
        """How many mesh shards Advice is planned for (1 = no mesh)."""
        return self._mesh_shards

    @property
    def mesh_mode(self) -> str:
        """How sharded calls execute: "virtual" clock or real "mesh"."""
        return self._mesh_mode

    def set_mesh(self, num_shards: int, mode: str = "virtual") -> None:
        """Configure the mesh width (and execution mode) Advice plans for.

        With ``num_shards > 1`` every memoized Advice carries the
        ``ShardSpec`` the sharding layer (``repro.sharding.plan``)
        derives for its call — the paper's §6 decision is then a
        per-shard statement, which Eq. 2's intensity invariance under
        data-parallel splitting keeps identical to the per-device one.
        ``mode`` stamps how those shards execute: ``"virtual"`` (serial
        launches, modeled N-way clock — PR 5's ShardedExecutor) or
        ``"mesh"`` (one ``shard_map`` step over real devices with
        measured wall time — MeshExecutor).  The mode does not change
        the split or the engine decision, only which executor the
        callers build and how records label their timings.  The Advice
        cache embeds both, so changing either drops it.
        """
        num_shards = int(num_shards)
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        mode = _check_mesh_mode(mode)
        if num_shards != self._mesh_shards or mode != self._mesh_mode:
            self._mesh_shards = num_shards
            self._mesh_mode = mode
            self.cache_clear()

    # -- advice ------------------------------------------------------------

    def _memoized(self, key: Hashable,
                  make: Callable[[], Advice]) -> Advice:
        advice = self._cache.get(key)
        if advice is None:
            self._misses += 1
            advice = self._cache[key] = make()
        else:
            self._hits += 1
        return advice

    def advise(self, op, *args, **kwargs) -> Advice:
        """Memoized Advice (paper §6 decision) for one op + call arguments.

        The cache key is (kernel, hardware, shapes/dtypes/static params);
        the op's ``KernelTraits`` factory (W flops, Q bytes per Eq. 2)
        only runs on a miss.
        """
        key_fn = op.cache_key or default_cache_key
        key = (op.name, self.hw.name, self._mesh_shards,
               key_fn(*args, **kwargs))

        def make() -> Advice:
            advice = self.advisor.advise(op.traits(*args, **kwargs))
            if self._mesh_shards > 1:
                # planned once per (kernel, shape, mesh) and memoized
                # with the engine decision: steady-state sharded
                # dispatch stays a dict hit (§6 in steady state)
                from ..sharding.plan import spec_for
                advice = dataclasses.replace(
                    advice,
                    shard_spec=spec_for(op, self._mesh_shards,
                                        *args, **kwargs),
                    exec_mode=self._mesh_mode)
            return advice

        return self._memoized(key, make)

    def advise_traits(self, traits: KernelTraits) -> Advice:
        """Memoized Advice (paper §6) for hand-built Eq. 2 traits.

        Used by the launch/analysis paths that know W and Q directly
        instead of going through a registered op.
        """
        key = (traits.name, self.hw.name, traits.work_flops,
               traits.traffic_bytes)
        return self._memoized(key, lambda: self.advisor.advise(traits))

    # -- dispatch ----------------------------------------------------------

    def resolve(self, op, *args, engine: str = "auto", **kwargs) -> str:
        """Resolve an engine flag to 'vector'|'matrix' for this call.

        'auto' defers to the advisor (paper §6: memory-bound -> vector);
        explicit flags are honored verbatim.
        """
        forced = normalize_engine(engine)
        if forced is not None:
            return forced
        return self.advise(op, *args, **kwargs).engine

    def run(self, op, *args, engine: str = "auto",
            interpret: Optional[bool] = None, **kwargs):
        """Advisor-route (paper §6) and launch one op.

        ``kwargs`` reach the engine body as given, tile kwargs
        (``block_rows``, ``block_s``, ...) included: the family's body
        owns its tile, and its traits factory ignores tile kwargs.

        Every call opens two :mod:`repro.obs` spans, which a profiler
        trace shows as ``dispatch.route`` (engine resolution) and
        ``dispatch.launch`` (the engine body: the wrapper's eager ops
        and the Pallas enqueue); neither blocks.  When the in-memory
        tracer is enabled, the call is also wrapped in a ``dispatch``
        span, and the launch span blocks on the result and carries the
        Eq. 2/3/4 roofline counters for the measured wall time.
        """
        if not TRACER.enabled:
            return self._run(op, *args, engine=engine, interpret=interpret,
                             **kwargs)
        with TRACER.span("dispatch", layer="dispatch",
                         kernel=op.name) as span_attrs:
            return self._run(op, *args, engine=engine, interpret=interpret,
                             _span_attrs=span_attrs, **kwargs)

    def _run(self, op, *args, engine: str, interpret: Optional[bool],
             _span_attrs: Optional[Dict[str, Any]] = None, **kwargs):
        with TRACER.span("route", layer="dispatch", kernel=op.name):
            eng = self.resolve(op, *args, engine=engine, **kwargs)
            fn = op.engines.get(eng)
            if fn is None:
                raise ValueError(
                    f"kernel {op.name!r} has no {eng!r} variant "
                    f"(has {sorted(op.engines)})")
        if _span_attrs is None:
            with TRACER.span("launch", layer="dispatch", kernel=op.name):
                return fn(*args, interpret=interpret, **kwargs)
        # traced launch: block on the result so the span duration is
        # the call's real wall time, then attach the roofline counters
        # (modeled bytes / achieved GB/s / % of bound and ceiling)
        dtype = _dtype_of(args, kwargs) or ""
        _span_attrs.update(engine=eng, dtype=dtype)
        with TRACER.span("launch", layer="dispatch", kernel=op.name,
                         engine=eng, dtype=dtype) as launch_attrs:
            t0 = time.perf_counter()
            out = fn(*args, interpret=interpret, **kwargs)
            jax.block_until_ready(out)
            dur_us = (time.perf_counter() - t0) * 1e6
            try:
                sample = roofline_sample(op.traits(*args, **kwargs),
                                         self.hw, eng, dtype, dur_us)
                launch_attrs.update(sample.as_attrs())
            except (TypeError, ValueError) as e:
                LOG.debug("roofline counters unavailable",
                          kernel=op.name, engine=eng, error=str(e))
        return out

    def cache_info(self) -> Dict[str, int]:
        """Advice-cache statistics: {size, hits, misses}."""
        return {"size": len(self._cache), "hits": self._hits,
                "misses": self._misses}

    def cache_clear(self) -> None:
        """Drop all memoized Advice (e.g. after swapping hardware specs)."""
        self._cache.clear()
        self._hits = self._misses = 0


DEFAULT_DISPATCHER = Dispatcher()


# --------------------------------------------------------------------------
# shared elementwise flatten/pad/tile/unpad wrapper
# --------------------------------------------------------------------------

ELEMENTWISE_LANES = 1024      # row width the wrapper reshapes to
ELEMENTWISE_BLOCK_ROWS = 256  # 256 x 1024 x 4B = 1 MiB VMEM blocks


@functools.partial(jax.jit, static_argnames=("body", "block_rows",
                                             "interpret"))
def _elementwise_grid(body, scalars, arrays, *, block_rows: int,
                      interpret: bool):
    """1D grid over (rows, lanes) tiles; scalars ride along as (1,1) refs."""
    rows, lanes = arrays[0].shape
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    tile_spec = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    return pl.pallas_call(
        body,
        grid=(rows // block_rows,),
        in_specs=[scalar_spec] * len(scalars) + [tile_spec] * len(arrays),
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), arrays[0].dtype),
        interpret=interpret,
        name=body.__name__,
    )(*scalars, *arrays)


def elementwise_call(body: Callable, arrays: Sequence[jnp.ndarray],
                     scalars: Sequence[Any] = (), *,
                     interpret: Optional[bool] = None,
                     lanes: Optional[int] = None,
                     block_rows: Optional[int] = None) -> jnp.ndarray:
    """Run an elementwise Pallas body over same-shape arrays of any shape.

    The shared plumbing behind the paper's §3.1 elementwise suite
    (SCALE, STREAM Triad, AXPY): ``body(*scalar_refs, *array_refs,
    o_ref)`` sees (block_rows, lanes)
    tiles; this wrapper owns the flatten -> pad-to-tile -> reshape ->
    grid/block-spec construction -> unpad round trip that every
    elementwise kernel family previously duplicated.

    ``block_rows``/``lanes`` are the tile shape; ``None`` means the
    static 256 x 1024 default.
    """
    lanes = ELEMENTWISE_LANES if lanes is None else int(lanes)
    block_rows = (ELEMENTWISE_BLOCK_ROWS if block_rows is None
                  else int(block_rows))
    arrays = tuple(arrays)
    shape, dtype = arrays[0].shape, arrays[0].dtype
    for a in arrays[1:]:
        if a.shape != shape:
            raise ValueError(f"elementwise arrays disagree: {a.shape} vs "
                             f"{shape}")
    n = arrays[0].size
    tile = block_rows * lanes
    pad = (-n) % tile
    flats = []
    for a in arrays:
        f = a.reshape(-1)
        if pad:
            f = jnp.pad(f, (0, pad))
        flats.append(f.reshape(-1, lanes))
    scalars2d = tuple(jnp.asarray(s, jnp.float32).reshape(1, 1)
                      for s in scalars)
    out = _elementwise_grid(body, scalars2d, tuple(flats),
                            block_rows=block_rows,
                            interpret=use_interpreter(interpret))
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)
