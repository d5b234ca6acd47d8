"""Public stencil op, registered as an ``EngineOp`` (temporal-blocking
aware: the advisor sees the blocked intensity I_t = t*|S|/D)."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ...core.intensity import stencil as stencil_traits
from ..registry import EngineOp, register
from .defs import TABLE3_DEPTH, StencilSpec, suite
from .ref import stencil_ref
from .stencil import stencil_apply

__all__ = ["STENCIL_OP", "stencil", "suite", "TABLE3_DEPTH", "StencilSpec"]

#: Static leading-axis block height (``stencil_apply``'s default).
DEFAULT_BLOCK_ROWS = 128


def _traits(u, spec: StencilSpec, *, steps: int = 1, block_rows=None):
    del block_rows
    return stencil_traits(spec.num_points, t=steps, dsize=u.dtype.itemsize,
                          npoints_domain=u.size)


def _reference(u, spec: StencilSpec, *, steps: int = 1, block_rows=None):
    del block_rows  # implementation tiling knob; the oracle has none
    return stencil_ref(u, spec, steps=steps)


def _make_inputs(rng: np.random.Generator, size: int, dtype: str = "float32"):
    """size = 2D domain side; the Table-3 5-point star at its paper depth."""
    spec = suite()["2d5pt"]
    u = jnp.asarray(rng.standard_normal((size, size)), dtype)
    return (u, spec), {"steps": TABLE3_DEPTH["2d5pt"]}


def _engine_fn(engine: str):
    def call(u, spec: StencilSpec, *, steps: int = 1, block_rows=None,
             interpret: Optional[bool] = None):
        br = DEFAULT_BLOCK_ROWS if block_rows is None else int(block_rows)
        return stencil_apply(u, spec, steps=steps, engine=engine,
                             block_rows=br, interpret=interpret)
    return call


STENCIL_OP = register(EngineOp(
    name="stencil",
    traits=_traits,
    engines={
        "vector": _engine_fn("vector"),
        "matrix": _engine_fn("matrix"),
    },
    reference=_reference,
    make_inputs=_make_inputs,
    bench_sizes=(128, 256),
    test_size=48,
    doc="|S|-point stencil, t fused steps; I_t = t*|S|/D (paper Eq. 13)",
    # mesh split: leading-axis row blocks; t fused steps at radius r
    # need t*r halo rows from each neighbour (the Eq. 13 trapezoid),
    # which the sharding layer slices in and crops back out
    shard_kind="rowblock",
    shard_halo=lambda u, spec, steps=1, **kw: steps * spec.radius,
))


def stencil(u: jnp.ndarray, spec: StencilSpec, *, steps: int = 1,
            engine: str = "auto", block_rows: int = None,
            interpret: Optional[bool] = None) -> jnp.ndarray:
    """Apply `spec` for `steps` fused timesteps.

    'auto' consults the advisor with the *temporally blocked* intensity
    I_t = t * |S| / D (paper Eq. 13): shallow blocking stays memory-bound
    (vector engine), deep blocking can cross the knee.  ``block_rows``
    is the leading-axis tile height; None means the static default of
    128, which the kernel lowers to fit its VMEM budget.
    """
    kwargs = {} if block_rows is None else {"block_rows": block_rows}
    return STENCIL_OP(u, spec, steps=steps, engine=engine,
                      interpret=interpret, **kwargs)
