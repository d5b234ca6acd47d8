"""Pallas TPU stencil kernels, one step-function per engine (paper §5.3).

TPU adaptation (DESIGN.md §2.3):
  * VPU kernel = the EBISU/Brick role: shifted adds on a VMEM tile, with
    in-kernel *temporal blocking* (t fused steps, trapezoid halo t*r).
    Shifts are lane/sublane rotations (``pltpu.roll``), never padded
    copies of the tile.
  * MXU kernel = the ConvStencil role re-thought for a 128x128 systolic
    array: each fused step is a set of *banded-matrix multiplications*
    (star: one 1D pass per axis + center term; separable box: product of
    1D passes).  The lane axis and (3-D) the middle axis run on the MXU;
    the leading axis of a 3-D grid has no tiled layout to contract on, so
    its 1D pass is a weighted sum of neighbouring planes.  W inflates
    from 2|S| to ~2*sum(tile dims) per point -- exactly the compute-waste
    the paper's roofline analysis prices in.

Tiling (sized from a VMEM budget, so the tile never depends on the grid
size): the grid is (leading blocks, lane blocks).  Each program DMAs one
overlapping window -- its output block plus a halo on the leading and
lane axes -- from the zero-padded array in HBM into VMEM, then runs the
fused steps over that window strip by strip (8 rows for a 2-D grid, one
plane for a 3-D grid) in a ``fori_loop``, so the compiled body stays
small whatever the block size.  A 3-D grid keeps its middle axis whole.
Zero boundary conditions are enforced exactly by re-masking the domain
after every fused step.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.dispatch import mxu_dot, use_interpreter
from .defs import StencilSpec
from .ref import banded_matrix

LANE, SUBLANE = 128, 8
#: Lane width of one output block when the lane axis is tiled.
LANE_TILE = 512
#: VMEM the tile planner may fill (window buffers + pipelined blocks).
VMEM_BUDGET = 40 * 2**20
#: Scoped-VMEM limit handed to Mosaic (a v5e core has 128 MiB).
VMEM_LIMIT = 64 * 2**20


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class _Tiling:
    """Static tile plan: how the padded grid maps onto windows/blocks."""

    b0: int          # output rows (2-D) / planes (3-D) per program
    lead_halo: int   # leading-axis halo in the window (>= t*r)
    slack: int       # zero rows/planes around the window in VMEM
    n0: int          # leading blocks
    mid: int         # padded middle axis (3-D), 0 for 2-D
    bo: int          # output lanes per program
    width: int       # window lanes
    lane_lo: int     # zero lanes left of the domain in the padded array
    n_lanes: int     # lane blocks

    @property
    def window(self) -> int:
        return self.b0 + 2 * self.lead_halo

    @property
    def buffer(self) -> int:
        return self.window + 2 * self.slack

    @property
    def lane_off(self) -> int:
        """Window lane of the output block's first lane."""
        return (self.width - self.bo) // 2


def _plan(shape: Tuple[int, ...], spec: StencilSpec, steps: int,
          block_rows: int, itemsize: int, engine: str) -> _Tiling:
    nd = len(shape)
    halo = steps * spec.radius
    n_last = shape[-1]
    if _round_up(n_last + 2 * halo, LANE) <= LANE_TILE + 2 * LANE:
        width = _round_up(n_last + 2 * halo, LANE)  # one lane block
        bo, lane_lo, n_lanes = width, halo, 1
    else:
        bo, lane_lo = LANE_TILE, _round_up(halo, LANE)
        width = bo + 2 * lane_lo
        n_lanes = -(-n_last // bo)
    mid = _round_up(shape[1] + 2 * halo, SUBLANE) if nd == 3 else 0
    if nd == 2:   # the leading axis is the sublane axis: 8-row aligned
        lead_halo, slack, align = _round_up(halo, SUBLANE), SUBLANE, SUBLANE
    else:         # an untiled plane axis: any offset is free
        lead_halo, slack, align = halo, spec.radius, 1
    row_bytes = (mid or 1) * itemsize
    fixed = 0
    if engine == "matrix":  # resident band matrices, double-buffered
        fixed = 2 * itemsize * (width * width + (mid * mid if nd == 3
                                                 else SUBLANE * 3 * SUBLANE))
    n_bufs = 1 if steps == 1 else 2
    b0 = max(align, min(_round_up(block_rows, align),
                        _round_up(shape[0], align)))
    while b0 > align:
        need = (n_bufs * (b0 + 2 * (lead_halo + slack)) * width
                + 2 * b0 * bo) * row_bytes + fixed
        if need <= VMEM_BUDGET:
            break
        b0 = max(align, _round_up(b0 // 2, align))
    return _Tiling(b0=b0, lead_halo=lead_halo, slack=slack,
                   n0=-(-shape[0] // b0), mid=mid, bo=bo, width=width,
                   lane_lo=lane_lo, n_lanes=n_lanes)


def _shift(x: jnp.ndarray, d: int, axis: int) -> jnp.ndarray:
    """out[p] = x[p + d] along a tiled axis, wrapping at the tile edge.

    Wrapped values only ever land in halo or padding cells, which the
    trapezoid discards or the domain mask re-zeroes.
    """
    if d == 0:
        return x
    axis %= x.ndim
    return pltpu.roll(x, (-d) % x.shape[axis], axis)


def _tap_groups(spec: StencilSpec) -> Dict[Tuple[int, ...],
                                           List[Tuple[int, float]]]:
    """Offsets grouped by everything but the lane shift (shared rolls)."""
    groups: Dict[Tuple[int, ...], List[Tuple[int, float]]] = {}
    for off, w in zip(spec.offsets, spec.weights):
        groups.setdefault(tuple(off[:-1]), []).append((off[-1], float(w)))
    return groups


def _stencil_kernel(x_hbm, *rest, spec: StencilSpec, engine: str,
                    steps: int, tl: _Tiling, true_shape: Tuple[int, ...]):
    nd = len(true_shape)
    n_bufs = 1 if steps == 1 else 2
    bands = rest[:-1 - n_bufs]
    o_ref, bufs = rest[-1 - n_bufs], rest[-n_bufs:]
    i, j = pl.program_id(0), pl.program_id(1)
    dtype = o_ref.dtype
    halo = steps * spec.radius
    lead0 = i * tl.b0 - tl.lead_halo - tl.slack   # global index of buf[0]
    lane0 = j * tl.bo - tl.lane_lo                # global index of lane 0
    strip = SUBLANE if nd == 2 else 1
    groups = _tap_groups(spec)

    # window (output block + halos) from HBM; zero slack around it
    lanes = pl.ds(pl.multiple_of(j * tl.bo, LANE), tl.width)
    lead = pl.ds(pl.multiple_of(i * tl.b0, strip), tl.window)
    src = x_hbm.at[lead, lanes] if nd == 2 else x_hbm.at[lead, :, lanes]
    for buf in bufs:
        zeros = jnp.zeros((tl.slack, *buf.shape[1:]), dtype)
        buf[:tl.slack] = zeros
        buf[tl.buffer - tl.slack:] = zeros
    pltpu.sync_copy(src, bufs[0].at[pl.ds(tl.slack, tl.window)])

    def new_values(buf, s):
        """Next-step values of strip s, before the domain mask."""
        if nd == 2:   # 8-row strip from an aligned 24-row slab
            slab = buf[pl.ds(pl.multiple_of((s - 1) * SUBLANE, SUBLANE),
                             3 * SUBLANE)]
            fetch = lambda d: _shift(slab, d, 0)[SUBLANE:2 * SUBLANE]
        else:         # one plane and its neighbours
            fetch = lambda d: buf[s + d]
        if engine == "vector":
            acc = None
            for key, taps in groups.items():
                x = fetch(key[0])
                if nd == 3:
                    x = _shift(x, key[1], 0)
                for dx, w in taps:
                    term = w * _shift(x, dx, -1)
                    acc = term if acc is None else acc + term
            return acc
        # matrix engine: 1D banded passes (leading pass on the MXU in
        # 2-D via the strip's slab, a weighted plane sum in 3-D)
        w0 = spec.axis_weights[0]
        r = spec.radius
        if nd == 2:
            lead_pass = mxu_dot(bands[0][...], slab)
        else:
            lead_pass = sum(w0[d + r] * fetch(d) for d in range(-r, r + 1)
                            if w0[d + r] != 0.0)
        mid_pass = (lambda x: mxu_dot(bands[0][...], x)) if nd == 3 else None
        lane_pass = lambda x: mxu_dot(x, bands[-1][...])
        if spec.kind == "star":
            c = fetch(0)
            acc = spec.center * c + lead_pass + lane_pass(c)
            return acc + mid_pass(c) if mid_pass else acc
        y = mid_pass(lead_pass) if mid_pass else lead_pass
        return lane_pass(y)

    def masked(vals, s):
        g_lead = lead0 + s * strip
        if nd == 2:
            g_lead = g_lead + jax.lax.broadcasted_iota(jnp.int32,
                                                       vals.shape, 0)
        ok = (g_lead >= 0) & (g_lead < true_shape[0])
        if nd == 3:
            y = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
            ok = ok & (y >= halo) & (y < halo + true_shape[1])
        g_lane = lane0 + jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
        ok = ok & (g_lane >= 0) & (g_lane < true_shape[-1])
        return jnp.where(ok, vals, jnp.zeros_like(vals)).astype(dtype)

    first = tl.slack // strip                    # first window strip
    n_strips = tl.window // strip
    for k in range(steps - 1):
        src_buf, dst_buf = bufs[k % 2], bufs[(k + 1) % 2]

        def body(s, carry, src_buf=src_buf, dst_buf=dst_buf):
            v = masked(new_values(src_buf, s), s)
            if nd == 2:
                dst_buf[pl.ds(pl.multiple_of(s * SUBLANE, SUBLANE),
                              SUBLANE)] = v
            else:
                dst_buf[s] = v
            return carry

        jax.lax.fori_loop(first, first + n_strips, body, 0)

    last = bufs[(steps - 1) % 2]
    out0 = (tl.slack + tl.lead_halo) // strip    # strip of output row 0

    def out_body(s, carry):
        v = masked(new_values(last, s), s)[..., tl.lane_off:
                                           tl.lane_off + tl.bo]
        if nd == 2:
            o_ref[pl.ds(pl.multiple_of((s - out0) * SUBLANE, SUBLANE),
                        SUBLANE)] = v
        else:
            o_ref[s - out0] = v
        return carry

    jax.lax.fori_loop(out0, out0 + tl.b0 // strip, out_body, 0)


def _bands(spec: StencilSpec, tl: _Tiling, nd: int, dtype) -> List:
    """The banded matrices the matrix engine multiplies by (constants)."""
    def band(ax: int, size: int) -> np.ndarray:
        return banded_matrix(spec.axis_weights[ax], size)

    out = []
    if nd == 2:   # (8, 24): slab rows -> strip rows
        out.append(band(0, 3 * SUBLANE).T[SUBLANE:2 * SUBLANE])
    else:         # (mid, mid), applied from the left
        out.append(band(1, tl.mid).T)
    out.append(band(nd - 1, tl.width))
    return [jnp.asarray(m, dtype) for m in out]


@functools.partial(
    jax.jit, static_argnames=("spec", "steps", "engine", "block_rows",
                              "interpret"))
def stencil_apply(u: jnp.ndarray, spec: StencilSpec, *, steps: int = 1,
                  engine: str = "vector", block_rows: int = 128,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Apply `spec` to u for `steps` fused timesteps on the chosen engine."""
    assert u.ndim == spec.ndim and u.ndim in (2, 3), (u.shape, spec.name)
    nd = u.ndim
    halo = steps * spec.radius
    tl = _plan(u.shape, spec, steps, block_rows, u.dtype.itemsize, engine)
    assert halo <= tl.lead_halo and halo <= tl.lane_lo, (halo, tl)

    # zero-pad: leading halo + whole blocks, middle halo, lane halo
    lead_hi = tl.n0 * tl.b0 - u.shape[0] + tl.lead_halo
    lane_hi = (tl.n_lanes - 1) * tl.bo + tl.width - tl.lane_lo - u.shape[-1]
    pads = [(tl.lead_halo, lead_hi)]
    if nd == 3:
        pads.append((halo, tl.mid - u.shape[1] - halo))
    pads.append((tl.lane_lo, lane_hi))
    up = jnp.pad(u, pads)

    mid = (tl.mid,) if nd == 3 else ()
    zeros = (0,) * len(mid)
    bands = _bands(spec, tl, nd, u.dtype) if engine == "matrix" else []
    band_specs = [pl.BlockSpec(b.shape, lambda i, j: (0, 0)) for b in bands]
    kernel = functools.partial(_stencil_kernel, spec=spec, engine=engine,
                               steps=steps, tl=tl, true_shape=u.shape)
    out = pl.pallas_call(
        kernel,
        grid=(tl.n0, tl.n_lanes),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + band_specs,
        out_specs=pl.BlockSpec((tl.b0, *mid, tl.bo),
                               lambda i, j: (i, *zeros, j)),
        out_shape=jax.ShapeDtypeStruct(
            (tl.n0 * tl.b0, *mid, tl.n_lanes * tl.bo), u.dtype),
        scratch_shapes=[pltpu.VMEM((tl.buffer, *mid, tl.width), u.dtype)
                        for _ in range(1 if steps == 1 else 2)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=use_interpreter(interpret),
    )(up, *bands)

    lane_start = tl.lane_lo - tl.lane_off
    sl = [slice(0, u.shape[0])]
    if nd == 3:
        sl.append(slice(halo, halo + u.shape[1]))
    sl.append(slice(lane_start, lane_start + u.shape[-1]))
    return out[tuple(sl)]
