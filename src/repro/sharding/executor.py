"""Mesh-sharded execution of registry kernels via the dispatch layer.

The runtime half of :mod:`repro.sharding.plan`: a
:class:`ShardedExecutor` takes an op + call arguments, plans the split
(:func:`~repro.sharding.plan.plan_for`), and launches each shard
through ``repro.core.dispatch.DEFAULT_DISPATCHER`` under a
``jax.make_mesh`` data axis — so every per-shard launch gets the §6
engine decision and its family's static tile, exactly as an unsharded
call would.  Outputs are reassembled with
:func:`~repro.sharding.plan.combine_outputs` and must equal the
unsharded result bit-for-bit (halo rows carry the trapezoid dependency
of Eq. 13; data/head splits are independent).

Timing model: shards are launched sequentially in this process (the
container exposes one XLA device), each shard's wall time is measured,
and :class:`ShardRun` reports both the serial sum and the
``parallel_s`` maximum — what an N-device mesh would charge the
virtual serving clock when the shards run side by side.  That is the
honest off-hardware analogue of the paper's §5 methodology: per-shard
*correctness* is real, per-shard *time* is measured, and the
N-way-parallel claim is the max-reduction the scheduler accounts, not
a pretended speedup of the host.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from ..core.dispatch import DEFAULT_DISPATCHER, Dispatcher, default_cache_key
from ..core.timing import time_fn
from ..obs.trace import TRACER
from ..launch.mesh import data_mesh
from .collective_matmul import rowparallel_matmul, weight_gathered_matmul
from .plan import (ShardPlan, combine_outputs, first_array, plan_for,
                   shard_call)

__all__ = ["MeshExecutor", "MeshRun", "ShardRun", "ShardedExecutor"]


@dataclasses.dataclass(frozen=True)
class ShardRun:
    """One sharded execution: the combined output + per-shard times."""

    out: Any
    plan: ShardPlan
    shard_seconds: Tuple[float, ...]

    @property
    def parallel_s(self) -> float:
        """Wall time an N-way mesh is charged: the slowest shard."""
        return max(self.shard_seconds) if self.shard_seconds else 0.0

    @property
    def serial_s(self) -> float:
        """Total measured compute across shards (host wall time)."""
        return float(sum(self.shard_seconds))


class ShardedExecutor:
    """Run registry kernels shard-by-shard under a data-axis mesh.

    The execution engine behind ``benchmarks.run sweep --mesh N`` and
    the serving batcher's shard-parallel packing: plans once per call
    shape, launches every shard through the dispatcher (memoized §6
    Advice + static tiles per shard), and reassembles the exact
    unsharded result.  ``engine``/``interpret`` follow the dispatch
    layer's conventions; ``num_shards=1`` degrades to a plain
    dispatched call wrapped in the same timing envelope.
    """

    def __init__(self, num_shards: int, *, engine: str = "auto",
                 interpret: Optional[bool] = None, dispatcher=None):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        self.num_shards = int(num_shards)
        self.engine = engine
        self.interpret = interpret
        self.dispatcher = (dispatcher if dispatcher is not None
                           else DEFAULT_DISPATCHER)
        self._flat = None  # lazy mesh-1 view of self.dispatcher
        self._mesh = data_mesh(self.num_shards)  # fixed per executor

    def _shard_dispatcher(self):
        """The dispatcher per-shard launches go through.

        A shard's launch is already the split — advising it under a
        mesh-configured dispatcher would plan a bogus sub-split onto
        its memoized Advice.  When the backing dispatcher has a mesh
        set, shards run through a flat (mesh-1) view sharing its
        advisor, so §6 routing is identical and only the shard-spec
        planning is skipped.
        """
        if self.dispatcher.mesh_shards == 1:
            return self.dispatcher
        if self._flat is None:
            self._flat = Dispatcher(advisor=self.dispatcher.advisor)
        return self._flat

    def mesh(self):
        """The data-axis mesh shard launches run under (built once —
        the shard count is fixed per executor, and serving calls this
        on the timed compute path)."""
        return self._mesh

    def plan(self, op, *args, **kwargs) -> ShardPlan:
        """The ShardPlan this executor would use for one call."""
        return plan_for(op, self.num_shards, *args, **kwargs)

    def run(self, op, *args, engine: Optional[str] = None,
            plan: Optional[ShardPlan] = None, **kwargs) -> ShardRun:
        """Plan, launch every shard via dispatch, and reassemble.

        Each shard's launch is a normal ``Dispatcher.run`` — §6 engine
        routing included — timed individually so
        callers can account the shard-parallel (max) or serial (sum)
        cost.  Pass *plan* to reuse a prior plan across calls of the
        same shape (the serving batcher's steady-state path).
        """
        eng = self.engine if engine is None else engine
        if plan is None:
            plan = self.plan(op, *args, **kwargs)
        dispatcher = self._shard_dispatcher()
        outputs, times = [], []
        with TRACER.span("shard_run", layer="mesh", kernel=op.name,
                         kind=plan.spec.kind, shards=len(plan.shards)):
            with jax.set_mesh(self.mesh()):
                for i, shard in enumerate(plan.shards):
                    sargs, skw = shard_call(plan, shard, args, kwargs)
                    t0 = time.perf_counter()
                    out = dispatcher.run(op, *sargs, engine=eng,
                                         interpret=self.interpret,
                                         **skw)
                    jax.block_until_ready(out)
                    dt = time.perf_counter() - t0
                    # emitted with the measured times: span == sample
                    TRACER.emit("shard", layer="mesh", start_s=t0,
                                dur_s=dt, kernel=op.name, shard=i)
                    times.append(dt)
                    outputs.append(out)
            template = None
            if plan.spec.kind == "data":
                template = first_array(args)
            with TRACER.span("reassembly", layer="mesh", kernel=op.name):
                combined = combine_outputs(plan, outputs,
                                           template=template)
        return ShardRun(out=combined, plan=plan,
                        shard_seconds=tuple(times))


# --------------------------------------------------------------------------
# real mesh execution (shard_map over N host devices)
# --------------------------------------------------------------------------

def _is_arrayish(x: Any) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype")


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class MeshRun:
    """One real-mesh execution: combined output + measured wall time.

    Unlike :class:`ShardRun` (per-shard serial launches summed/maxed on
    a virtual clock), ``wall_s`` here is the *measured* wall time of
    one ``shard_map`` call over ``devices`` actual XLA devices — the
    shards genuinely ran side by side, halo rows genuinely crossed the
    mesh via ``ppermute``.  ``out_sharding`` is the sharding of the
    mesh program's own output, before the padding is cropped off: the
    proof of which devices the step actually ran on.
    """

    out: Any
    plan: ShardPlan
    devices: int
    wall_s: float
    out_sharding: Any = None

    @property
    def parallel_s(self) -> float:
        """Batcher-compatible alias: shard-parallel time IS the wall."""
        return self.wall_s


class _Lowered:
    """One compiled mesh program: prep -> shard_map fn -> postprocess.

    ``prep`` pads/flattens live call arrays into the uniform per-device
    blocks ``shard_map`` needs; ``fn`` is the jitted multi-device
    program; ``post`` crops the padding back off.  ``collective`` is
    the halo-exchange-only twin program (the ``ppermute`` ring with a
    reduction to defeat DCE and nothing else) used to measure the
    collective's own wall time; None when the plan wires no bytes.
    """

    def __init__(self, width: int, prep: Callable, fn: Callable,
                 post: Callable, collective: Optional[Callable] = None):
        self.width = width
        self.prep = prep
        self.fn = fn
        self.post = post
        self.collective = collective
        self.warmed = False


class MeshExecutor:
    """Run registry kernels through ``shard_map`` on a real device mesh.

    The measured counterpart of :class:`ShardedExecutor`: where that
    class launches shards serially on one device and *models* the
    N-way-parallel time as ``max(shard times)``, this one lowers the
    same :class:`~repro.sharding.plan.ShardPlan` to one ``shard_map``
    program over ``num_shards`` actual XLA host devices
    (``--xla_force_host_platform_device_count``, see
    :func:`repro.launch.mesh.host_device_count`) and measures the wall
    time of the whole mesh step — compute and collectives overlapped
    by XLA's scheduler, per the paper's §4.1 lesson.

    Per shard kind:

    * ``data`` — arrays flatten, zero-pad to ``N x L``, and split
      ``P('data')``; each device runs the family's XLA reference on
      its block (elementwise, so padding is inert and cropped after).
    * ``rowblock`` + halo (stencil) — each device owns ``L`` rows and
      borrows ``halo = t·r`` rows from each neighbour via two
      ``ppermute`` rings (edge devices receive zeros = the domain's
      zero boundary), then applies ``t`` fused reference steps with a
      *global-row* domain mask: out-of-domain rows re-zero after every
      step, exactly like the Pallas kernel's domain mask, so
      owned rows are exact despite the halo rows going progressively
      stale (the Eq. 13 trapezoid).
    * ``rowblock`` without halo (block-ELL SpMV) — block-rows split
      ``P('data')`` with ``x`` replicated; each device contracts its
      blocks against its gathered ``x`` slices.
    * ``head`` (decode attention) — KV heads split (q on axis 1, k/v
      on axis 2); heads are independent, no exchange.

    Timing methodology: the bodies are XLA-native (reference math, the
    same computation ``ref_us_per_call`` times) — interpret-mode
    Pallas inside ``shard_map`` would measure the emulator, not the
    mesh.  Per-engine *correctness* under sharding stays with
    :class:`ShardedExecutor`; this class is where shard-parallel
    *time* and collective cost become measurements.
    """

    def __init__(self, num_shards: int, *, dispatcher=None):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        self.num_shards = int(num_shards)
        self.dispatcher = (dispatcher if dispatcher is not None
                           else DEFAULT_DISPATCHER)
        devices = jax.devices()
        n = self.num_shards
        if len(devices) < n:
            raise RuntimeError(
                f"MeshExecutor({n}) needs {n} devices but this process "
                f"has {len(devices)} ({devices[0].platform}). On a TPU "
                f"host, run on one with at least {n} chips. On the CPU, "
                f"force a multi-device host platform before JAX "
                f"initializes: repro.launch.mesh.host_device_count({n}), "
                f"or export XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n} "
                f"(benchmarks.run's --real flag does this for you).")
        self._lowered_cache: Dict[Any, _Lowered] = {}

    def plan(self, op, *args, **kwargs) -> ShardPlan:
        """The ShardPlan this executor lowers for one call."""
        return plan_for(op, self.num_shards, *args, **kwargs)

    # -- lowering ----------------------------------------------------------

    def _mesh(self, width: int, axis: str = "data"):
        return jax.make_mesh((width,), (axis,),
                             axis_types=(AxisType.Auto,))

    def _lowered(self, op, plan: ShardPlan, args: tuple,
                 kwargs: dict) -> _Lowered:
        key = (op.name, plan.spec, default_cache_key(*args, **kwargs))
        low = self._lowered_cache.get(key)
        if low is None:
            kind = plan.spec.kind
            if kind == "data":
                low = self._lower_data(op, plan, args, kwargs)
            elif kind == "rowblock" and hasattr(args[0], "blocks"):
                low = self._lower_bell(op, plan, args, kwargs)
            elif kind == "rowblock" and plan.spec.halo > 0:
                low = self._lower_stencil(op, plan, args, kwargs)
            elif kind == "rowblock":
                low = self._lower_rows(op, plan, args, kwargs)
            else:
                low = self._lower_head(op, plan, args, kwargs)
            self._lowered_cache[key] = low
        return low

    def _lower_data(self, op, plan, args, kwargs) -> _Lowered:
        width = plan.spec.num_shards
        mesh = self._mesh(width)
        arr_idx = [i for i, a in enumerate(args) if _is_arrayish(a)]
        template = args[arr_idx[0]]
        n = int(template.size)
        padded = width * _ceil_div(n, width)
        statics = tuple(args)

        def body(*locs):
            call = list(statics)
            for i, loc in zip(arr_idx, locs):
                call[i] = loc
            return op.reference(*call, **kwargs)

        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(P("data"),) * len(arr_idx),
                                   out_specs=P("data"), check_vma=False))

        def prep(live):
            flats = []
            for i in arr_idx:
                f = jnp.asarray(live[i]).reshape(-1)
                if padded > n:
                    f = jnp.pad(f, (0, padded - n))
                flats.append(f)
            return tuple(flats)

        def post(out):
            return out.reshape(-1)[:n].reshape(template.shape)

        return _Lowered(width, prep, fn, post)

    def _lower_bell(self, op, plan, args, kwargs) -> _Lowered:
        width = plan.spec.num_shards
        mesh = self._mesh(width)
        bell, rest = args[0], args[1:]
        nbr = int(bell.blocks.shape[0])
        bm, bn = bell.bm, bell.bn
        padded = width * _ceil_div(nbr, width)

        def body(blocks_loc, cols_loc, x):
            # gather each block's x slice, contract, flatten to rows
            xb = x.reshape(-1, bn)[cols_loc]
            y = jnp.einsum("ijab,ijb->ia", blocks_loc, xb)
            return y.reshape(-1)

        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(P("data"), P("data"), P()),
                                   out_specs=P("data"), check_vma=False))

        def prep(live):
            b = live[0]
            blocks, cols = b.blocks, b.cols
            if padded > nbr:
                grow = padded - nbr
                blocks = jnp.pad(blocks,
                                 ((0, grow), (0, 0), (0, 0), (0, 0)))
                cols = jnp.pad(cols, ((0, grow), (0, 0)))
            return (blocks, cols, live[1])

        def post(out):
            return out[:nbr * bm]

        return _Lowered(width, prep, fn, post)

    def _lower_stencil(self, op, plan, args, kwargs) -> _Lowered:
        from ..kernels.stencil.ref import _shift_zero

        width = plan.spec.num_shards
        halo = plan.spec.halo
        mesh = self._mesh(width)
        u, spec = args[0], args[1]
        steps = int(kwargs.get("steps", 1))
        true_rows = int(u.shape[0])
        block = _ceil_div(true_rows, width)
        if halo > block:
            raise ValueError(
                f"stencil halo {halo} exceeds the {block} rows each of "
                f"{width} shards owns; a ppermute neighbour exchange "
                f"cannot reach {halo} rows away — use fewer shards or a "
                f"larger domain")
        padded = width * block
        fwd = [(j, j + 1) for j in range(width - 1)]
        bwd = [(j + 1, j) for j in range(width - 1)]

        def body(uloc):
            idx = jax.lax.axis_index("data")
            # ring halo exchange; edge devices receive zeros, which is
            # exactly the domain's zero boundary extended past the edge
            lo = jax.lax.ppermute(uloc[-halo:], "data", fwd)
            hi = jax.lax.ppermute(uloc[:halo], "data", bwd)
            tile = jnp.concatenate([lo, uloc, hi], axis=0)
            row0 = idx * block - halo
            rows = row0 + jnp.arange(tile.shape[0])
            in_dom = (rows >= 0) & (rows < true_rows)
            mask = in_dom.reshape((-1,) + (1,) * (tile.ndim - 1))
            mask = mask.astype(tile.dtype)
            for _ in range(steps):
                acc = jnp.zeros_like(tile)
                for off, w in zip(spec.offsets, spec.weights):
                    acc = acc + jnp.asarray(w, tile.dtype) \
                        * _shift_zero(tile, off)
                # re-zero out-of-domain rows with *global* indices:
                # pad rows and zero-halo rows must keep acting as the
                # boundary, or steps > 1 corrupt the owned interior
                tile = acc * mask
            return tile[halo:halo + block]

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                                   out_specs=P("data"), check_vma=False))

        def coll_body(uloc):
            lo = jax.lax.ppermute(uloc[-halo:], "data", fwd)
            hi = jax.lax.ppermute(uloc[:halo], "data", bwd)
            # reduce so the transfers cannot be dead-code-eliminated
            return (lo.sum() + hi.sum()).reshape(1)

        collective = jax.jit(jax.shard_map(
            coll_body, mesh=mesh, in_specs=(P("data"),),
            out_specs=P("data"), check_vma=False)) if width > 1 else None

        def prep(live):
            up = jnp.asarray(live[0])
            if padded > true_rows:
                pads = [(0, padded - true_rows)] + [(0, 0)] * (up.ndim - 1)
                up = jnp.pad(up, pads)
            return (up,)

        def post(out):
            return out[:true_rows]

        return _Lowered(width, prep, fn, post, collective)

    def _lower_rows(self, op, plan, args, kwargs) -> _Lowered:
        """Halo-free rowblock fallback: leading rows split, rest rides."""
        width = plan.spec.num_shards
        mesh = self._mesh(width)
        first, rest = args[0], args[1:]
        rows = int(first.shape[0])
        padded = width * _ceil_div(rows, width)

        def body(loc):
            return op.reference(loc, *rest, **kwargs)

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                                   out_specs=P("data"), check_vma=False))

        def prep(live):
            a = jnp.asarray(live[0])
            if padded > rows:
                pads = [(0, padded - rows)] + [(0, 0)] * (a.ndim - 1)
                a = jnp.pad(a, pads)
            return (a,)

        def post(out):
            return out[:rows]

        return _Lowered(width, prep, fn, post)

    def _lower_head(self, op, plan, args, kwargs) -> _Lowered:
        width = plan.spec.num_shards
        mesh = self._mesh(width)
        q, k, v = args[0], args[1], args[2]
        rest = args[3:]
        heads = int(q.shape[1])
        padded = width * _ceil_div(heads, width)
        head_spec = P(None, "data", None, None)
        kv_spec = P(None, None, "data", None)

        def body(ql, kl, vl):
            return op.reference(ql, kl, vl, *rest, **kwargs)

        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(head_spec, kv_spec, kv_spec),
                                   out_specs=head_spec, check_vma=False))

        def prep(live):
            ql, kl, vl = live[0], live[1], live[2]
            if padded > heads:
                grow = padded - heads
                ql = jnp.pad(ql, ((0, 0), (0, grow), (0, 0), (0, 0)))
                kl = jnp.pad(kl, ((0, 0), (0, 0), (0, grow), (0, 0)))
                vl = jnp.pad(vl, ((0, 0), (0, 0), (0, grow), (0, 0)))
            return (ql, kl, vl)

        def post(out):
            return out[:, :heads]

        return _Lowered(width, prep, fn, post)

    # -- execution ---------------------------------------------------------

    def run(self, op, *args, engine: Optional[str] = None,
            plan: Optional[ShardPlan] = None, **kwargs) -> MeshRun:
        """One measured mesh step: warm (compile) once, then time one call.

        ``engine`` is accepted for :class:`ShardedExecutor` drop-in
        compatibility and ignored: the mesh bodies are XLA-native
        reference math, engine-independent by construction (Pallas
        interpret mode inside ``shard_map`` would time the emulator).
        """
        del engine
        if plan is None:
            plan = self.plan(op, *args, **kwargs)
        low = self._lowered(op, plan, args, kwargs)
        with TRACER.span("mesh_run", layer="mesh", kernel=op.name,
                         devices=self.num_shards, kind=plan.spec.kind):
            with TRACER.span("pad_prep", layer="mesh", kernel=op.name):
                prepared = low.prep(args)
            if not low.warmed:
                with TRACER.span("warmup", layer="mesh", kernel=op.name):
                    jax.block_until_ready(low.fn(*prepared))
                low.warmed = True
            t0 = time.perf_counter()
            out = low.fn(*prepared)
            jax.block_until_ready(out)
            wall = time.perf_counter() - t0
            TRACER.emit("mesh_step", layer="mesh", start_s=t0, dur_s=wall,
                        kernel=op.name, devices=low.width)
        return MeshRun(out=low.post(out), plan=plan, devices=low.width,
                       wall_s=wall, out_sharding=out.sharding)

    def measure(self, op, *args, plan: Optional[ShardPlan] = None,
                **kwargs) -> Dict[str, float]:
        """The schema-6 ``mesh_exec`` evidence for one call.

        Three measurements, all median-of-iterations via
        :func:`repro.core.timing.time_fn`:

        * ``mesh_wall_us`` — the full ``shard_map`` step over the real
          mesh (compute + collectives, overlapped by XLA),
        * ``collective_us`` — the halo-exchange-only twin program
          (``ppermute`` rings + a defeat-DCE reduction); 0.0 when the
          plan wires no bytes (``traffic()['wire_bytes'] == 0``),
        * ``virtual_us`` — the PR 5 virtual-clock analogue restated
          with the same XLA-native math: the slowest shard's
          single-device reference wall time (``max`` over shards), so
          the real-vs-virtual skew compares like against like.
        """
        if plan is None:
            plan = self.plan(op, *args, **kwargs)
        low = self._lowered(op, plan, args, kwargs)
        with TRACER.span("mesh_measure", layer="mesh", kernel=op.name,
                         devices=self.num_shards, kind=plan.spec.kind):
            with TRACER.span("pad_prep", layer="mesh", kernel=op.name):
                prepared = low.prep(args)
            t_mesh = time_fn(lambda: low.fn(*prepared),
                             label="mesh_step", layer="mesh",
                             kernel=op.name, devices=self.num_shards)
            low.warmed = True
            collective_us = 0.0
            if low.collective is not None:
                collective_us = time_fn(
                    lambda: low.collective(*prepared),
                    label="collective", layer="mesh",
                    kernel=op.name, devices=self.num_shards).median_us
            shard_us = []
            for shard_idx, shard in enumerate(plan.shards):
                sa, skw = shard_call(plan, shard, args, kwargs)
                arr_idx = [i for i, x in enumerate(sa) if _is_arrayish(x)]
                statics = tuple(sa)

                def local(*arrs, _statics=statics, _idx=tuple(arr_idx),
                          _kw=skw):
                    call = list(_statics)
                    for i, a in zip(_idx, arrs):
                        call[i] = a
                    return op.reference(*call, **_kw)

                fn = jax.jit(local)
                arrs = tuple(sa[i] for i in arr_idx)
                shard_us.append(time_fn(
                    lambda: fn(*arrs), label="shard_ref", layer="mesh",
                    kernel=op.name, shard=shard_idx).median_us)
        virtual_us = max(shard_us) if shard_us else 0.0
        return {
            "mode": "mesh",
            "devices": int(low.width),
            "mesh_wall_us": round(t_mesh.median_us, 1),
            "mesh_iqr_us": round(t_mesh.iqr_us, 1),
            "collective_us": round(collective_us, 1),
            "virtual_us": round(virtual_us, 1),
            "skew": round(t_mesh.median_us / virtual_us, 4)
            if virtual_us > 0 else 0.0,
        }

    def overlap_probe(self, *, rows: int = 128, contract: int = 2048,
                      cols: int = 256, seed: int = 0) -> Dict[str, float]:
        """Measure §4.1's lesson on the live mesh: overlapped vs. not.

        Times :func:`~repro.sharding.collective_matmul.
        weight_gathered_matmul` (weight shards rotate a ``ppermute``
        ring, every hop's partial matmul overlaps the in-flight
        transfer) against the serialized formulation ``x @
        all_gather(w)`` (the MXU waits for the whole gather), plus the
        :func:`rowparallel_matmul` ring-accumulation variant — all on
        this executor's real device mesh, numerics asserted against
        the unsharded product.  ``overlap_gain`` is
        serialized/overlapped wall time: ≥ ~1 means the scheduler hid
        the ring behind compute, the measured form of "fully
        overlapped communication is free".
        """
        import numpy as np

        width = self.num_shards
        contract = width * _ceil_div(contract, width)
        mesh = self._mesh(width, "model")
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((rows, contract)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((contract, cols)), jnp.float32)
        want = np.asarray(x @ w)

        ring = jax.jit(
            lambda a, b: weight_gathered_matmul(a, b, mesh, "model"))
        rowpar = jax.jit(
            lambda a, b: rowparallel_matmul(a, b, mesh, "model"))

        def serial_body(xl, wl):
            wg = jax.lax.all_gather(wl, "model", axis=0, tiled=True)
            return xl @ wg

        serial = jax.jit(jax.shard_map(serial_body, mesh=mesh,
                                       in_specs=(P(), P("model", None)),
                                       out_specs=P(), check_vma=False))

        for name, fn in (("ring", ring), ("serialized", serial),
                         ("rowparallel", rowpar)):
            got = np.asarray(fn(x, w))
            err = float(np.max(np.abs(got - want)))
            if err > 1e-2:
                raise AssertionError(
                    f"overlap probe {name} diverged from x @ w "
                    f"(max err {err:.3g})")
        t_ring = time_fn(lambda: ring(x, w))
        t_serial = time_fn(lambda: serial(x, w))
        t_rowpar = time_fn(lambda: rowpar(x, w))
        return {
            "devices": int(width),
            "shape": [rows, contract, cols],
            "ring_us": round(t_ring.median_us, 1),
            "serialized_us": round(t_serial.median_us, 1),
            "rowparallel_us": round(t_rowpar.median_us, 1),
            "overlap_gain": round(
                t_serial.median_us / t_ring.median_us, 3)
            if t_ring.median_us > 0 else 0.0,
        }
