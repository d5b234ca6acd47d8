"""Mesh construction.

Every mesh here is built with ``jax.make_mesh`` and Auto axis types, and
entered with ``jax.set_mesh``.  ``make_production_mesh`` is a function
(never a module-level constant) so importing this module touches no jax
device state.  The single-pod mesh is 16x16 = 256 chips (one v5e pod);
multi-pod adds a leading "pod" axis for 2 pods = 512 chips.  The "pod"
and "data" axes are both data-parallel (gradients reduce over both);
"model" carries TP/EP.  ``data_mesh`` is the single-axis mesh the
virtual (shard-by-shard) kernel path runs under: it clamps to the
devices this process actually has, so a one-device process still
executes N-way ShardPlans — shard by shard — under a ``(1,)`` mesh.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


def host_device_count(n: int) -> int:
    """Make this process see at least *n* devices, or say why it cannot.

    The one entry point ``benchmarks.run sweep --mesh N --real``, the
    serving driver, and the multi-device tests share.  It sets
    ``--xla_force_host_platform_device_count=n`` in ``XLA_FLAGS``
    (replacing any stale value), which the CPU client reads when JAX
    first initializes, and then asks JAX for its devices.  A TPU host
    brings its own chips and ignores the flag.  Raises ``RuntimeError``
    with the fix when fewer than *n* devices exist -- on the CPU, that
    means JAX was initialized before this call.  Returns the device count
    the process sees.
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"host_device_count needs n >= 1, got {n}")
    old = os.environ.get("XLA_FLAGS", "")
    kept = [t for t in old.split() if not t.startswith(_HOST_COUNT_FLAG)]
    os.environ["XLA_FLAGS"] = " ".join(kept + [f"{_HOST_COUNT_FLAG}={n}"])
    devices = jax.devices()
    if len(devices) < n:
        os.environ["XLA_FLAGS"] = old  # it took no effect here
        raise RuntimeError(
            f"JAX already initialized with {len(devices)} "
            f"{devices[0].platform} device(s); {n} are needed. On a TPU "
            f"host, run on one with at least {n} chips. On the CPU, call "
            f"host_device_count({n}) (or export XLA_FLAGS="
            f"{_HOST_COUNT_FLAG}={n}) before the first jax.devices()/"
            f"computation — e.g. run the sweep via 'python -m "
            f"benchmarks.run sweep --mesh {n} --real', which does this "
            f"before touching JAX.")
    return len(devices)


def data_mesh(num_shards: int):
    """The 1-D "data" mesh for shard-by-shard kernel execution.

    Axis width = min(num_shards, available devices), never less than 1:
    the ShardPlan still splits ``num_shards`` ways, but the mesh only
    claims devices that exist (a single-device process gets ``(1,)``
    and runs shards back-to-back; the scheduler's shard-parallel
    accounting is what models the N-device roof).
    """
    width = max(1, min(int(num_shards), len(jax.devices())))
    return jax.make_mesh((width,), ("data",), axis_types=(AxisType.Auto,))


def make_production_mesh(*, multi_pod: bool = False):
    """The 256-chip single-pod (or 512-chip two-pod) serving mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for subprocess multi-device tests (8 host devices)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
