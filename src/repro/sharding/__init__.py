"""Mesh-sharded execution layer: split kernels, keep the paper's verdict.

The paper's Eq. 23/24 ceiling on matrix-engine speedups for
memory-bound kernels is a per-device statement; this package carries
it across a device mesh.  :mod:`repro.sharding.plan` describes *how* a
registered kernel call splits (data / rowblock-with-halo / head — one
kind per §3 family shape) and accounts the traffic each shard moves;
:mod:`repro.sharding.executor` executes the split two ways —
:class:`ShardedExecutor` launches shards serially through the engine
dispatcher under a ``jax.make_mesh`` data axis and *models* the
N-way clock (max over shards), while :class:`MeshExecutor` lowers the
same plan to one ``shard_map`` program over N **real** XLA host
devices and *measures* the wall time, halo rows crossing the mesh via
``ppermute`` rings.  :mod:`repro.sharding.rules` and
:mod:`repro.sharding.collective_matmul` are the LM-stack side of
the same story: parameter/activation PartitionSpecs and
latency-hiding (§4.1-style fully-overlapped) tensor-parallel matmuls,
the latter resurrected by ``MeshExecutor.overlap_probe`` as a live
overlapped-vs-serialized measurement.

Consumers: ``repro.core.dispatch`` attaches a :class:`ShardSpec` to
its memoized Advice when a mesh is configured; ``benchmarks.run sweep
--mesh N [--real]`` produces schema-6 records whose shard and mesh
claims ``repro.report.claims`` verifies; ``repro.serving.batcher``
packs batches per shard and charges the virtual clock the
shard-parallel maximum (or the measured mesh wall, with
``real_mesh``).  See docs/sharding.md for the end-to-end story.
"""
from .executor import MeshExecutor, MeshRun, ShardRun, ShardedExecutor
from .plan import (SHARD_KINDS, Shard, ShardPlan, ShardSpec,
                   combine_outputs, first_array, plan_for, shard_call,
                   spec_for, traffic)

__all__ = [
    "MeshExecutor", "MeshRun", "SHARD_KINDS", "Shard", "ShardPlan",
    "ShardRun", "ShardSpec", "ShardedExecutor", "combine_outputs",
    "first_array", "plan_for", "shard_call", "spec_for", "traffic",
]
