"""Mesh-sharded execution layer: plans, halo exchange, mesh helpers.

Covers the three legs of docs/sharding.md:

* **plans are pure data** — ShardSpec/ShardPlan JSON round-trips,
  extent partitioning, num_shards clamping, halo edge-clipping;
* **sharding is exact** — every registered family reassembles the
  unsharded oracle result, the stencil *because of* its Eq. 13 halo
  rows (a deliberately halo-less split is shown wrong), and the
  traffic accounting matches the Eq. 2 traits;
* **the mesh helpers work on this jax** — `data_mesh` builds an
  Auto-typed `jax.make_mesh` that `jax.set_mesh` enters, plus the
  dispatcher's `set_mesh` Advice integration and the serving batcher's
  shard-parallel accounting.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.dispatch import Dispatcher
from repro.kernels import registry
from repro.launch.mesh import data_mesh
from repro.sharding import (SHARD_KINDS, ShardPlan, ShardSpec,
                            ShardedExecutor, combine_outputs, plan_for,
                            shard_call, spec_for, traffic)


# --------------------------------------------------------------------------
# ShardSpec / ShardPlan: pure-data semantics
# --------------------------------------------------------------------------

def test_shard_spec_round_trip():
    spec = ShardSpec(kind="rowblock", num_shards=3, axis="data", halo=2)
    assert ShardSpec.from_json(spec.to_json()) == spec


def test_shard_spec_rejects_nonsense():
    with pytest.raises(ValueError):
        ShardSpec(kind="diagonal", num_shards=2)
    with pytest.raises(ValueError):
        ShardSpec(kind="data", num_shards=0)
    with pytest.raises(ValueError):
        ShardSpec(kind="data", num_shards=2, halo=-1)


@pytest.mark.parametrize("kernel", registry.names())
@pytest.mark.parametrize("n", [1, 2, 3])
def test_shard_plan_round_trip(kernel, n):
    """to_json/from_json reproduces every family's plan exactly."""
    op = registry.get(kernel)
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, op.test_size or 1024, "float32")
    plan = plan_for(op, n, *args, **kw)
    assert ShardPlan.from_json(plan.to_json()) == plan


def test_plan_partitions_extent_exactly():
    op = registry.get("scale")
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, 1000, "float32")  # not divisible by 3
    plan = plan_for(op, 3, *args, **kw)
    assert plan.extent == 1000
    assert [s.owned for s in plan.shards] == [334, 333, 333]
    assert plan.shards[0].start == 0 and plan.shards[-1].stop == 1000
    # contiguous, non-overlapping
    for a, b in zip(plan.shards, plan.shards[1:]):
        assert a.stop == b.start


def test_plan_clamps_num_shards_to_extent():
    """A 4-way mesh over a 2-head cache plans 2 useful shards."""
    op = registry.get("attention")
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, 256, "float32")
    plan = plan_for(op, 4, *args, **kw)
    assert plan.spec.kind == "head"
    assert plan.spec.num_shards == 2  # KH = 2 in make_inputs


def test_stencil_plan_halo_clips_at_domain_edges():
    op = registry.get("stencil")
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, 48, "float32")
    plan = plan_for(op, 3, *args, **kw)
    halo = plan.spec.halo
    assert halo == kw["steps"] * args[1].radius and halo > 0
    first, last = plan.shards[0], plan.shards[-1]
    assert first.lo == 0 and first.hi == halo     # no neighbour below
    assert last.lo == halo and last.hi == 0       # no neighbour above
    for mid in plan.shards[1:-1]:
        assert mid.lo == halo and mid.hi == halo


def test_plan_invariants_reject_bad_construction():
    spec = ShardSpec(kind="data", num_shards=2)
    from repro.sharding.plan import Shard
    with pytest.raises(ValueError):  # shard count mismatch
        ShardPlan(spec=spec, shards=(Shard(0, 0, 10),), extent=10)
    with pytest.raises(ValueError):  # does not partition the extent
        ShardPlan(spec=spec,
                  shards=(Shard(0, 0, 4), Shard(1, 4, 8)), extent=10)


# --------------------------------------------------------------------------
# sharded execution is exact (every family, vs. the oracle)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", registry.names())
def test_sharded_execution_matches_oracle(kernel):
    op = registry.get(kernel)
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, op.test_size or 1024, "float32")
    want = np.asarray(op.reference(*args, **kw), np.float32)
    run = ShardedExecutor(2).run(op, *args, **kw)
    got = np.asarray(run.out, np.float32)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert len(run.shard_seconds) == run.plan.spec.num_shards
    assert run.parallel_s <= run.serial_s + 1e-12


def test_stencil_halo_correctness():
    """The sharded stencil equals the unsharded run bit-for-bit."""
    op = registry.get("stencil")
    rng = np.random.default_rng(1)
    args, kw = op.make_inputs(rng, 48, "float32")
    unsharded = np.asarray(op(*args, engine="vector", **kw))
    for n in (2, 3):
        run = ShardedExecutor(n, engine="vector").run(op, *args, **kw)
        np.testing.assert_array_equal(np.asarray(run.out), unsharded)


def test_stencil_sharded_without_halo_is_wrong():
    """The halo is load-bearing: dropping it corrupts boundary rows.

    Guards against a planner regression that silently stops borrowing
    the Eq. 13 trapezoid rows — the split would still reassemble to
    the right shape and pass a smoke test that only checks shapes.
    """
    op = registry.get("stencil")
    rng = np.random.default_rng(1)
    args, kw = op.make_inputs(rng, 48, "float32")
    want = np.asarray(op.reference(*args, **kw), np.float32)
    plan = plan_for(op, 2, *args, **kw)
    bad = dataclasses.replace(
        plan,
        spec=dataclasses.replace(plan.spec, halo=0),
        shards=tuple(dataclasses.replace(s, lo=0, hi=0)
                     for s in plan.shards))
    run = ShardedExecutor(2, engine="vector").run(op, *args, plan=bad,
                                                  **kw)
    err = float(np.max(np.abs(np.asarray(run.out, np.float32) - want)))
    assert err > 1e-3, "halo-less split unexpectedly matched the oracle"


def test_single_shard_degenerates_to_plain_call():
    op = registry.get("triad")
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, 4096, "float32")
    run = ShardedExecutor(1).run(op, *args, **kw)
    np.testing.assert_array_equal(
        np.asarray(run.out), np.asarray(op(*args, **kw)))
    assert run.plan.spec.num_shards == 1


# --------------------------------------------------------------------------
# traffic accounting feeds the shard claims
# --------------------------------------------------------------------------

def test_traffic_data_split_is_exact():
    op = registry.get("scale")
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, 2**16, "float32")
    plan = plan_for(op, 4, *args, **kw)
    t = traffic(op, plan, args, kw)
    assert t["agg_bytes"] == pytest.approx(t["total_bytes"])
    assert t["shard_bytes"] * 4 == pytest.approx(t["total_bytes"])
    assert t["shard_intensity"] == pytest.approx(
        op.traits(*args, **kw).intensity)


def test_traffic_stencil_halo_overhead_is_positive_and_bounded():
    op = registry.get("stencil")
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, 48, "float32")
    plan = plan_for(op, 2, *args, **kw)
    t = traffic(op, plan, args, kw)
    rows, halo = args[0].shape[0], plan.spec.halo
    expected = (rows + 2 * halo) / rows  # one interior boundary
    assert t["agg_bytes"] / t["total_bytes"] == pytest.approx(expected)
    assert t["shard_intensity"] <= op.traits(*args, **kw).intensity + 1e-9


def test_shard_call_slices_match_manual_slicing():
    op = registry.get("axpy")
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, 1024, "float32")
    plan = plan_for(op, 2, *args, **kw)
    sargs, _ = shard_call(plan, plan.shards[1], args, kw)
    for orig, sliced in zip(args, sargs):
        if hasattr(orig, "shape"):
            np.testing.assert_array_equal(
                np.asarray(sliced), np.asarray(orig).reshape(-1)[512:])
    outs = []
    for shard in plan.shards:
        sa, skw = shard_call(plan, shard, args, kw)
        outs.append(op.reference(*sa, **skw))
    np.testing.assert_allclose(
        np.asarray(combine_outputs(plan, outs, template=args[0])),
        np.asarray(op.reference(*args, **kw)), atol=1e-5)


# --------------------------------------------------------------------------
# launch.mesh helpers (previously untested)
# --------------------------------------------------------------------------

def test_data_mesh_is_a_working_auto_mesh():
    """data_mesh is jax.make_mesh with an Auto axis; jax.set_mesh enters
    it, a sharded computation runs under it, and it exits cleanly."""
    mesh = data_mesh(1)
    assert mesh.axis_names == ("data",)
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)
    x = jax.numpy.arange(8.0)
    with jax.set_mesh(mesh):
        y = jax.device_put(x, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data")))
        assert float(jax.jit(jax.numpy.sum)(y)) == 28.0
    with jax.set_mesh(mesh):  # no context leak breaks a second entry
        pass


def test_mesh_executor_refuses_a_wider_plan_than_devices():
    """MeshExecutor(n) never shrinks to the devices it finds (unlike the
    virtual path's data_mesh): it names what it saw and how to get n,
    on the chip and on the CPU."""
    from repro.sharding import MeshExecutor
    have = len(jax.devices())
    with pytest.raises(RuntimeError) as info:
        MeshExecutor(have + 1)
    msg = str(info.value)
    assert f"needs {have + 1} devices" in msg
    assert jax.devices()[0].platform in msg
    assert f"at least {have + 1} chips" in msg


def test_data_mesh_clamps_to_available_devices():
    mesh = data_mesh(8)
    assert mesh.axis_names == ("data",)
    assert 1 <= mesh.shape["data"] <= max(1, len(jax.devices()))
    assert data_mesh(1).shape["data"] == 1


# --------------------------------------------------------------------------
# dispatch + serving integration
# --------------------------------------------------------------------------

def test_dispatcher_set_mesh_attaches_shard_spec():
    d = Dispatcher(mesh_shards=2)
    op = registry.get("scale")
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, 4096, "float32")
    advice = d.advise(op, *args, **kw)
    assert advice.shard_spec is not None
    assert advice.shard_spec.num_shards == 2
    assert advice.shard_spec.kind == "data"
    # memoized: the second call is a cache hit carrying the same spec
    assert d.advise(op, *args, **kw) is advice
    # reconfiguring the mesh drops the cache and replans
    d.set_mesh(1)
    assert d.advise(op, *args, **kw).shard_spec is None


def test_executor_shards_are_not_replanned_as_sub_splits():
    """Per-shard launches under a mesh-configured dispatcher must not
    get a bogus nested shard_spec memoized onto their Advice — a shard
    IS the split, not something to split again."""
    d = Dispatcher(mesh_shards=2)
    ex = ShardedExecutor(2, dispatcher=d)
    op = registry.get("scale")
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, 4096, "float32")
    run = ex.run(op, *args, **kw)
    np.testing.assert_allclose(np.asarray(run.out),
                               np.asarray(op.reference(*args, **kw)),
                               atol=1e-5)
    flat = ex._shard_dispatcher()
    assert flat is not d and flat.mesh_shards == 1
    # the shard-shaped advice the launches memoized carries no spec
    sargs, skw = shard_call(run.plan, run.plan.shards[0], args, kw)
    assert flat.advise(op, *sargs, **skw).shard_spec is None
    # while the mesh-level dispatcher still plans the full call
    assert d.advise(op, *args, **kw).shard_spec.num_shards == 2


def test_spec_for_matches_plan_spec():
    op = registry.get("spmv")
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, 128, "float32")
    assert spec_for(op, 2, *args, **kw) == \
        plan_for(op, 2, *args, **kw).spec
    assert spec_for(op, 2, *args, **kw).kind in SHARD_KINDS


def test_serving_batcher_reports_shard_count():
    from repro.serving import SessionConfig, run_session
    cfg = SessionConfig(kernel="scale", size=8192, duration_s=0.3,
                        rate_rps=32.0, num_shards=2, seed=3)
    log, summary, record = run_session(cfg)
    assert summary.completed > 0
    assert record["num_shards"] == 2
    # every launched batch was split 2-way and charged a finite,
    # positive shard-parallel compute time
    assert all(b[4] > 0 for b in log.batches)


# --------------------------------------------------------------------------
# Real mesh execution (single-device fast paths; the multi-device
# equivalence runs live in tests/test_distributed.py subprocesses)
# --------------------------------------------------------------------------
def test_mesh_executor_needs_enough_devices():
    """In this single-device test process a 2-way MeshExecutor must
    refuse loudly and point at host_device_count, never fall back to
    quietly simulating."""
    from repro.sharding import MeshExecutor
    with pytest.raises(RuntimeError, match="host_device_count"):
        MeshExecutor(2)
    with pytest.raises(ValueError):
        MeshExecutor(0)


def test_mesh_executor_one_device_runs_and_measures():
    """Width 1 is the degenerate real mesh: no collectives (empty
    ppermute rings yield the zero boundary), output matches the
    oracle, and measure() reports a zero collective."""
    from repro.sharding import MeshExecutor
    mex = MeshExecutor(1)
    rng = np.random.default_rng(0)
    for name in ("scale", "stencil"):
        op = registry.get(name)
        args, kw = op.make_inputs(rng, op.test_size, "float32")
        run = mex.run(op, *args, **kw)
        assert run.devices == 1
        assert run.parallel_s == run.wall_s  # batcher contract
        np.testing.assert_allclose(np.asarray(run.out),
                                   np.asarray(op.reference(*args, **kw)),
                                   atol=2e-4)
        m = mex.measure(op, *args, **kw)
        assert m["collective_us"] == 0.0 and m["mesh_wall_us"] > 0


def test_host_device_count_post_init_paths():
    """After JAX initialized (this process: 1 device), asking for more
    devices raises with the fix; asking for what we have is a no-op."""
    from repro.launch.mesh import host_device_count
    have = len(jax.devices())
    assert host_device_count(have) == have
    with pytest.raises(RuntimeError, match="already initialized"):
        host_device_count(have + 1)
    with pytest.raises(ValueError):
        host_device_count(0)


def test_traffic_wire_bytes_accounting():
    """wire_bytes = exactly the halo rows a real mesh must move:
    zero for data/head/halo-free splits, lo+hi rows x row bytes for
    the stencil exchange."""
    rng = np.random.default_rng(0)
    for name in ("scale", "spmv", "attention"):
        op = registry.get(name)
        args, kw = op.make_inputs(rng, op.test_size, "float32")
        plan = plan_for(op, 2, *args, **kw)
        assert traffic(op, plan, args, kw)["wire_bytes"] == 0.0
    op = registry.get("stencil")
    args, kw = op.make_inputs(rng, 48, "float32")
    plan = plan_for(op, 2, *args, **kw)
    u = args[0]
    row_bytes = int(np.prod(u.shape[1:])) * u.dtype.itemsize
    expect = sum(s.lo + s.hi for s in plan.shards) * row_bytes
    assert traffic(op, plan, args, kw)["wire_bytes"] == expect > 0


def test_dispatcher_mesh_mode_stamped_on_advice():
    d = Dispatcher(mesh_shards=2)
    op = registry.get("scale")
    rng = np.random.default_rng(0)
    args, kw = op.make_inputs(rng, 4096, "float32")
    assert d.mesh_mode == "virtual"
    assert d.advise(op, *args, **kw).exec_mode == "virtual"
    d.set_mesh(2, "mesh")
    advice = d.advise(op, *args, **kw)
    assert advice.exec_mode == "mesh"
    assert advice.shard_spec is not None
    with pytest.raises(ValueError, match="mesh mode"):
        d.set_mesh(2, "warp")
    # mode is part of the memo contract: switching back re-advises
    d.set_mesh(2, "virtual")
    assert d.advise(op, *args, **kw).exec_mode == "virtual"


def test_serving_record_carries_mesh_exec_mode():
    from repro.serving import SessionConfig, run_session
    cfg = SessionConfig(kernel="scale", size=8192, duration_s=0.3,
                        rate_rps=32.0, num_shards=2, seed=3)
    _, _, record = run_session(cfg)
    assert record["mesh_exec_mode"] == "virtual"
    cfg1 = dataclasses.replace(cfg, num_shards=1)
    _, _, record1 = run_session(cfg1)
    assert record1["mesh_exec_mode"] is None
