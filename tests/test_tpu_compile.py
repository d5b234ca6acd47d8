"""The main path's Pallas kernels compile for a TPU v5e at real sizes.

Nothing here runs: each test lowers and compiles one registered engine
for a *described* v5e chip (``jax.experimental.topologies``), at the
sizes ``chip_smoke.py`` runs on the chip, and checks that the compiled
program holds a Mosaic kernel (``tpu_custom_call``).  The TPU compiler
refuses what interpret mode accepts -- misaligned blocks, more VMEM than
a core has -- so these guard every later change at no chip time.

The topology is described inside a module-scoped fixture (never at
import time): only one process may load the TPU library, and the test
workers must all collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import registry
from repro.kernels.expert_gmm import expert_gmm
from repro.kernels.spmv.ref import BlockEll
from repro.kernels.stencil.defs import TABLE3_DEPTH, suite

ELEMENTWISE_N = 2**27
ATTN_Q, ATTN_KV = (4, 32, 1, 128), (4, 4096, 32, 128)
SPMV_BLOCKS, SPMV_SHAPE = (1024, 128, 8, 128), (8192, 16384)
STENCIL_SHAPE = {2: (4096, 4096), 3: (256, 256, 256)}
ENGINES = ("vector", "matrix")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent cache off: a compile
    for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiles_a_kernel(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _arr(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("name,arity", [("scale", 1), ("triad", 2),
                                        ("axpy", 2)])
def test_elementwise_compiles(one_chip, name, arity, engine):
    fn = registry.get(name).engines[engine]
    if name == "axpy":
        def call(x, y):
            return fn(0.75, x, y, interpret=False)
    else:
        def call(*arrays):
            return fn(*arrays, 1.5, interpret=False)
    shapes = [_arr((ELEMENTWISE_N,), jnp.float32, one_chip)] * arity
    _compiles_a_kernel(call, *shapes)


@pytest.mark.parametrize("engine", ENGINES)
def test_attention_compiles_at_deepseek_decode_shape(one_chip, engine):
    fn = registry.get("attention").engines[engine]
    kv = _arr(ATTN_KV, jnp.bfloat16, one_chip)
    _compiles_a_kernel(
        lambda q, k, v: fn(q, k, v, ATTN_KV[1] - 512, interpret=False),
        _arr(ATTN_Q, jnp.bfloat16, one_chip), kv, kv)


@pytest.mark.parametrize("engine", ENGINES)
def test_spmv_compiles(one_chip, engine):
    fn = registry.get("spmv").engines[engine]
    _compiles_a_kernel(
        lambda blocks, cols, x: fn(BlockEll(blocks, cols, SPMV_SHAPE), x,
                                   interpret=False),
        _arr(SPMV_BLOCKS, jnp.float32, one_chip),
        _arr(SPMV_BLOCKS[:2], jnp.int32, one_chip),
        _arr((SPMV_SHAPE[1],), jnp.float32, one_chip))


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("name", ["2d5pt", "3d7pt"])
def test_stencil_compiles_on_a_64mib_grid(one_chip, name, engine):
    fn = registry.get("stencil").engines[engine]
    spec = suite()[name]
    _compiles_a_kernel(
        lambda u: fn(u, spec, steps=TABLE3_DEPTH[name], interpret=False),
        _arr(STENCIL_SHAPE[spec.ndim], jnp.float32, one_chip))


#: deepseek-v2-lite's held-expert products, (rows, K, N, row tile): a
#: decode step of 128 tokens and a prefill of 128 x 256, 8 experts held
EXPERT_GMM = [(1024, 2048, 1408, 32), (1024, 1408, 2048, 32),
              (200704, 2048, 1408, 512), (200704, 1408, 2048, 512)]


@pytest.mark.parametrize("rows,k,n,tm", EXPERT_GMM)
def test_expert_gmm_compiles_at_deepseek_v2_lite_shapes(one_chip, rows, k,
                                                        n, tm):
    _compiles_a_kernel(
        lambda x, w, te, t: expert_gmm(x, w, te, t, tm, False),
        _arr((rows, k), jnp.bfloat16, one_chip),
        _arr((8, k, n), jnp.bfloat16, one_chip),
        _arr((rows // tm,), jnp.int32, one_chip),
        _arr((), jnp.int32, one_chip))
