"""Per-kernel allclose validation vs pure-jnp oracles (interpret mode).

Sweeps shapes/dtypes per kernel and asserts the MXU and VPU variants
agree with ref.py -- the empirical backbone of the paper's claim that
both engines compute the same thing through the same memory path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional dev dependency (pip install -e .[dev]); property tests
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - skip only the property tests
    HAVE_HYPOTHESIS = False


def _hypothesis_stub():
    """Placeholder so missing property tests show up as skips, not as
    silently-uncollected coverage."""
    pytest.skip("hypothesis not installed (pip install -e .[dev])")

from repro.kernels import registry
from repro.kernels.axpy.ref import axpy_ref
from repro.kernels.scale.ops import scale
from repro.kernels.scale.ref import scale_ref
from repro.kernels.spmv.ops import dense_to_bell, spmv
from repro.kernels.spmv.ref import bell_matvec_ref, csr_spmv_ref
from repro.kernels.stencil.defs import suite
from repro.kernels.stencil.ops import stencil
from repro.kernels.stencil.ref import stencil_ref
from repro.kernels.triad.ref import triad_ref

ENGINES = ["vpu", "mxu"]


# --------------------------------------------------------------------------
# Elementwise (SCALE, STREAM Triad, AXPY)
# --------------------------------------------------------------------------

def _elementwise_args(name, shape, dtype):
    """Call arguments of one elementwise family (scalars exact in bf16)."""
    rng = np.random.default_rng(0)

    def arr():
        return jnp.asarray(rng.standard_normal(shape), dtype)

    if name == "scale":
        return (arr(), 2.5)
    if name == "triad":
        return (arr(), arr(), 1.5)
    return (0.75, arr(), arr())  # axpy


@pytest.mark.parametrize("engine", ENGINES + ["auto"])
@pytest.mark.parametrize("shape", [(17,), (1024,), (300_000,), (33, 95)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scale_matches_ref(engine, shape, dtype):
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal(shape), dtype)
    q = 2.5
    got = scale(b, q, engine=engine)
    want = scale_ref(b, q)
    assert got.shape == b.shape and got.dtype == b.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("name,ref", [("triad", triad_ref),
                                      ("axpy", axpy_ref)])
@pytest.mark.parametrize("engine", ENGINES + ["auto"])
@pytest.mark.parametrize("shape", [(17,), (1024,), (300_000,), (33, 95)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_elementwise_matches_ref(name, ref, engine, shape, dtype):
    """The two-operand families at SCALE's engines, shapes and dtypes."""
    args = _elementwise_args(name, shape, dtype)
    got = registry.get(name)(*args, engine=engine)
    want = ref(*args)
    assert got.shape == shape and got.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    # a sum of two unit-scale operands can cancel, so its rounding error
    # is bounded against the operands, not the result
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("name,ref", [("scale", scale_ref),
                                      ("triad", triad_ref),
                                      ("axpy", axpy_ref)])
@pytest.mark.parametrize("engine", ENGINES)
def test_elementwise_explicit_tile_matches_ref(name, ref, engine):
    """A caller's tile kwargs reach the body as given: an 8x128 tile
    over a (33, 95) array pads to several grid steps, and the traits
    factory ignores the kwargs."""
    args = _elementwise_args(name, (33, 95), jnp.float32)
    got = registry.get(name)(*args, engine=engine, block_rows=8, lanes=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(*args)),
                               rtol=1e-6, atol=0.0 if name == "scale"
                               else 1e-6)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(1, 5000), q=st.floats(-10, 10, allow_nan=False))
    def test_scale_property(n, q):
        b = jnp.arange(n, dtype=jnp.float32) / max(n, 1)
        np.testing.assert_allclose(np.asarray(scale(b, q, engine="vpu")),
                                   np.asarray(scale_ref(b, q)), rtol=1e-5,
                                   atol=1e-6)
else:
    def test_scale_property():
        _hypothesis_stub()


# --------------------------------------------------------------------------
# SpMV
# --------------------------------------------------------------------------

def _random_sparse(m, n, density, rng, bm=8, bn=128):
    a = rng.standard_normal((m, n)).astype(np.float32)
    mask = rng.random((m, n)) < density
    return a * mask


@pytest.mark.parametrize("engine", ENGINES + ["auto"])
@pytest.mark.parametrize("m,n,density", [
    (32, 256, 0.05), (64, 512, 0.01), (128, 384, 0.3), (8, 128, 1.0),
])
def test_spmv_matches_ref(engine, m, n, density):
    rng = np.random.default_rng(1)
    a = _random_sparse(m, n, density, rng)
    bell = dense_to_bell(a, bm=8, bn=128)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    got = spmv(bell, x, engine=engine)
    want = a @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    # block-ELL oracle agrees with the dense ground truth too
    np.testing.assert_allclose(np.asarray(bell_matvec_ref(bell, x)), want,
                               rtol=1e-4, atol=1e-4)


def _dense_to_csr(a):
    m, n = a.shape
    indptr = [0]
    indices, data = [], []
    for i in range(m):
        nz = np.nonzero(a[i])[0]
        indices.extend(nz.tolist())
        data.extend(a[i, nz].tolist())
        indptr.append(len(indices))
    return (jnp.asarray(indptr, jnp.int32), jnp.asarray(indices, jnp.int32),
            jnp.asarray(data, jnp.float32))


def test_csr_oracle():
    rng = np.random.default_rng(3)
    a = _random_sparse(40, 64, 0.15, rng)
    indptr, indices, data = _dense_to_csr(a)
    x = jnp.asarray(rng.standard_normal(64), jnp.float32)
    got = csr_spmv_ref(indptr, indices, data, x, m=40)
    np.testing.assert_allclose(np.asarray(got), a @ np.asarray(x),
                               rtol=1e-4, atol=1e-4)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16), density=st.floats(0.0, 1.0))
    def test_spmv_property_engines_agree(seed, density):
        """Property: VPU and MXU paths agree on any sparsity pattern."""
        rng = np.random.default_rng(seed)
        a = _random_sparse(16, 256, density, rng)
        bell = dense_to_bell(a)
        x = jnp.asarray(rng.standard_normal(256), jnp.float32)
        yv = spmv(bell, x, engine="vpu")
        ym = spmv(bell, x, engine="mxu")
        np.testing.assert_allclose(np.asarray(yv), np.asarray(ym),
                                   rtol=1e-4, atol=1e-4)
else:
    def test_spmv_property_engines_agree():
        _hypothesis_stub()


# --------------------------------------------------------------------------
# Stencil
# --------------------------------------------------------------------------

SPECS = suite()


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("name", sorted(SPECS))
def test_stencil_single_step(engine, name):
    spec = SPECS[name]
    rng = np.random.default_rng(4)
    shape = (40, 70) if spec.ndim == 2 else (12, 20, 34)
    u = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    got = stencil(u, spec, steps=1, engine=engine, block_rows=8)
    want = stencil_ref(u, spec, steps=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("name,steps", [("2d5pt", 3), ("2d9pt", 3),
                                        ("2d13pt", 2), ("3d7pt", 3),
                                        ("3d27pt", 2)])
def test_stencil_temporal_blocking(engine, name, steps):
    """Fused t-step kernels == t oracle applications (paper Eq. 13)."""
    spec = SPECS[name]
    rng = np.random.default_rng(5)
    shape = (48, 52) if spec.ndim == 2 else (16, 20, 30)
    u = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    got = stencil(u, spec, steps=steps, engine=engine, block_rows=16)
    want = stencil_ref(u, spec, steps=steps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("name,shape", [("2d49pt", (24, 1100)),
                                        ("3d27pt", (6, 10, 1100))])
def test_stencil_lane_tiled_grid(engine, name, shape):
    """A grid too wide for one lane block is tiled on the lane axis too:
    overlapping windows (128-lane halos) still give the oracle's answer."""
    spec = SPECS[name]
    rng = np.random.default_rng(7)
    u = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    got = stencil(u, spec, steps=2, engine=engine, block_rows=8)
    want = stencil_ref(u, spec, steps=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 2**16), steps=st.integers(1, 3))
    def test_stencil_property_linearity(seed, steps):
        """Stencils are linear: S(a u + b v) = a S(u) + b S(v)."""
        spec = SPECS["2d5pt"]
        rng = np.random.default_rng(seed)
        u = jnp.asarray(rng.standard_normal((24, 30)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((24, 30)), jnp.float32)
        lhs = stencil(2.0 * u + 3.0 * v, spec, steps=steps, engine="vpu",
                      block_rows=8)
        rhs = (2.0 * stencil(u, spec, steps=steps, engine="vpu",
                             block_rows=8)
               + 3.0 * stencil(v, spec, steps=steps, engine="vpu",
                               block_rows=8))
        np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                                   rtol=1e-3, atol=1e-4)
else:
    def test_stencil_property_linearity():
        _hypothesis_stub()


def test_stencil_engines_agree_suite():
    """MXU banded-matmul == VPU shifted-add on the whole Table-3 suite."""
    rng = np.random.default_rng(6)
    for name, spec in SPECS.items():
        shape = (32, 40) if spec.ndim == 2 else (12, 16, 24)
        u = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        yv = stencil(u, spec, steps=1, engine="vpu", block_rows=8)
        ym = stencil(u, spec, steps=1, engine="mxu", block_rows=8)
        np.testing.assert_allclose(np.asarray(yv), np.asarray(ym),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
