"""Flash-decode Pallas kernel vs oracle: shape/dtype sweep + properties."""
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

from repro.core.dispatch import DEFAULT_DISPATCHER
from repro.kernels import registry
from repro.kernels.attention.flash_decode import (CHUNK, VMEM_BLOCK_BUDGET,
                                                  block_len)
from repro.kernels.attention.ops import decode_attention
from repro.kernels.attention.ref import decode_attention_ref


def _mk(b, s, kh, g, dh, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, kh, g, dh)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kh, dh)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kh, dh)), dtype)
    return q, k, v


@pytest.mark.parametrize("engine", ["vector", "matrix"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,kh,g,dh,block,kv_len", [
    (1, 512, 2, 4, 64, 128, 496),
    (2, 1024, 4, 8, 128, 256, 1008),
    (1, 256, 1, 1, 32, 64, 240),
    # a cache length that is no power of two, kv_len == S
    (2, 400, 2, 1, 128, 128, 400),
    # kv_len inside the first block
    (1, 400, 8, 4, 64, 128, 37),
    # kv_len in the middle of a block
    (1, 400, 8, 1, 128, 128, 200),
    # kv_len on a block edge
    (2, 400, 32, 1, 128, 64, 256),
    (1, 400, 32, 4, 64, 128, 128),
    # a block request past the VMEM cap (128 positions in float32,
    # 256 in bfloat16 at KH 32, Dh 128)
    (1, 640, 32, 1, 128, 4096, 600),
])
def test_flash_decode_matches_ref(b, s, kh, g, dh, block, kv_len, dtype,
                                  engine):
    """Positions past kv_len hold NaN: neither read into the result nor
    needed, whichever block they fall in."""
    q, k, v = _mk(b, s, kh, g, dh, dtype)
    dead = jnp.arange(s)[None, :, None, None] >= kv_len
    got = decode_attention(q, jnp.where(dead, jnp.nan, k),
                           jnp.where(dead, jnp.nan, v), kv_len,
                           engine=engine, block_s=block)
    want = decode_attention_ref(q, k, v, kv_len)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("itemsize", [4, 2])
@pytest.mark.parametrize("kh,dh", [(2, 64), (8, 128), (32, 128)])
def test_block_len_fits_vmem_and_honours_smaller_requests(kh, dh,
                                                          itemsize):
    """Double-buffered K and V blocks, padded to the dtype's tile, stay
    within the budget; a request under the cap is kept as given."""
    sublanes = 8 * (4 // itemsize)
    slab = -(-kh // sublanes) * sublanes * -(-dh // 128) * 128 * itemsize
    cap = block_len(1 << 20, 1 << 20, kh, dh, itemsize)
    assert cap % CHUNK == 0
    assert 4 * cap * slab <= VMEM_BLOCK_BUDGET
    assert 4 * (cap + CHUNK) * slab > VMEM_BLOCK_BUDGET
    assert block_len(64, 1 << 20, kh, dh, itemsize) == 64
    assert block_len(1 << 20, 400, kh, dh, itemsize) == min(cap, 400)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(kv_len=st.integers(1, 512), seed=st.integers(0, 1000))
    def test_flash_decode_kv_len_property(kv_len, seed):
        """Masked positions never influence the result."""
        q, k, v = _mk(1, 512, 2, 2, 64, jnp.float32, seed)
        got = decode_attention(q, k, v, kv_len, block_s=128)
        # poison the masked tail: result must not change
        k2 = k.at[:, kv_len:].set(1e6)
        v2 = v.at[:, kv_len:].set(-1e6)
        got2 = decode_attention(q, k2, v2, kv_len, block_s=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(got2),
                                   rtol=1e-6, atol=1e-6)
else:
    def test_flash_decode_kv_len_property():
        _hypothesis_stub()


def test_flash_decode_is_convex_combination():
    """Output rows lie within the convex hull of V rows (softmax weights)."""
    q, k, v = _mk(1, 256, 1, 2, 32, jnp.float32, 7)
    out = decode_attention(q, k, v, 256, block_s=64)
    vmax = np.asarray(v).max(axis=(0, 1))
    vmin = np.asarray(v).min(axis=(0, 1))
    o = np.asarray(out)[0, 0]
    assert (o <= vmax + 1e-4).all() and (o >= vmin - 1e-4).all()


# --------------------------------------------------------------------------
# registry-dispatched path (what the model decode engine calls)
# --------------------------------------------------------------------------

def test_registry_dispatch_matches_ref():
    """registry.get('attention') with engine='auto' == the oracle.

    This is the exact call path ``repro.models.attention`` takes when
    ``decode_attention_impl='registry'``: the EngineOp's __call__ routes
    through the default dispatcher's memoized §6 Advice.
    """
    op = registry.get("attention")
    q, k, v = _mk(1, 256, 2, 4, 64, jnp.float32, 11)
    got = op(q, k, v, 200)
    want = decode_attention_ref(q, k, v, 200)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("engine", ["vector", "matrix"])
def test_forced_engines_match_ref_through_registry(engine):
    """Both forced variants reproduce the oracle (same memory path)."""
    op = registry.get("attention")
    q, k, v = _mk(2, 128, 2, 2, 32, jnp.float32, 13)
    got = op(q, k, v, 100, engine=engine)
    want = decode_attention_ref(q, k, v, 100)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_advice_routes_decode_attention_to_vector():
    """§6: the GEMV-shaped cache scan is memory-bound -> vector engine."""
    op = registry.get("attention")
    q, k, v = _mk(1, 512, 2, 4, 64, jnp.float32)
    advice = DEFAULT_DISPATCHER.advise(op, q, k, v, 512)
    assert advice.memory_bound
    assert advice.engine == "vector"
    # Eq. 23 caps any matrix-engine hope below 2x on every platform
    assert 1.0 <= advice.max_speedup_matrix < 2.0


def test_registry_dispatch_model_scale_cache_lengths():
    """Serving cache lengths aren't block-aligned (e.g. prompt 8 + gen 4
    = 12); the clamped block must still mask correctly."""
    op = registry.get("attention")
    for s, kv_len in ((12, 9), (24, 24), (56, 1)):
        q, k, v = _mk(2, s, 1, 2, 16, jnp.float32, seed=s)
        got = op(q, k, v, kv_len)
        want = decode_attention_ref(q, k, v, kv_len)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5, err_msg=f"S={s}")
