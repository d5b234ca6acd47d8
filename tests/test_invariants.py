"""System-invariant property tests (hypothesis) across the stack."""
import dataclasses

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

from repro.configs import get_arch, reduced
from repro.core import (EngineAdvisor, TPU_V5E, best_case_speedup,
                        machine_balance, tensor_core_upper_bound)
from repro.core.intensity import KernelTraits
from repro.models import lm
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope
from repro.models.moe import moe_ffn
from repro.models.ssm import _ssd_chunked


# --------------------------------------------------------------------------
# theory invariants
# --------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(alpha=st.floats(1.001, 1e6), i=st.floats(1e-6, 1e3))
    def test_bounds_ordering_property(alpha, i):
        """Eq. 23 dominates every achievable memory-bound speedup, and the
        best-case bound is monotone in intensity."""
        hw = TPU_V5E
        b = machine_balance(hw, "vector")
        if i >= b:
            return  # not memory-bound
        s = best_case_speedup(hw, i)
        assert 1.0 <= s <= tensor_core_upper_bound(hw.alpha) + 1e-9
        s2 = best_case_speedup(hw, i * 0.5)
        assert s2 <= s + 1e-12  # less intensity -> less benefit

    @settings(max_examples=30, deadline=None)
    @given(w=st.floats(1, 1e15), q=st.floats(1, 1e15))
    def test_advisor_total_function(w, q):
        """The advisor returns a decision for any (W, Q) without error."""
        adv = EngineAdvisor(TPU_V5E).advise(KernelTraits("x", w, q))
        assert adv.engine in ("vector", "matrix")
        assert adv.max_speedup_matrix >= 1.0
else:
    def test_bounds_ordering_property():
        _hypothesis_stub()

    def test_advisor_total_function():
        _hypothesis_stub()


# --------------------------------------------------------------------------
# SSD invariants
# --------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 1000), chunk=st.sampled_from([4, 8, 16]))
    def test_ssd_chunk_size_invariance(seed, chunk):
        """The chunked SSD scan must be independent of the chunk size."""
        rng = np.random.default_rng(seed)
        b, s, h, p, n = 1, 32, 2, 4, 8
        x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
        a = jnp.asarray(rng.uniform(0.5, 2.0, (h,)), jnp.float32)
        bm = jnp.asarray(rng.standard_normal((b, s, 1, n)), jnp.float32)
        cm = jnp.asarray(rng.standard_normal((b, s, 1, n)), jnp.float32)
        y1, f1 = _ssd_chunked(x, dt, a, bm, cm, chunk)
        y2, f2 = _ssd_chunked(x, dt, a, bm, cm, 32)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                                   rtol=1e-4, atol=1e-5)
else:
    def test_ssd_chunk_size_invariance():
        _hypothesis_stub()


def test_ssd_matches_sequential_recurrence():
    """Chunked SSD == naive per-step recurrence."""
    rng = np.random.default_rng(3)
    b, s, h, p, n = 1, 16, 1, 2, 4
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.3, (b, s, h)), jnp.float32)
    a = jnp.asarray([1.3], jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, s, 1, n)), jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, s, 1, n)), jnp.float32)
    y, final = _ssd_chunked(x, dt, a, bm, cm, 8)

    state = np.zeros((p, n), np.float32)
    ys = []
    for t in range(s):
        decay = np.exp(-float(dt[0, t, 0]) * float(a[0]))
        state = state * decay + float(dt[0, t, 0]) * np.outer(
            np.asarray(x[0, t, 0]), np.asarray(bm[0, t, 0]))
        ys.append(state @ np.asarray(cm[0, t, 0]))
    np.testing.assert_allclose(np.asarray(y[0, :, 0]), np.stack(ys),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(final[0, 0]), state,
                               rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# attention / rope invariants
# --------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(shift=st.integers(0, 100), seed=st.integers(0, 1000))
    def test_rope_relative_position_property(shift, seed):
        """RoPE inner products depend only on relative position."""
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((1, 4, 1, 32)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 4, 1, 32)), jnp.float32)
        pos = jnp.arange(4)[None]
        q1 = apply_rope(q, pos, 1e4)
        k1 = apply_rope(k, pos, 1e4)
        q2 = apply_rope(q, pos + shift, 1e4)
        k2 = apply_rope(k, pos + shift, 1e4)
        s1 = jnp.einsum("bqhd,bkhd->bqk", q1, k1)
        s2 = jnp.einsum("bqhd,bkhd->bqk", q2, k2)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-3, atol=1e-4)
else:
    def test_rope_relative_position_property():
        _hypothesis_stub()


# --------------------------------------------------------------------------
# MoE invariants
# --------------------------------------------------------------------------

def test_moe_batch_split_invariance():
    """Dropless routing: a token's output does not depend on which other
    tokens share its batch, so the layer over a batch equals the layer
    over each half of it (rows of the grouped product differ, the sum
    per token is the same up to float32 rounding)."""
    cfg = reduced(get_arch("qwen3-moe-235b-a22b"))
    from repro.models.moe import init_moe
    p = init_moe(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model)), jnp.float32)
    y, _ = moe_ffn(p, x, cfg)
    halves = [moe_ffn(p, x[i:i + 1], cfg)[0] for i in range(2)]
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.concatenate(halves)),
                               rtol=1e-5, atol=1e-6)


def test_moe_gates_convexity():
    """Renormalised top-k gates (qwen3-moe) sum to 1 per token; unnormalised
    ones (deepseek-v2-lite) are the top-k softmax probabilities, under 1.
    A zero input gives a zero output through the SwiGLU experts."""
    from repro.models.moe import init_moe, route
    rng = np.random.default_rng(1)
    for name, normed in (("qwen3-moe-235b-a22b", True),
                         ("deepseek-v2-lite-16b", False)):
        cfg = reduced(get_arch(name))
        assert cfg.norm_topk_prob is normed
        p = init_moe(jax.random.key(1), cfg)
        xt = jnp.asarray(rng.standard_normal((8, cfg.d_model)), jnp.float32)
        _, gates, _ = route(p, xt, cfg)
        total = np.asarray(gates.sum(-1))
        if normed:
            np.testing.assert_allclose(total, 1.0, rtol=1e-6)
        else:
            assert (total < 1.0).all() and (total > 0.0).all()
        y, aux = moe_ffn(p, jnp.zeros((1, 4, cfg.d_model), jnp.float32), cfg)
        assert float(jnp.max(jnp.abs(y))) < 1e-5
        assert np.isfinite(float(aux["aux_loss"]))


# --------------------------------------------------------------------------
# bf16-master optimizer invariant
# --------------------------------------------------------------------------

def test_master_weights_track_f32_training():
    """The f32 master trajectory is *exactly* the f32-optimizer trajectory
    fed the same (bf16) gradients: no precision is lost in the update,
    only in gradient/weight transport -- the FSDP mixed-precision
    contract.  The bf16 params are the rounded view of the master."""
    from repro.optim.adamw import AdamW
    rng = np.random.default_rng(0)
    w32 = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32) * 0.1
    gbf = g.astype(jnp.bfloat16)

    w0 = w32.astype(jnp.bfloat16).astype(jnp.float32)  # shared start point
    opt32 = AdamW(lr=1e-2, clip_norm=None)
    s32 = opt32.init({"w": w0})
    p32 = {"w": w0}
    optbf = AdamW(lr=1e-2, clip_norm=None, master_weights=True)
    pbf = {"w": w32.astype(jnp.bfloat16)}
    sbf = optbf.init(pbf)
    for _ in range(10):
        p32, s32 = opt32.update({"w": gbf}, s32, p32)  # same bf16 grads
        pbf, sbf = optbf.update({"w": gbf}, sbf, pbf)
    master_err = float(jnp.max(jnp.abs(sbf.master["w"] - p32["w"])))
    # identical except weight decay couples through f32-vs-master weights
    assert master_err < 1e-4, master_err
    np.testing.assert_allclose(
        np.asarray(pbf["w"].astype(jnp.float32)),
        np.asarray(sbf.master["w"]), rtol=1e-2, atol=1e-2)  # rounded view
