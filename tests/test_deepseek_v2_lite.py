"""DeepSeek-V2-Lite on the decode path: MLA with YaRN, a dropless expert
layer that holds a share of the routed experts, and the last-position
prefill head.  All on the CPU at small sizes with seeded random weights;
the plain reference is the benchmark's, loaded by path."""
import dataclasses
import importlib.util
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch, reduced
from repro.data.synthetic import make_batch
from repro.models import lm
from repro.models.config import ModelConfig, YaRN
from repro.models.engine import DecodeEngine
from repro.models.layers import (apply_rope, mlp, rope_frequencies,
                                 softmax_mscale, yarn_correction_range)
from repro.models.moe import held_experts, init_moe, moe_ffn, route
from repro.obs.trace import capture

REPO = pathlib.Path(__file__).resolve().parent.parent
BENCH = REPO / "bench"


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(BENCH / "configs" / "deepseek-v2-lite.py", "v2lite_ref")


@pytest.fixture(scope="module")
def driver():
    return _load(BENCH / "drivers" / "lm_moe_decode.py", "v2lite_driver")


#: the published configuration at a CPU size: every key the driver and
#: the reference read, widths cut, 3 layers (the dense one and 2 MoE),
#: 8 held experts of a 32-wide router, top-6, YaRN as published
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
         "intermediate_size": 96, "moe_intermediate_size": 32,
         "num_hidden_layers": 3, "vocab_size": 256}


def small_config():
    cfg = json.loads((BENCH / "configs" / "deepseek-v2-lite.json")
                     .read_text())
    cfg.update(SMALL)
    cfg["published"] = dict(cfg["published"], n_routed_experts=32)
    return cfg


# --------------------------------------------------------------------------
# (a) prefill, then decode through the latent cache, against the reference
# --------------------------------------------------------------------------

def test_engine_decode_matches_reference_forward(ref, driver):
    """A float32 engine's prefill and teacher-forced decode logits equal
    the reference's full forward pass at every served position.  Both
    sides compute in float32; they associate the same sums differently
    (absorbed against decompressed MLA, a grouped product against dense
    experts), so they agree to float32 rounding: 1e-4 on logits of
    magnitude ~1, where a wrong rotary pairing, YaRN frequency, softmax
    scale or a dropped expert moves them by 1e-2 or more."""
    cfg = small_config()
    w = ref.make_weights(cfg, jax.random.key(3))
    b, p, gen = 2, 8, 5
    eng = DecodeEngine(driver.model_config(cfg), max_batch=b, prompt_len=p,
                       max_gen=gen, dtype=jnp.float32,
                       params=driver.program_params(
                           w, cfg["first_k_dense_replace"]))
    tokens = np.random.default_rng(0).integers(0, 256, (b, p + gen - 1),
                                               dtype=np.int32)
    logits, caches = eng.prefill({"tokens": jnp.asarray(tokens[:, :p])})
    got = [logits[:, 0]]
    for i in range(p, p + gen - 1):
        logits, caches = eng.decode_step(jnp.asarray(tokens[:, i:i + 1]),
                                         caches, i)
        got.append(logits[:, 0])
    got = np.stack(got, axis=1)
    for row in range(b):
        want = ref.logits(cfg, w, jnp.asarray(tokens[row]), p - 1)
        np.testing.assert_allclose(got[row, :, :256], np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# (b) the shares of the held experts add up to the uncut layer
# --------------------------------------------------------------------------

def _moe_cfg(**kw):
    base = dict(name="moe", family="moe", n_layers=2, d_model=32,
                n_heads=2, n_kv_heads=2, d_ff=64, vocab=256, n_experts=64,
                top_k=6, n_shared_experts=2, moe_d_ff=16,
                norm_topk_prob=False)
    return ModelConfig(**{**base, **kw})


def test_held_expert_shares_sum_to_the_uncut_layer():
    """Eight chips of an EP-8 deployment hold 8 experts each: their
    parts, with the shared experts (computed alike on every chip) counted
    once, sum to the 64-expert layer.  Float32 sums of the same terms in
    another order: 1e-5."""
    cfg = _moe_cfg()
    p = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, 32), jnp.float32)
    whole, _ = moe_ffn(p, x, cfg)
    shared = mlp(p["shared"], x)
    parts = []
    for s in range(8):
        ps = dict(p, **{n: p[n][8 * s:8 * s + 8]
                        for n in ("w_gate", "w_up", "w_down")})
        cs = dataclasses.replace(cfg, held_experts=(8 * s, 8 * s + 8))
        parts.append(moe_ffn(ps, x, cs)[0] - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# (c) no drops under skewed routing
# --------------------------------------------------------------------------

def _dense_experts(p, xt, gates, idx, cfg):
    """Every routed slot through its expert, densely (no capacity)."""
    start, stop = cfg.held_range
    out = jnp.zeros_like(xt)
    for e in range(start, stop):
        g = jnp.where(idx == e, gates, 0.0).sum(-1, keepdims=True)
        pe = {n: p[n][e - start] for n in ("w_gate", "w_up", "w_down")}
        out = out + g * mlp(pe, xt)
    return out


def test_skewed_routing_drops_nothing():
    """A router that sends every token to the same 6 of 16 experts: a
    capacity of 1.25 x the mean slots an expert takes (15 here, against
    the 32 each hot expert is sent) drops 17 of 32; the held-expert layer
    computes every slot, and the engine records ``dropped`` 0 with every
    slot counted."""
    cfg = _moe_cfg(n_experts=16, held_experts=None)
    p = init_moe(jax.random.key(2), cfg)
    hot = jnp.zeros((32, 16)).at[:, :6].set(1.0)
    p["router"] = p["router"] + 50.0 * hot
    xt = jnp.abs(jax.random.normal(jax.random.key(3), (32, 32)))
    _, gates, idx = route(p, xt, cfg)
    assert set(np.asarray(idx).ravel()) == set(range(6))
    out, load = held_experts(p, xt, gates, idx, cfg)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_experts(p, xt, gates, idx,
                                                         cfg)),
                               rtol=1e-5, atol=1e-5)
    assert np.asarray(load).tolist() == [32 * 6, 32]

    full = dataclasses.replace(reduced(get_arch("deepseek-v2-lite-16b")),
                               n_experts=16)
    params = lm.init_params(full, jax.random.key(4))
    params["layers"]["moe"]["router"] = (
        params["layers"]["moe"]["router"]
        + 50.0 * jnp.zeros((full.d_model, 16)).at[:, :6].set(1.0))
    eng = DecodeEngine(full, max_batch=2, prompt_len=8, max_gen=3,
                       dtype=jnp.float32, params=params)
    with capture() as view:
        res = eng.generate(eng.make_prompt_batch(seed=1))
    stats = [e.attrs for e in view.events if e.name == "moe_load"]
    assert stats and stats[0]["dropped"] == 0
    assert stats[0]["held_slots"] == int(
        res.moe_load["prefill"][:, 0].sum() + res.moe_load["steps"][:, 0].sum())
    # every token's 2 slots (reduced top-k) are held: prefill 2 x 8 tokens
    assert (res.moe_load["prefill"][:, 0] == 2 * 8 * 2).all()


# --------------------------------------------------------------------------
# (d) YaRN as published
# --------------------------------------------------------------------------

def test_yarn_constants_are_pinned(ref):
    cfg = get_arch("deepseek-v2-lite-16b")
    y = cfg.rope_yarn
    assert y == YaRN(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    assert cfg.norm_eps == 1e-6 and cfg.norm_topk_prob is False
    assert yarn_correction_range(y, 64, 1e4) == (10, 23)
    assert softmax_mscale(y) == pytest.approx(1.58963, abs=1e-5)
    scale = softmax_mscale(y) / math.sqrt(128 + 64)
    assert scale == pytest.approx(0.114721, abs=1e-6)
    inv = np.asarray(rope_frequencies(64, 1e4, y))
    plain = np.asarray(rope_frequencies(64, 1e4))
    np.testing.assert_array_equal(inv[:11], plain[:11])
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    ref_inv, cos_sin, ref_scale = ref.yarn(
        json.loads((BENCH / "configs" / "deepseek-v2-lite.json").read_text()))
    np.testing.assert_allclose(inv, np.asarray(ref_inv), rtol=1e-6)
    assert cos_sin == 1.0 and ref_scale == pytest.approx(scale, rel=1e-6)


def test_interleaved_rope_pairs_even_and_odd_dims():
    """DeepSeek-V2's rotary part rotates (2i, 2i+1) together: the same as
    rotate-half applied to the de-interleaved vector."""
    x = jax.random.normal(jax.random.key(5), (1, 6, 2, 8))
    pos = jnp.arange(6)[None]
    got = apply_rope(x, pos, 1e4, interleaved=True)
    perm = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(apply_rope(perm, pos, 1e4)))


# --------------------------------------------------------------------------
# (e) the prefill head at the last position only
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["deepseek-7b", "deepseek-v2-lite-16b",
                                  "qwen3-moe-235b-a22b", "mamba2-780m"])
def test_prefill_last_logits_equal_forward_last_row(name):
    """Float32 on both sides; a (B,1,D) and a (B,S,D) head product may be
    blocked differently, so they agree to float32 rounding (1e-6)."""
    cfg = reduced(get_arch(name))
    params = lm.init_params(cfg, jax.random.key(0))
    batch = make_batch(cfg, 2, 16, seed=4)
    full, _, _ = lm.forward(params, cfg, batch, dtype=jnp.float32)
    last, _ = lm.prefill(params, cfg, batch, dtype=jnp.float32)
    assert last.shape == (2, 1, cfg.vocab_padded)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, -1:]),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# (f) what did not change
# --------------------------------------------------------------------------

def _capacity_moe(p, x, cfg, capacity_factor=1.25, group_size=2048):
    """The capacity-einsum layer this repository ran before the
    held-expert layer (GShard grouped dispatch), for comparison: returns
    the output and which tokens lost no slot."""
    dtype = x.dtype
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    sg = min(group_size, t)
    g = t // sg
    cap = max(int(sg * k * capacity_factor / e), 4)
    xt = x.reshape(g, sg, d)
    logits = (xt @ p["router"].astype(dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)
    slot_flat = jax.nn.one_hot(idx, e, dtype=jnp.int32).reshape(g, sg * k, e)
    pos = ((jnp.cumsum(slot_flat, axis=1) - 1) * slot_flat).sum(-1).reshape(
        g, sg, k)
    keep = pos < cap
    gate_vals = gate_vals * keep.astype(jnp.float32)
    exp_oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)
    cap_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap, dtype=jnp.float32)
    combine = jnp.einsum("gske,gskc,gsk->gsec", exp_oh, cap_oh, gate_vals)
    dispatch = (combine > 0).astype(dtype)
    xe = jnp.einsum("gsec,gsd->gecd", dispatch, xt)
    h = (jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"]))
         * jnp.einsum("gecd,edf->gecf", xe, p["w_up"]))
    y = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    out = jnp.einsum("gsec,gecd->gsd", combine, y).reshape(b, s, d)
    return out, keep.all(-1).reshape(b, s)


def test_qwen3_moe_matches_the_capacity_layer_where_it_dropped_nothing():
    """Float32 sums of the same terms, associated differently: 1e-5."""
    cfg = reduced(get_arch("qwen3-moe-235b-a22b"))
    p = init_moe(jax.random.key(6), cfg)
    x = jax.random.normal(jax.random.key(7), (2, 32, cfg.d_model))
    old, kept = _capacity_moe(p, x, cfg)
    new, _ = moe_ffn(p, x, cfg)
    kept = np.asarray(kept)
    assert 0 < kept.sum() < kept.size  # some tokens were dropped there
    np.testing.assert_allclose(np.asarray(new)[kept], np.asarray(old)[kept],
                               rtol=1e-5, atol=1e-5)


def _rope_before(x, positions, theta):
    """``apply_rope`` as it stood before YaRN was added."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("name", sorted(n for n, c in ARCHS.items()
                                        if not c.n_experts
                                        and not c.use_mla))
def test_dense_architectures_rotate_bit_for_bit_as_before(name):
    cfg = reduced(get_arch(name))
    assert cfg.rope_yarn is None
    x = jax.random.normal(jax.random.key(8), (2, 12, 4, cfg.head_dim))
    pos = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    np.testing.assert_array_equal(
        np.asarray(apply_rope(x, pos, cfg.rope_theta, cfg.rope_yarn)),
        np.asarray(_rope_before(x, pos, cfg.rope_theta)))
