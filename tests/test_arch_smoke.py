"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes and no NaNs; plus a decode-vs-prefill
consistency check per family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch, reduced
from repro.data.synthetic import make_batch
from repro.models import lm
from repro.models.config import ModelConfig

ARCH_IDS = sorted(ARCHS)


@pytest.fixture(scope="module")
def small_setup():
    cache = {}

    def build(name: str):
        if name not in cache:
            cfg = reduced(get_arch(name))
            params = lm.init_params(cfg, jax.random.key(0))
            cache[name] = (cfg, params)
        return cache[name]
    return build


@pytest.mark.parametrize("name", ARCH_IDS)
def test_forward_shapes_and_finite(small_setup, name):
    cfg, params = small_setup(name)
    b, s = 2, 64
    batch = make_batch(cfg, b, s, seed=1)
    logits, _, aux = lm.forward(params, cfg, batch, dtype=jnp.float32)
    assert logits.shape == (b, s, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits).all()), f"{name}: non-finite logits"
    for v in aux.values():
        assert bool(jnp.isfinite(v).all())


@pytest.mark.parametrize("name", ARCH_IDS)
def test_train_step_decreases_loss_is_finite(small_setup, name):
    cfg, params = small_setup(name)
    batch = make_batch(cfg, 2, 32, seed=2)
    loss, metrics = lm.loss_fn(params, cfg, batch, dtype=jnp.float32)
    assert bool(jnp.isfinite(loss)), f"{name}: loss {loss}"
    # gradient exists and is finite for every parameter
    grads = jax.grad(lambda p: lm.loss_fn(p, cfg, batch,
                                          dtype=jnp.float32)[0])(params)
    flat, _ = jax.tree.flatten(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in flat), name


@pytest.mark.parametrize("name", ARCH_IDS)
def test_decode_matches_forward(small_setup, name):
    """Teacher-forced decode step-by-step == full forward (same tokens)."""
    cfg, params = small_setup(name)
    if cfg.enc_dec:
        pytest.skip("enc-dec decode covered in test_encdec_decode")
    b, s = 1, 8
    batch = make_batch(cfg, b, s, seed=3)
    logits_full, _, _ = lm.forward(params, cfg, batch, dtype=jnp.float32)

    caches = lm.init_caches(cfg, b, max_len=16, dtype=jnp.float32)
    outs = []
    for t in range(s):
        tok = batch["tokens"][:, t:t + 1]
        if cfg.frontend == "vision" and t < cfg.frontend_len:
            # vision positions differ under the stub; skip strict check
            pass
        lg, caches = lm.decode_step(params, cfg, tok, caches,
                                    jnp.int32(t), dtype=jnp.float32)
        outs.append(lg[:, 0])
    got = jnp.stack(outs, axis=1)
    if cfg.frontend == "vision":
        got = got[:, cfg.frontend_len:]
        logits_full = logits_full[:, cfg.frontend_len:]
        pytest.skip("vlm decode path exercised; embeddings differ by design")
    np.testing.assert_allclose(np.asarray(got), np.asarray(logits_full),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("name", ARCH_IDS)
def test_registry_decode_matches_dense(small_setup, name):
    """Registry-dispatched flash-decode attention == the in-model dense
    path, layer by layer through a real teacher-forced decode.

    This is the serving engine's default configuration
    (``decode_attention_impl='registry'``): every layer's cache scan
    goes through the registered EngineOp and the dispatcher's §6
    Advice, and must be numerically interchangeable with the dense
    softmax path the training graph uses.
    """
    cfg, params = small_setup(name)
    if cfg.is_attention_free:
        pytest.skip("attention-free family: no decode-attention dispatch")
    if cfg.use_mla:
        pytest.skip("MLA decodes via the absorbed latent path, not the "
                    "registry op")
    b, s = 1, 6
    batch = make_batch(cfg, b, s, seed=5)
    variants = {}
    for impl in ("dense", "registry"):
        c = dataclasses.replace(cfg, decode_attention_impl=impl)
        caches = lm.init_caches(c, b, max_len=8, dtype=jnp.float32)
        outs = []
        for t in range(s):
            lg, caches = lm.decode_step(params, c,
                                        batch["tokens"][:, t:t + 1],
                                        caches, jnp.int32(t),
                                        dtype=jnp.float32)
            outs.append(lg[:, 0])
        variants[impl] = np.asarray(jnp.stack(outs, axis=1))
    np.testing.assert_allclose(variants["registry"], variants["dense"],
                               rtol=1e-4, atol=1e-4)


def test_registry_decode_forced_engines_agree():
    """Forcing the matrix variant changes the compute engine only --
    identical numerics through the same KV-cache memory path."""
    cfg = reduced(get_arch("deepseek-7b"))
    params = lm.init_params(cfg, jax.random.key(0))
    batch = make_batch(cfg, 1, 4, seed=6)
    outs = {}
    for engine in ("vector", "matrix"):
        c = dataclasses.replace(cfg, decode_attention_impl="registry",
                                decode_attention_engine=engine)
        caches = lm.init_caches(c, 1, max_len=8, dtype=jnp.float32)
        per_step = []
        for t in range(4):
            lg, caches = lm.decode_step(params, c,
                                        batch["tokens"][:, t:t + 1],
                                        caches, jnp.int32(t),
                                        dtype=jnp.float32)
            per_step.append(lg[:, 0])
        outs[engine] = np.asarray(jnp.stack(per_step, axis=1))
    np.testing.assert_allclose(outs["matrix"], outs["vector"],
                               rtol=1e-5, atol=1e-5)


def test_encdec_decode():
    """Prefill (1 token, fills cross KV) then teacher-forced decode matches
    the full forward pass."""
    cfg = reduced(get_arch("seamless-m4t-large-v2"))
    params = lm.init_params(cfg, jax.random.key(0))
    b, s = 1, 8
    batch = make_batch(cfg, b, s, seed=4)
    logits_full, _, _ = lm.forward(params, cfg, batch, dtype=jnp.float32)

    pre_batch = dict(batch, tokens=batch["tokens"][:, :1])
    lg0, caches = lm.prefill(params, cfg, pre_batch, dtype=jnp.float32)
    caches = lm.pad_caches(caches, max_len=16)
    outs = [lg0[:, 0]]
    for t in range(1, s):
        lg, caches = lm.decode_step(params, cfg, batch["tokens"][:, t:t + 1],
                                    caches, jnp.int32(t), dtype=jnp.float32)
        outs.append(lg[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(logits_full),
                               rtol=2e-2, atol=2e-2)


def test_param_counts_match_full_configs():
    """Full configs land near their published sizes (the configs' N feeds
    MODEL_FLOPS in the roofline)."""
    expect = {
        "zamba2-7b": (6e9, 9e9),
        "qwen2-vl-72b": (68e9, 76e9),
        "stablelm-12b": (11e9, 13.5e9),
        "mistral-nemo-12b": (11e9, 13.5e9),
        "deepseek-7b": (6e9, 7.5e9),
        # assignment pins kv=40 (MHA) -> 35.2B; the HF checkpoint's GQA
        # kv=8 would give 32.5B.  We follow the assignment (DESIGN.md §5).
        "qwen1.5-32b": (30e9, 36e9),
        "qwen3-moe-235b-a22b": (225e9, 245e9),
        "deepseek-v2-lite-16b": (14e9, 17e9),
        "mamba2-780m": (0.6e9, 0.95e9),
        "seamless-m4t-large-v2": (1.2e9, 2.7e9),
    }
    for name, (lo, hi) in expect.items():
        n = get_arch(name).param_count()
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"


def test_moe_active_params():
    cfg = get_arch("qwen3-moe-235b-a22b")
    active = cfg.active_param_count()
    assert 18e9 <= active <= 26e9, active / 1e9
