"""Decode-engine correctness tier (ISSUE 7, satellite a).

Three independent references pin the scan-over-layers decode path:

* the **unrolled** graph -- ``DecodeEngine(unroll=True)`` lowers the
  same per-layer block as an unrolled loop instead of one ``lax.scan``
  over the stacked parameter pytree; both must produce identical
  greedy generations,
* a **pure-numpy fp64 oracle** of the tiny dense config -- embedding,
  RMSNorm, RoPE, GQA softmax attention, SwiGLU, LM head re-implemented
  with no JAX in the loop -- which the fp32 engine must match on both
  prefill logits and full greedy decode,
* **full recompute** -- every KV-cache incremental decode step must
  reproduce the logits of a fresh teacher-forced forward pass over the
  whole extended sequence.

Plus the serving invariant: padding a batch out to engine capacity
must not change any real row's argmax (continuous batching relies on
batch-size invariance of greedy decode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch, reduced
from repro.models import DecodeEngine, ModelConfig
from repro.models import lm

pytestmark = pytest.mark.model

jax.config.update("jax_platform_name", "cpu")

#: Tiny dense config the numpy oracle re-implements: GQA (2 query
#: heads over 1 KV head), RoPE, SwiGLU, untied LM head.
TINY = ModelConfig(name="tiny-dense", family="dense", n_layers=2,
                   d_model=16, n_heads=2, n_kv_heads=1, d_ff=32,
                   vocab=50, rope_theta=1e4, pad_vocab_to=8)


# --------------------------------------------------------------------------
# pure-numpy oracle (float64)
# --------------------------------------------------------------------------

def _np_rmsnorm(w, x, eps):
    var = np.mean(x * x, axis=-1, keepdims=True)
    return x / np.sqrt(var + eps) * w


def _np_rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    angles = pos[..., None] * freqs               # (B,S,half)
    cos = np.cos(angles)[..., None, :]            # (B,S,1,half)
    sin = np.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)


def _np_forward(params, cfg: ModelConfig, tokens: np.ndarray) -> np.ndarray:
    """fp64 logits for the full sequence (causal, no cache)."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    b, s = tokens.shape
    x = p["embed"][tokens]
    pos = np.broadcast_to(np.arange(s, dtype=np.float64), (b, s))
    g = cfg.n_heads // cfg.n_kv_heads
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], p["layers"])
        h = _np_rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q = (h @ lp["attn"]["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["attn"]["wk"]).reshape(b, s, cfg.n_kv_heads,
                                           cfg.head_dim)
        v = (h @ lp["attn"]["wv"]).reshape(b, s, cfg.n_kv_heads,
                                           cfg.head_dim)
        q, k = _np_rope(q, pos, cfg.rope_theta), _np_rope(k, pos,
                                                          cfg.rope_theta)
        q = q.reshape(b, s, cfg.n_kv_heads, g, cfg.head_dim)
        sc = np.einsum("bqhgd,bkhd->bhgqk", q, k) / np.sqrt(cfg.head_dim)
        causal = pos[:, None, :] <= pos[:, :, None]          # (B,Sq,Skv)
        sc = np.where(causal[:, None, None], sc, -np.inf)
        sc = sc - sc.max(axis=-1, keepdims=True)
        w = np.exp(sc)
        w = w / w.sum(axis=-1, keepdims=True)
        out = np.einsum("bhgqk,bkhd->bqhgd", w, v).reshape(b, s, -1)
        x = x + out @ lp["attn"]["wo"]
        h = _np_rmsnorm(lp["ln2"], x, cfg.norm_eps)
        gate = h @ lp["mlp"]["w_gate"]
        silu = gate / (1.0 + np.exp(-gate))
        x = x + (silu * (h @ lp["mlp"]["w_up"])) @ lp["mlp"]["w_down"]
    x = _np_rmsnorm(p["final_norm"], x, cfg.norm_eps)
    return x @ p["head"]


def _np_greedy(params, cfg: ModelConfig, prompt: np.ndarray, gen: int):
    """Greedy decode by full fp64 recompute each step."""
    seq = np.array(prompt)
    toks = []
    for _ in range(gen):
        logits = _np_forward(params, cfg, seq)[:, -1]
        nxt = np.argmax(logits, axis=-1).astype(np.int32)
        toks.append(nxt)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    return np.stack(toks, axis=1), logits


# --------------------------------------------------------------------------
# scanned == unrolled
# --------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [TINY, reduced(get_arch("mamba2-780m"))],
                         ids=["tiny-dense", "mamba2-reduced"])
def test_scanned_decode_matches_unrolled(cfg):
    """One lax.scan over the stacked layer block == the unrolled graph."""
    scanned = DecodeEngine(cfg, max_batch=2, prompt_len=4, max_gen=4,
                           dtype=jnp.float32, seed=0)
    unrolled = DecodeEngine(cfg, max_batch=2, prompt_len=4, max_gen=4,
                            dtype=jnp.float32, unroll=True,
                            params=scanned.params)
    batch = scanned.make_prompt_batch(seed=1)
    rs, ru = scanned.generate(batch), unrolled.generate(batch)
    np.testing.assert_array_equal(np.asarray(rs.tokens),
                                  np.asarray(ru.tokens))
    np.testing.assert_allclose(np.asarray(rs.logits),
                               np.asarray(ru.logits), atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------
# fp32 engine == fp64 numpy oracle
# --------------------------------------------------------------------------

def test_prefill_logits_match_numpy_oracle():
    eng = DecodeEngine(TINY, max_batch=2, prompt_len=6, max_gen=4,
                       dtype=jnp.float32, seed=0)
    batch = eng.make_prompt_batch(seed=2)
    logits, _ = eng.prefill(batch)
    want = _np_forward(eng.params, TINY,
                       np.asarray(batch["tokens"]))[:, -1]
    np.testing.assert_allclose(np.asarray(logits[:, -1]), want,
                               atol=1e-4, rtol=1e-3)


def test_greedy_decode_matches_numpy_oracle():
    """Scanned KV-cache decode == greedy fp64 full recompute."""
    eng = DecodeEngine(TINY, max_batch=2, prompt_len=6, max_gen=4,
                       dtype=jnp.float32, seed=0)
    batch = eng.make_prompt_batch(seed=2)
    result = eng.generate(batch)
    tokens, last_logits = _np_greedy(eng.params, TINY,
                                     np.asarray(batch["tokens"]), gen=4)
    np.testing.assert_array_equal(np.asarray(result.tokens), tokens)
    np.testing.assert_allclose(np.asarray(result.logits), last_logits,
                               atol=1e-4, rtol=1e-3)


# --------------------------------------------------------------------------
# incremental decode == full recompute
# --------------------------------------------------------------------------

def test_incremental_decode_matches_full_recompute():
    """Every cached decode step reproduces a fresh forward's logits."""
    prompt_len, gen = 6, 4
    eng = DecodeEngine(TINY, max_batch=2, prompt_len=prompt_len,
                       max_gen=gen, dtype=jnp.float32, seed=0)
    batch = eng.make_prompt_batch(seed=3)
    logits, caches = eng.prefill(batch)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    seq = jnp.concatenate([batch["tokens"], tok], axis=1)
    for i in range(prompt_len, prompt_len + gen - 1):
        step_logits, caches = eng.decode_step(tok, caches, i)
        full, _, _ = lm.forward(eng.params, eng.cfg, {"tokens": seq},
                                dtype=jnp.float32, remat=False)
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(full[:, -1]),
                                   atol=1e-4, rtol=1e-3)
        tok = jnp.argmax(step_logits[:, 0], axis=-1)[:, None]
        seq = jnp.concatenate([seq, tok], axis=1)


# --------------------------------------------------------------------------
# greedy determinism across batch sizes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("small", [1, 2])
def test_padding_must_not_change_argmax(small):
    """A row's greedy tokens are invariant to co-batched padding rows."""
    eng = DecodeEngine(TINY, max_batch=4, prompt_len=6, max_gen=4,
                       dtype=jnp.float32, seed=0)
    batch4 = eng.make_prompt_batch(seed=5)
    sub = {k: v[:small] for k, v in batch4.items()}
    np.testing.assert_array_equal(
        np.asarray(eng.generate(batch4).tokens)[:small],
        np.asarray(eng.generate(sub).tokens))


# --------------------------------------------------------------------------
# weights cast once == cast on every use
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(ARCHS))
def test_cast_once_engine_matches_uncast_params(name):
    """A bf16 engine holds its weights cast once; its prefill and decode
    logits and greedy tokens are bit-identical to the model run on the
    uncast float32 tree, which casts every weight on use."""
    cfg = reduced(get_arch(name))
    params = lm.init_params(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, max_batch=2, prompt_len=8, max_gen=5,
                       dtype=jnp.bfloat16, params=params)
    ecfg = eng.cfg
    prefill = jax.jit(lambda p, b: lm.prefill(p, ecfg, b,
                                              dtype=jnp.bfloat16))
    step = jax.jit(lambda p, t, c, i: lm.decode_step(
        p, ecfg, t, c, i, dtype=jnp.bfloat16))

    def greedy(got, want):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        got_tok, want_tok = (jnp.argmax(x[:, -1], axis=-1)[:, None]
                             for x in (got, want))
        np.testing.assert_array_equal(np.asarray(got_tok),
                                      np.asarray(want_tok))
        return got_tok, want_tok

    batch = eng.make_prompt_batch(seed=1)
    got, got_caches = eng.prefill(batch)
    want, want_caches = prefill(params, batch)
    want_caches = lm.pad_caches(want_caches, eng.max_len)
    for i in range(eng.prompt_len, eng.prompt_len + 4):
        got_tok, want_tok = greedy(got, want)
        got, got_caches = eng.decode_step(got_tok, got_caches, i)
        want, want_caches = step(params, want_tok, want_caches,
                                 jnp.int32(i))
    greedy(got, want)


#: Leaves that rmsnorm, the SSM scan and the MoE router use in float32.
FLOAT32_LEAVES = {"ln1", "ln2", "ln_cross", "final_norm", "enc_norm",
                  "q_norm", "kv_norm", "norm", "a_log", "dt_bias", "d_skip",
                  "conv_x", "conv_x_b", "conv_bc", "conv_bc_b", "router"}


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_compute_params_casts_projections_and_keeps_norms(name):
    params = lm.init_params(reduced(get_arch(name)), jax.random.key(0))
    cast = lm.compute_params(params, jnp.bfloat16)
    for path, x in jax.tree_util.tree_leaves_with_path(cast):
        key = path[-1].key
        assert key in lm.COMPUTE_LEAVES | FLOAT32_LEAVES, key
        want = jnp.bfloat16 if key in lm.COMPUTE_LEAVES else jnp.float32
        assert x.dtype == want, (jax.tree_util.keystr(path), x.dtype)


def test_float32_engine_holds_the_arrays_it_was_given():
    params = lm.init_params(TINY, jax.random.key(0))
    assert lm.compute_params(params, jnp.float32) is params
    eng = DecodeEngine(TINY, max_batch=2, prompt_len=4, max_gen=3,
                       dtype=jnp.float32, params=params)
    assert eng.params is params
    # a bf16 engine's params, given to another bf16 engine, are kept
    bf16 = DecodeEngine(TINY, max_batch=2, prompt_len=4, max_gen=3,
                        dtype=jnp.bfloat16, params=params)
    again = DecodeEngine(TINY, max_batch=2, prompt_len=4, max_gen=3,
                         dtype=jnp.bfloat16, params=bf16.params)
    assert again.params is bf16.params


# --------------------------------------------------------------------------
# flash-decode reads the cache where it lies
# --------------------------------------------------------------------------

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it (scan
    and pjit bodies, cond branches), in program order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def test_decode_step_hands_flash_decode_the_cache_layout():
    """A deepseek-7b decode step passes each layer's K and V to the
    flash-decode kernel as (B, S, KH, Dh), with no transpose of a
    K/V-shaped array anywhere in the step (the relayout a head-major
    kernel needed was a full read and write of the cache a layer)."""
    cfg = reduced(get_arch("deepseek-7b"))
    eng = DecodeEngine(cfg, max_batch=2, prompt_len=8, max_gen=4,
                       dtype=jnp.bfloat16)
    caches = jax.eval_shape(eng.prefill, eng.make_prompt_batch())[1]
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(eng._step)(eng.params, tokens, caches,
                                      jnp.int32(eng.prompt_len))
    kv = (2, eng.max_len, cfg.n_kv_heads, cfg.head_dim)
    eqns = list(_eqns(jaxpr.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert kernels, "the step runs no flash-decode kernel"
    for e in kernels:
        assert [v.aval.shape for v in e.invars[2:4]] == [kv, kv]
    relaid = [e for e in eqns if e.primitive.name == "transpose"
              and any(sorted(v.aval.shape) == sorted(kv)
                      for v in e.invars)]
    assert not relaid, [(e.invars[0].aval, e.params) for e in relaid]
