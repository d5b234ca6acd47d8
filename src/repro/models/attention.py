"""Attention: GQA with chunked (flash-style) softmax, KV caches, MLA.

Grouped-query attention never materializes repeated KV heads: scores are
computed with the (kv_head, group) factorization.  Long sequences go
through a double-scan online-softmax path (q-chunks outer, kv-chunks
inner) so the dry-run's compiled memory stays tile-sized instead of
O(S^2).

MLA (DeepSeek-V2) caches the compressed latent + shared rope key; decode
uses the *absorbed* formulation (w_uk folded into q, w_uv folded into the
output projection), which is the memory-bound GEMV shape the paper's
advisor classifies.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (Params, apply_mrope, apply_rope, dense_init, rmsnorm,
                     softmax_mscale)

NEG_INF = -1e30


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Params:
    if cfg.use_mla and not cross:
        return _init_mla(key, cfg)
    ks = jax.random.split(key, 5)
    d = cfg.d_model
    p = {
        "wq": dense_init(ks[0], d, cfg.q_dim),
        "wk": dense_init(ks[1], d, cfg.kv_dim),
        "wv": dense_init(ks[2], d, cfg.kv_dim),
        "wo": dense_init(ks[3], cfg.n_heads * cfg.head_dim, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.kv_dim,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.kv_dim,), jnp.float32)
    return p


def _init_mla(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6)
    d, r = cfg.d_model, cfg.kv_lora_rank
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    h = cfg.n_heads
    p = {
        "wkv_a": dense_init(ks[1], d, r + rope_d),
        "kv_norm": jnp.ones((r,), jnp.float32),
        "wkv_b": dense_init(ks[2], r, h * (nope + vd)),
        "wo": dense_init(ks[3], h * vd, d),
    }
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], d, cfg.q_lora_rank)
        p["q_norm"] = jnp.ones((cfg.q_lora_rank,), jnp.float32)
        p["wq_b"] = dense_init(ks[4], cfg.q_lora_rank, h * (nope + rope_d))
    else:
        p["wq"] = dense_init(ks[0], d, h * (nope + rope_d))
    return p


# --------------------------------------------------------------------------
# core attention math
# --------------------------------------------------------------------------

def _gqa_scores(q, k):
    """q: (B,Sq,KH,G,Dh), k: (B,Skv,KH,Dh) -> (B,KH,G,Sq,Skv)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k)


def _gqa_out(w, v):
    """w: (B,KH,G,Sq,Skv), v: (B,Skv,KH,Dh) -> (B,Sq,KH,G,Dh)."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", w, v)


def _sdpa_dense(q, k, v, q_pos, kv_pos, causal: bool, kv_len=None):
    """Unchunked softmax attention with GQA factorization.

    q: (B,Sq,KH,G,Dh); k,v: (B,Skv,KH,Dh); positions broadcast (B,S)."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = _gqa_scores(q, k).astype(jnp.float32) * scale
    mask = jnp.ones(s.shape[-2:], bool)
    if causal:
        mask = kv_pos[:, None, :] <= q_pos[:, :, None]      # (B,Sq,Skv)
        mask = mask[:, None, None]
    if kv_len is not None:
        valid = (jnp.arange(k.shape[1])[None, :] < kv_len[:, None])
        mask = mask & valid[:, None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return _gqa_out(w, v)


def _sdpa_flash(q, k, v, q_pos, kv_pos, causal: bool,
                q_chunk: int, kv_chunk: int):
    """Double-scan online-softmax attention (compiled memory = tiles)."""
    b, sq, kh, g, dh = q.shape
    skv = k.shape[1]
    assert sq % q_chunk == 0 and skv % kv_chunk == 0, (sq, skv)
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))

    nq = sq // q_chunk
    nk = skv // kv_chunk
    qr = q.reshape(b, nq, q_chunk, kh, g, dh).swapaxes(0, 1)
    qp = q_pos.reshape(b, nq, q_chunk).swapaxes(0, 1)
    kr = k.reshape(b, nk, kv_chunk, kh, dh).swapaxes(0, 1)
    vr = v.reshape(b, nk, kv_chunk, kh, dh).swapaxes(0, 1)
    kp = kv_pos.reshape(b, nk, kv_chunk).swapaxes(0, 1)

    def q_block(carry, qc):
        qi, qpi = qc

        def kv_block(state, kc):
            ki, vi, kpi = kc
            acc, m, l = state
            s = _gqa_scores(qi, ki).astype(jnp.float32) * scale
            if causal:
                mask = kpi[:, None, :] <= qpi[:, :, None]
                s = jnp.where(mask[:, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + _gqa_out(
                p.astype(qi.dtype), vi).astype(jnp.float32).transpose(
                    0, 2, 3, 1, 4)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, kh, g, q_chunk, dh), jnp.float32)
        m0 = jnp.full((b, kh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, q_chunk), jnp.float32)
        (acc, _, l), _ = jax.lax.scan(kv_block, (acc0, m0, l0), (kr, vr, kp))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).transpose(0, 3, 1, 2, 4)
        return carry, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_block, None, (qr, qp))        # (nq,B,qc,KH,G,Dh)
    return outs.swapaxes(0, 1).reshape(b, sq, kh, g, dh)


def sdpa(q, k, v, q_pos, kv_pos, *, causal: bool, kv_len=None,
         q_chunk: int = 512, kv_chunk: int = 1024):
    """Dispatch dense vs flash by size; shapes as in _sdpa_dense."""
    sq, skv = q.shape[1], k.shape[1]
    if (sq > q_chunk and sq % q_chunk == 0 and skv % kv_chunk == 0
            and kv_len is None):
        return _sdpa_flash(q, k, v, q_pos, kv_pos, causal, q_chunk, kv_chunk)
    return _sdpa_dense(q, k, v, q_pos, kv_pos, causal, kv_len)


# --------------------------------------------------------------------------
# GQA attention layer (standard path)
# --------------------------------------------------------------------------

def _project_qkv(p: Params, x, kv_x, cfg: ModelConfig):
    dtype = x.dtype
    q = x @ p["wq"].astype(dtype)
    k = kv_x @ p["wk"].astype(dtype)
    v = kv_x @ p["wv"].astype(dtype)
    if "bq" in p:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    b, sq = x.shape[:2]
    skv = kv_x.shape[1]
    q = q.reshape(b, sq, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, skv, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, skv, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _rope_qk(q, k, q_pos, kv_pos, cfg: ModelConfig):
    if cfg.rope_kind == "none":
        return q, k
    if cfg.rope_kind == "mrope":
        return (apply_mrope(q, q_pos, cfg.rope_theta, cfg.mrope_sections),
                apply_mrope(k, kv_pos, cfg.rope_theta, cfg.mrope_sections))
    return (apply_rope(q, q_pos, cfg.rope_theta, cfg.rope_yarn),
            apply_rope(k, kv_pos, cfg.rope_theta, cfg.rope_yarn))


def _scalar_pos(positions, cfg: ModelConfig):
    """The (B,S) stream used for causal masking (mrope uses temporal)."""
    return positions[0] if cfg.rope_kind == "mrope" else positions


def attention(p: Params, x, cfg: ModelConfig, *, positions,
              cache: Optional[Dict] = None, cache_index=None,
              kv_x=None, kv_positions=None, causal: bool = True
              ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Attention in all modes.

    train/prefill: cache=None -> full self-attention (returns fresh cache
      when cache_index == 'prefill').
    decode: cache given + cache_index (B,) -> one-step attention against
      the cache; cache updated in place.
    cross: kv_x given -> encoder-decoder attention (no causal mask).
    """
    if cfg.use_mla and kv_x is None:
        return mla_attention(p, x, cfg, positions=positions, cache=cache,
                             cache_index=cache_index)
    b, sq, _ = x.shape
    group = cfg.n_heads // cfg.n_kv_heads

    if kv_x is not None:                                     # cross-attention
        k, v = make_cross_kv(p, kv_x, cfg)
        out = cross_attend(p, x, cfg, (k, v),
                           _scalar_pos(positions, cfg), kv_positions)
        return out, {"ck": k, "cv": v}
    elif cache is None:                                      # train / prefill
        q, k, v = _project_qkv(p, x, x, cfg)
        q, k = _rope_qk(q, k, positions, positions, cfg)
        new_cache = {"k": k, "v": v}
        q = q.reshape(b, sq, cfg.n_kv_heads, group, cfg.head_dim)
        qpos = _scalar_pos(positions, cfg)
        out = sdpa(q, k, v, qpos, qpos, causal=causal)
        out = out.reshape(b, sq, cfg.n_heads * cfg.head_dim)
        return out @ p["wo"].astype(x.dtype), new_cache
    else:                                                    # decode
        q, k, v = _project_qkv(p, x, x, cfg)
        kv_pos_new = _decode_positions(positions, cache_index, cfg)
        q, k = _rope_qk(q, k, positions, kv_pos_new, cfg)
        if cache["k"].dtype == jnp.int8:
            # quantized KV cache: per-(position, head) scales (beyond-paper
            # memory-term optimization; see EXPERIMENTS.md §Perf)
            cache = _int8_cache_update(cache, k, v, cache_index)
            ck = (cache["k"].astype(x.dtype)
                  * cache["k_scale"][..., None].astype(x.dtype))
            cv = (cache["v"].astype(x.dtype)
                  * cache["v_scale"][..., None].astype(x.dtype))
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
            cache = {**cache, "k": ck, "v": cv}
            ck = ck.astype(x.dtype)
            cv = cv.astype(x.dtype)
        q = q.reshape(b, sq, cfg.n_kv_heads, group, cfg.head_dim)
        if cfg.decode_attention_impl == "registry" and sq == 1:
            # single-token decode through the registered flash-decode
            # EngineOp: the dispatcher's memoized §6 Advice routes the
            # per-layer cache scan (engine='auto' -> vector on this
            # memory-bound shape), identical numerics to the dense path
            from ..kernels.attention.ops import decode_attention
            out = decode_attention(q[:, 0], ck, cv, cache_index + sq,
                                   engine=cfg.decode_attention_engine)
            out = out[:, None]
        else:
            kv_len = jnp.full((b,), cache_index + sq, jnp.int32)
            kv_pos = jnp.broadcast_to(jnp.arange(ck.shape[1])[None],
                                      (b, ck.shape[1]))
            qpos = _scalar_pos(positions, cfg)
            out = _sdpa_dense(q, ck, cv, qpos, kv_pos, causal=True,
                              kv_len=kv_len)
    out = out.reshape(b, sq, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(x.dtype), cache


def make_cross_kv(p: Params, enc_out, cfg: ModelConfig):
    """Project encoder output to K/V once (cached across decode steps)."""
    dtype = enc_out.dtype
    b, se, _ = enc_out.shape
    k = enc_out @ p["wk"].astype(dtype)
    v = enc_out @ p["wv"].astype(dtype)
    if "bk" in p:
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    return (k.reshape(b, se, cfg.n_kv_heads, cfg.head_dim),
            v.reshape(b, se, cfg.n_kv_heads, cfg.head_dim))


def cross_attend(p: Params, x, cfg: ModelConfig, kv, q_pos, kv_pos):
    dtype = x.dtype
    b, sq, _ = x.shape
    group = cfg.n_heads // cfg.n_kv_heads
    q = x @ p["wq"].astype(dtype)
    if "bq" in p:
        q = q + p["bq"].astype(dtype)
    q = q.reshape(b, sq, cfg.n_kv_heads, group, cfg.head_dim)
    k, v = kv
    out = _sdpa_dense(q, k.astype(dtype), v.astype(dtype), q_pos, kv_pos,
                      causal=False)
    out = out.reshape(b, sq, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(dtype)


def _decode_positions(positions, cache_index, cfg: ModelConfig):
    return positions


def make_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16
               ) -> Dict:
    if cfg.use_mla:
        lat_dtype = jnp.bfloat16 if dtype == jnp.int8 else dtype
        return {
            "latent": jnp.zeros((batch, max_len, cfg.kv_lora_rank),
                                lat_dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), lat_dtype),
        }
    c = {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
    }
    if dtype == jnp.int8:
        c["k_scale"] = jnp.zeros((batch, max_len, cfg.n_kv_heads),
                                 jnp.float32)
        c["v_scale"] = jnp.zeros((batch, max_len, cfg.n_kv_heads),
                                 jnp.float32)
    return c


def _int8_cache_update(cache: Dict, k, v, cache_index) -> Dict:
    """Quantize new K/V rows with per-(position, head) scales."""
    def q(x):
        scale = jnp.max(jnp.abs(x), axis=-1) / 127.0          # (B,S,KH)
        scale = jnp.maximum(scale, 1e-8)
        xq = jnp.clip(jnp.round(x / scale[..., None]), -127, 127)
        return xq.astype(jnp.int8), scale.astype(jnp.float32)

    kq, ks = q(k.astype(jnp.float32))
    vq, vs = q(v.astype(jnp.float32))
    upd = lambda buf, val: jax.lax.dynamic_update_slice_in_dim(
        buf, val.astype(buf.dtype), cache_index, axis=1)
    return {"k": upd(cache["k"], kq), "v": upd(cache["v"], vq),
            "k_scale": upd(cache["k_scale"], ks),
            "v_scale": upd(cache["v_scale"], vs)}


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# --------------------------------------------------------------------------

def _mla_q(p: Params, x, cfg: ModelConfig):
    dtype = x.dtype
    if cfg.q_lora_rank:
        ql = rmsnorm(p["q_norm"], x @ p["wq_a"].astype(dtype), cfg.norm_eps)
        q = ql @ p["wq_b"].astype(dtype)
    else:
        q = x @ p["wq"].astype(dtype)
    b, s = x.shape[:2]
    q = q.reshape(b, s, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
    return q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]


def _mla_rope(x, positions, cfg: ModelConfig):
    """DeepSeek-V2's rotary part: pairs (2i, 2i+1), YaRN where stated."""
    return apply_rope(x, positions, cfg.rope_theta, cfg.rope_yarn,
                      interleaved=True)


def mla_attention(p: Params, x, cfg: ModelConfig, *, positions,
                  cache=None, cache_index=None):
    """MLA: latent-compressed KV.  Prefill caches (latent, k_rope); decode
    runs the absorbed formulation entirely in latent space.  The logits
    are scaled by 1/sqrt(nope + rope) times YaRN's mscale squared."""
    dtype = x.dtype
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim

    kv_a = x @ p["wkv_a"].astype(dtype)                     # (B,S,r+rd)
    latent = rmsnorm(p["kv_norm"], kv_a[..., :r], cfg.norm_eps)
    k_rope_raw = kv_a[..., r:].reshape(b, s, 1, rd)

    q_nope, q_rope = _mla_q(p, x, cfg)
    scale = softmax_mscale(cfg.rope_yarn) / jnp.sqrt(
        jnp.asarray(nope + rd, jnp.float32))

    if cache is None:                                        # train / prefill
        k_rope = _mla_rope(k_rope_raw, positions, cfg)
        q_rope = _mla_rope(q_rope, positions, cfg)
        kv = latent @ p["wkv_b"].astype(dtype)               # (B,S,H*(nope+vd))
        kv = kv.reshape(b, s, h, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        sc = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkod->bhqk", q_rope, k_rope)
              ).astype(jnp.float32) * scale
        mask = positions[:, None, :] <= positions[:, :, None]
        sc = jnp.where(mask[:, None], sc, NEG_INF)
        w = jax.nn.softmax(sc, axis=-1).astype(dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", w, v)
        new_cache = {"latent": latent, "k_rope": k_rope.squeeze(2)}
        out = out.reshape(b, s, h * vd)
        return out @ p["wo"].astype(dtype), new_cache

    # ---- decode: absorbed path ----
    k_rope = _mla_rope(k_rope_raw, positions, cfg)
    q_rope = _mla_rope(q_rope, positions, cfg)
    lat = jax.lax.dynamic_update_slice_in_dim(
        cache["latent"], latent.astype(cache["latent"].dtype), cache_index,
        axis=1)
    kr = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope.squeeze(2).astype(cache["k_rope"].dtype),
        cache_index, axis=1)
    cache = {"latent": lat, "k_rope": kr}
    wkv_b = p["wkv_b"].astype(dtype).reshape(r, h, nope + vd)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
    # absorb: q' = q_nope @ w_uk  -> score against the latent directly
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)      # (B,1,H,r)
    latf = lat.astype(dtype)
    sc = (jnp.einsum("bqhr,bkr->bhqk", q_lat, latf)
          + jnp.einsum("bqhd,bkd->bhqk", q_rope, kr.astype(dtype))
          ).astype(jnp.float32) * scale
    kv_len = cache_index + s
    valid = jnp.arange(lat.shape[1])[None, :] < kv_len
    sc = jnp.where(valid[:, None, None], sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1).astype(dtype)
    out_lat = jnp.einsum("bhqk,bkr->bqhr", w, latf)          # (B,1,H,r)
    out = jnp.einsum("bqhr,rhd->bqhd", out_lat, w_uv)        # (B,1,H,vd)
    out = out.reshape(b, s, h * vd)
    return out @ p["wo"].astype(dtype), cache
