"""Shared building blocks: norms, rotary embeddings, SwiGLU, initializers.

All layers are pure functions over parameter pytrees (dicts of arrays).
Parameters are initialized in f32; compute happens in the caller-chosen
dtype, each weight cast to it on use.  A decode engine holds the weights
already cast (``lm.compute_params``), where that cast is a no-op;
training keeps f32 master weights and casts them here.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, jnp.ndarray]


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, scale: Optional[float] = None
               ) -> jnp.ndarray:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rmsnorm(w: jnp.ndarray, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w).astype(dtype)


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor 0.1 * mscale * ln(factor) + 1 (1 when
    nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(yarn, dim: int, theta: float) -> Tuple[int, int]:
    """The rotary pair indices [low, high] between which YaRN ramps from
    the original frequencies to the stretched ones: the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    def pair(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(pair(yarn.beta_fast)), 0),
            min(math.ceil(pair(yarn.beta_slow)), dim - 1))


def softmax_mscale(yarn) -> float:
    """The factor on attention logits: mscale_all_dim's magnitude factor,
    squared (1 without YaRN)."""
    if yarn is None or not yarn.mscale_all_dim:
        return 1.0
    return yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2


def rope_frequencies(head_dim: int, theta: float, yarn=None) -> jnp.ndarray:
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if yarn is None:
        return freqs
    low, high = yarn_correction_range(yarn, head_dim, theta)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn=None, interleaved: bool = False) -> jnp.ndarray:
    """x: (..., S, H, Dh); positions: (..., S) int32.

    Rotate-half over pairs (i, i + Dh/2); ``interleaved`` first gathers
    the pairs (2i, 2i+1) into that layout (DeepSeek-V2's rotary part),
    which the output keeps.  ``yarn`` stretches the frequencies and
    scales cos and sin by its mscale ratio."""
    half = x.shape[-1] // 2
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    freqs = rope_frequencies(x.shape[-1], theta, yarn)    # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
                sections: Tuple[int, ...]) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE.

    positions: (3, ..., S) -- temporal/height/width position ids.  The
    rotary half-dim is split into `sections` (t, h, w); each section takes
    its angle from the corresponding position stream.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_frequencies(x.shape[-1], theta)          # (half,)
    angle_streams = positions[..., None].astype(jnp.float32) * freqs
    # angle_streams: (3, ..., S, half); select per-section stream
    parts = []
    start = 0
    for idx, sec in enumerate(sections):
        parts.append(angle_streams[idx][..., start:start + sec])
        start += sec
    angles = jnp.concatenate(parts, axis=-1)              # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# feed-forward
# --------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": dense_init(k1, d_model, d_ff),
            "w_up": dense_init(k2, d_model, d_ff),
            "w_down": dense_init(k3, d_ff, d_model)}


def mlp(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    dtype = x.dtype
    g = x @ p["w_gate"].astype(dtype)
    u = x @ p["w_up"].astype(dtype)
    return (jax.nn.silu(g) * u) @ p["w_down"].astype(dtype)
