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

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float
               ) -> jnp.ndarray:
    """x: (..., S, H, Dh); positions: (..., S) int32."""
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)          # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
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
