"""Plain reference of deepseek-llm-7b-base (a Llama-architecture decoder).

Follows the published ``config.json`` and modelling code: token
embedding; per layer RMSNorm, multi-head attention with rotary position
embeddings (rotate-half, theta ``rope_theta``) under a causal mask, the
output projection and a residual; RMSNorm, a SwiGLU MLP and a residual;
a final RMSNorm and an untied head.  Float32 at HIGHEST matmul
precision, one sequence at a time, layer by layer.  Nothing here
imports the program.

``make_weights`` makes the random weights of a run from its seed, in
float32; the benchmark hands these same arrays to the program.
"""
import jax
import jax.numpy as jnp


def sizes(cfg):
    d = cfg["hidden_size"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (d, h, kh, cfg.get("head_dim") or d // h,
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def make_weights(cfg, key):
    """Weights as (in, out) matrices stacked over layers, float32:
    projections N(0, 1/fan_in), embedding and head N(0, 0.02^2), norm
    gains 1 + N(0, 0.1^2)."""
    d, h, kh, dh, ff, v, n = sizes(cfg)
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def gain(shape):
        return 1.0 + normal(shape, 0.1)

    return {
        "embed": normal((v, d), 0.02),
        "norm": gain((d,)),
        "head": normal((d, v), 0.02),
        "ln1": gain((n, d)), "ln2": gain((n, d)),
        "wq": normal((n, d, h * dh), d ** -0.5),
        "wk": normal((n, d, kh * dh), d ** -0.5),
        "wv": normal((n, d, kh * dh), d ** -0.5),
        "wo": normal((n, h * dh, d), (h * dh) ** -0.5),
        "w_gate": normal((n, d, ff), d ** -0.5),
        "w_up": normal((n, d, ff), d ** -0.5),
        "w_down": normal((n, ff, d), ff ** -0.5),
    }


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (T, heads, dh); rotate-half over positions 0..T-1."""
    t, dh = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def logits(cfg, w, tokens, first: int, cast=lambda a: a):
    """Logits (count, vocab) at positions ``first``.. of one sequence.

    ``cast`` is applied to both operands of every matrix product (the
    identity for the reference; a lower precision for its control).
    """
    d, h, kh, dh, ff, v, n = sizes(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm = lambda a, b: jnp.matmul(cast(a), cast(b), precision="highest")
    t = tokens.shape[0]
    x = w["embed"][tokens]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(n):
        hx = _rms(x, w["ln1"][i], eps)
        q = _rope(mm(hx, w["wq"][i]).reshape(t, h, dh), theta)
        k = _rope(mm(hx, w["wk"][i]).reshape(t, kh, dh), theta)
        val = mm(hx, w["wv"][i]).reshape(t, kh, dh)
        k = jnp.repeat(k, h // kh, axis=1)
        val = jnp.repeat(val, h // kh, axis=1)
        s = jnp.einsum("qhd,khd->hqk", cast(q), cast(k),
                       precision="highest") / jnp.sqrt(jnp.float32(dh))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", cast(p), cast(val),
                       precision="highest").reshape(t, h * dh)
        x = x + mm(o, w["wo"][i])
        hx = _rms(x, w["ln2"][i], eps)
        x = x + mm(jax.nn.silu(mm(hx, w["w_gate"][i])) * mm(hx, w["w_up"][i]),
                   w["w_down"][i])
    x = _rms(x[first:], w["norm"], eps)
    return mm(x, w["head"])


def fp8(a):
    """``a`` as float8 e4m3 holds it, with one scale per tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


#: the precision below the configuration's bfloat16, for the control
CONTROLS = {"float8_e4m3": fp8}
