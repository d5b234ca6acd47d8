"""Plain reference of DeepSeek-V2-Lite, the share one chip holds.

Follows the published ``config.json`` and modelling code
(``DeepseekV2ForCausalLM``): token embedding; per layer RMSNorm and
multi-head latent attention (MLA) in its plain form: q from the hidden
state (no q-LoRA), the compressed latent and a shared rotary key from
``kv_a``, the latent RMS-normed and decompressed through ``kv_b`` into
per-head k_nope and v; the rotary parts de-interleaved (pairs 2i, 2i+1)
then rotated by rotate-half at YaRN frequencies; softmax scale
1/sqrt(192) times YaRN's mscale squared, a causal mask, the output
projection and a residual.  Then RMSNorm and, in the first
``first_k_dense_replace`` layers, a SwiGLU MLP; in the others the MoE:
the router scores all published ``n_routed_experts`` (softmax), takes
the top ``num_experts_per_tok`` probabilities as gates (no
renormalisation, times ``routed_scaling_factor``), and the layer adds,
for every token, each held expert's SwiGLU output times its gate (0
where the token is not routed to it), computed densely, plus the shared
experts' SwiGLU on every token.  The held experts are 0 to
``n_routed_experts`` - 1 of the configuration (expert rank 0).  A final
RMSNorm and an untied head.  Float32 at HIGHEST matmul precision, one
sequence at a time, layer by layer.  Nothing here imports the program.

``make_weights`` makes the random weights of a run from its seed, in
float32; the benchmark hands these same arrays to the program.
"""
import math

import jax
import jax.numpy as jnp


def sizes(cfg):
    """(d, heads, nope, rope, v, rank, dense ff, expert ff, shared ff,
    routed experts published, held, top-k, vocab, layers, dense layers)"""
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            cfg["published"]["n_routed_experts"], cfg["n_routed_experts"],
            cfg["num_experts_per_tok"], cfg["vocab_size"],
            cfg["num_hidden_layers"], cfg["first_k_dense_replace"])


def make_weights(cfg, key):
    """Weights as (in, out) matrices stacked over layers, float32:
    projections and router N(0, 1/fan_in), embedding and head
    N(0, 0.02^2), norm gains 1 + N(0, 0.1^2)."""
    d, h, nope, rope, vd, r, ff, fe, fs, e, held, k, v, n, nd = sizes(cfg)
    nm = n - nd
    ks = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def gain(shape):
        return 1.0 + normal(shape, 0.1)

    return {
        "embed": normal((v, d), 0.02),
        "norm": gain((d,)),
        "head": normal((d, v), 0.02),
        "ln1": gain((n, d)), "ln2": gain((n, d)),
        "wq": normal((n, d, h * (nope + rope)), d ** -0.5),
        "wkv_a": normal((n, d, r + rope), d ** -0.5),
        "kv_norm": gain((n, r)),
        "wkv_b": normal((n, r, h * (nope + vd)), r ** -0.5),
        "wo": normal((n, h * vd, d), (h * vd) ** -0.5),
        "w_gate": normal((nd, d, ff), d ** -0.5),
        "w_up": normal((nd, d, ff), d ** -0.5),
        "w_down": normal((nd, ff, d), ff ** -0.5),
        "router": normal((nm, d, e), d ** -0.5),
        "e_gate": normal((nm, held, d, fe), d ** -0.5),
        "e_up": normal((nm, held, d, fe), d ** -0.5),
        "e_down": normal((nm, held, fe, d), fe ** -0.5),
        "s_gate": normal((nm, d, fs), d ** -0.5),
        "s_up": normal((nm, d, fs), d ** -0.5),
        "s_down": normal((nm, fs, d), fs ** -0.5),
    }


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(cfg):
    """(inv_freq (rope/2,), cos/sin factor, softmax scale) as the
    published ``DeepseekV2YarnRotaryEmbedding`` and attention set them."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg["rope_scaling"]
    f, orig = rs["factor"], rs["original_max_position_embeddings"]

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / ((high + 0.001 if high == low else high) - low), 0, 1)
    inv = extra / f * ramp + extra * (1.0 - ramp)
    cs = _yarn_mscale(f, rs["mscale"]) / _yarn_mscale(f, rs["mscale_all_dim"])
    m = _yarn_mscale(f, rs["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5 * m * m
    return inv, cs, scale


def _rope(x, inv, cs):
    """x: (T, heads, dim): pairs (2i, 2i+1) de-interleaved, then
    rotate-half over positions 0..T-1."""
    t, dim = x.shape[0], x.shape[-1]
    x = x.reshape(*x.shape[:-1], dim // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = (jnp.cos(jnp.concatenate([ang, ang], -1)) * cs)[:, None]
    sin = (jnp.sin(jnp.concatenate([ang, ang], -1)) * cs)[:, None]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def logits(cfg, w, tokens, first: int, cast=lambda a: a):
    """Logits (count, vocab) at positions ``first``.. of one sequence.

    ``cast`` is applied to both operands of every matrix product (the
    identity for the reference; a lower precision for its control).
    """
    d, h, nope, rope, vd, r, ff, fe, fs, e, held, k, v, n, nd = sizes(cfg)
    eps = cfg["rms_norm_eps"]
    inv, cs, scale = yarn(cfg)
    mm = lambda a, b: jnp.matmul(cast(a), cast(b), precision="highest")
    swiglu = lambda x, g, u, dn: mm(jax.nn.silu(mm(x, g)) * mm(x, u), dn)
    t = tokens.shape[0]
    x = w["embed"][tokens]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(n):
        hx = _rms(x, w["ln1"][i], eps)
        q = mm(hx, w["wq"][i]).reshape(t, h, nope + rope)
        kv_a = mm(hx, w["wkv_a"][i])
        c = _rms(kv_a[:, :r], w["kv_norm"][i], eps)
        k_pe = _rope(kv_a[:, r:].reshape(t, 1, rope), inv, cs)
        kv = mm(c, w["wkv_b"][i]).reshape(t, h, nope + vd)
        qf = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, cs)],
                             -1)
        kf = jnp.concatenate([kv[..., :nope],
                              jnp.broadcast_to(k_pe, (t, h, rope))], -1)
        s = jnp.einsum("qhd,khd->hqk", cast(qf), cast(kf),
                       precision="highest") * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", cast(p), cast(kv[..., nope:]),
                       precision="highest").reshape(t, h * vd)
        x = x + mm(o, w["wo"][i])
        hx = _rms(x, w["ln2"][i], eps)
        if i < nd:
            x = x + swiglu(hx, w["w_gate"][i], w["w_up"][i], w["w_down"][i])
            continue
        j = i - nd
        probs = jax.nn.softmax(mm(hx, w["router"][j]), axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        gates = jnp.zeros((t, e)).at[jnp.arange(t)[:, None], idx].set(top)
        gates = gates * cfg["routed_scaling_factor"]
        y = swiglu(hx, w["s_gate"][j], w["s_up"][j], w["s_down"][j])
        for ex in range(held):
            y = y + gates[:, ex:ex + 1] * swiglu(
                hx, w["e_gate"][j, ex], w["e_up"][j, ex], w["e_down"][j, ex])
        x = x + y
    x = _rms(x[first:], w["norm"], eps)
    return mm(x, w["head"])


def fp8(a):
    """``a`` as float8 e4m3 holds it, with one scale per tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


#: the precision below the configuration's bfloat16, for the control
CONTROLS = {"float8_e4m3": fp8}
