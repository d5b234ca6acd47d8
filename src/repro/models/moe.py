"""Mixture-of-Experts FFN: a top-k router over all experts and a dropless
grouped product over the experts this layer holds.

The router scores every one of ``n_experts`` (softmax and top-k in
float32, its product at HIGHEST precision, as DeepSeek-V2's gate
computes it); the gates are the top-k probabilities, renormalised to sum
1 where ``norm_topk_prob``.  The layer holds the routed experts
``held_range`` = [start, stop) (all by default): a chip of an
expert-parallel deployment holds its share and computes that share's
part of the result, for every token-slot routed to it.  Nothing is
dropped and there is no capacity: the held slots are sorted by expert
into a buffer sized for the worst case, each expert's run padded to
whole row tiles, and one grouped product per projection
(``repro.kernels.expert_gmm``) runs over the tiles the routing filled.
Each token's part is then gathered back and weighted by its gates.
Shared experts run on every token.  Router z-loss and the load-balancing
aux loss (over all experts) are returned for the training loop, with
the load: held token-slots and the most any held expert took.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..kernels.expert_gmm import expert_gmm
from .config import ModelConfig
from .layers import Params, dense_init


#: the routed experts' weights, (E, D, F) or (E, F, D) per layer
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def init_moe(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 5)
    start, stop = cfg.held_range
    d, e, f = cfg.d_model, stop - start, cfg.moe_d_ff
    p = {
        "router": dense_init(ks[0], d, cfg.n_experts, scale=0.02),
        "w_gate": jax.random.normal(ks[1], (e, d, f), jnp.float32) / d**0.5,
        "w_up": jax.random.normal(ks[2], (e, d, f), jnp.float32) / d**0.5,
        "w_down": jax.random.normal(ks[3], (e, f, d), jnp.float32) / f**0.5,
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * cfg.moe_d_ff
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {"w_gate": dense_init(kk[0], d, fs),
                       "w_up": dense_init(kk[1], d, fs),
                       "w_down": dense_init(kk[2], fs, d)}
    return p


def row_tile(tokens: int, cfg: ModelConfig) -> int:
    """Rows of one grouped-product tile: twice the slots an expert takes
    on average, as a power of two in [16, 512] (a decode step's few
    slots a tile each; a prefill's thousands in MXU-sized tiles)."""
    mean = 2 * tokens * cfg.top_k / cfg.n_experts
    return int(min(512, max(16, 1 << max(0, int(mean - 1).bit_length()))))


def route(p: Params, xt: jnp.ndarray, cfg: ModelConfig):
    """Router logits (T, E) and the top-k gates and experts (T, k)."""
    logits = jnp.dot(xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return logits, gates, idx


def held_experts(p: Params, xt, gates, idx, cfg: ModelConfig, layer=None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of the layer for tokens ``xt`` (T, D)
    routed to ``idx`` with ``gates`` (T, k); and the load, int32
    [held token-slots, the most slots one held expert took].  With
    ``layer``, ``p``'s expert weights are a stack over layers (L, E, ...)
    and this layer's are at ``layer``; the kernel reads them in place."""
    t, k = idx.shape
    start, stop = cfg.held_range
    e = stop - start
    tm = row_tile(t, cfg)
    rows = -(-(t * min(k, e) + e * (tm - 1)) // tm) * tm   # worst case

    local = (idx - start).reshape(-1)                     # (T*k,)
    held = (local >= 0) & (local < e)
    onehot = (local[:, None] == jnp.arange(e)).astype(jnp.int32)
    counts = onehot.sum(0)                                # (E,)
    rank = ((jnp.cumsum(onehot, 0) - 1) * onehot).sum(1)  # within expert
    expert_tiles = -(-counts // tm)
    ends = jnp.cumsum(expert_tiles)
    row = jnp.where(held, (ends - expert_tiles)[jnp.clip(local, 0, e - 1)]
                    * tm + rank, rows)                    # rows: not held
    tile_expert = jnp.minimum(
        (jnp.arange(rows // tm)[:, None] >= ends).sum(1), e - 1
    ).astype(jnp.int32)
    tiles = ends[-1].astype(jnp.int32)

    token = jnp.arange(t * k, dtype=jnp.int32) // k
    row_token = jnp.full((rows,), t, jnp.int32).at[row].set(token,
                                                            mode="drop")
    xs = jnp.take(xt, row_token, axis=0, mode="fill", fill_value=0)

    dtype = xt.dtype
    w = {n: p[n].astype(dtype) for n in EXPERT_LEAVES}
    if layer is not None:
        w = {n: v.reshape(-1, *v.shape[2:]) for n, v in w.items()}
        tile_expert = tile_expert + layer * e
    h = (jax.nn.silu(expert_gmm(xs, w["w_gate"], tile_expert, tiles, tm))
         * expert_gmm(xs, w["w_up"], tile_expert, tiles, tm))
    y = expert_gmm(h, w["w_down"], tile_expert, tiles, tm)
    ys = jnp.take(y, row, axis=0, mode="fill", fill_value=0)
    gw = jnp.where(held, gates.reshape(-1), 0.0)
    out = (ys.reshape(t, k, -1).astype(jnp.float32)
           * gw.reshape(t, k, 1)).sum(1)
    load = jnp.stack([counts.sum(), counts.max()]).astype(jnp.int32)
    return out.astype(dtype), load


def moe_ffn(p: Params, x: jnp.ndarray, cfg: ModelConfig, layer=None
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B,S,D) -> (B,S,D), aux {aux_loss, z_loss, load}; ``layer`` as
    in :func:`held_experts`."""
    dtype = x.dtype
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits, gates, idx = route(p, xt, cfg)
    out, load = held_experts(p, xt, gates, idx, cfg, layer)

    if "shared" in p:
        sp = p["shared"]
        sg = xt @ sp["w_gate"].astype(dtype)
        su = xt @ sp["w_up"].astype(dtype)
        out = out + (jax.nn.silu(sg) * su) @ sp["w_down"].astype(dtype)

    # aux losses (Switch-style load balance + router z-loss)
    e = cfg.n_experts
    probs = jax.nn.softmax(logits, axis=-1)
    me = probs.mean(axis=0)                                          # (E,)
    ce = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1).mean(0)    # (E,)
    aux = (me * ce).sum() * e * cfg.router_aux_weight
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * 1e-3
    return out.reshape(b, s, d), {"aux_loss": aux, "z_loss": z,
                                  "load": load}
